"""Speculative-decoding rollout engine (paper Fig. 3): one round loop,
``SpecEngine.serve``, with two draft sources.

Host side: the length-aware budget policy (length_policy.py +
budget.py), slot admission (scheduler.py), per-request output assembly
and rollout statistics. Each round the host solves one (B,) budget
vector; where the drafts come from decides the rest of the round:

* device drafts (drafter scopes ``problem``/``global``) — the ENTIRE
  round is one jitted program (core/fused_round.py): suffix-match
  propose over the packed forest, verify-block assembly, model forward +
  acceptance, cache commit, EOS/limit emit scan, and the next round's
  session state (heads / context tails / emitted counts live on device
  in a ``RoundState`` between rounds). The host uploads the budgets and
  downloads one packed per-row result;
* host drafts (scope ``problem+request``, whose per-request tree grows
  on the host) — per-row sessions propose, the host builds the block,
  one jitted verify (the same ``verify_step``) runs, and the host scans
  the emitted tokens.

``serve`` feeds a fixed pool of device slots from an admission queue
ordered longest-predicted-first. A finished row's slot is re-prefilled
with the next pending request, and rounds are double-buffered: while
round *t* executes on device, the host observes finished rollouts and
pre-solves round *t+1* budgets. ``generate`` is the batch wrapper: with
one slot per prompt (its default) no slot is ever recycled, every row
steps together and finished rows ride along idle until the stragglers
drain — lock-step rollout, the Fig. 1 batch collapse.

The verify block is padded to a *bucketed* size so each bucket compiles
once: per-row budgets stay ragged (positions past a row's budget are
auto-rejected), matching the paper's per-request budget allocation while
keeping XLA shapes static. Latency is accounted with the paper's model
(Eq. 2): t = c_base·N_fwd + c_tok·N_toks + C, using *proposed* token
counts (what a ragged-batching serving engine would execute), plus
measured wall-clock on this host.

Greedy (T=0) speculative verification is lossless: outputs are
token-identical to plain decoding whatever the pool size or draft
source (tests/test_scheduler.py, tests/test_fused_round.py).
"""

from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.budget import LatencyModel, solve_budgets
from repro.core.drafter import DrafterConfig, SuffixDrafter
from repro.core.fused_round import (
    advance_feed,
    build_fused_round,
    forget_matches,
    make_state,
    matcher_feeds,
    unpack_round_out,
    verify_step,
)
from repro.core.length_policy import (
    CLASS_NAMES,
    LengthPolicy,
    LengthPolicyConfig,
)
from repro.core.scheduler import CANCELLED, EXPIRED, Request, SlotScheduler
from repro.obs.flight import NULL_FLIGHT
from repro.core.verify import sample_token_rows
from repro.models import model as M


@dataclass
class EngineConfig:
    max_draft: int = 16  # hard cap K on draft tokens per round
    block_buckets: Tuple[int, ...] = (0, 4, 8, 16)  # draft sizes compiled
    temperature: float = 0.0
    max_new_tokens: int = 256
    eos_token: int = 1
    use_budget_solver: bool = True  # Eq. 7/9 budgets (vs class-only)
    spec_enabled: bool = True  # False = plain AR decode (baseline)
    unlimited_budget: bool = False  # ablation: always max_draft
    attn_impl: str = "xla"
    cache_headroom: int = 64


@dataclass
class RolloutStats:
    n_rounds: int = 0  # verify rounds (continuous: pool rounds = makespan)
    n_fwd: int = 0  # forward passes (prefills + verify rounds)
    n_toks_proposed: int = 0  # Σ block tokens over active rows (ragged)
    n_toks_emitted: int = 0
    n_drafted: int = 0
    n_accepted: int = 0
    wall_time_s: float = 0.0
    # Round-path host accounting: host seconds spent on per-round
    # bookkeeping (budget solve, block/dispatch assembly, consume-side
    # bookkeeping — device waits excluded) and the number of
    # host↔device array crossings.
    host_time_s: float = 0.0
    n_h2d: int = 0
    n_d2h: int = 0
    per_row_rounds: Optional[np.ndarray] = None
    per_row_emitted: Optional[np.ndarray] = None
    effective_batch: List[int] = field(default_factory=list)
    round_accepts: List[float] = field(default_factory=list)

    @property
    def acceptance_per_round(self) -> float:
        return self.n_accepted / max(self.n_rounds, 1)

    @property
    def mean_accepted_per_fwd(self) -> float:
        return self.n_toks_emitted / max(self.n_fwd, 1)

    def modeled_latency(self, lat: LatencyModel) -> float:
        return lat.t_total(self.n_fwd, self.n_toks_proposed)


def _emit_scan(
    cand: np.ndarray,  # (B, K+1) candidate emissions per row
    n_new: np.ndarray,  # (B,) accepted + 1 (tokens the verify produced)
    remaining: np.ndarray,  # (B,) max_new - emitted before this round
    eos: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized EOS/token-limit scan (append-then-check semantics).

    Each row appends its candidates in order, stopping after the first
    EOS or once the emitted count reaches the row's limit (the token
    that trips either condition is still appended). Returns

      n_take — tokens to append this round,
      alive  — rows that neither hit EOS nor their limit.

    Rows outside the caller's active mask produce garbage (n_new is 1
    there); the caller must AND ``alive`` with its own mask.
    """
    B, K1 = cand.shape
    idx = np.arange(K1)[None, :]
    valid = idx < n_new[:, None]
    eos_hit = (cand == eos) & valid
    has_eos = eos_hit.any(axis=1)
    first_eos = np.where(has_eos, eos_hit.argmax(axis=1), K1)
    cap = np.maximum(remaining, 1)  # append-then-check: >=1 lands
    n_take = np.minimum(np.minimum(n_new, cap),
                        np.where(has_eos, first_eos + 1, K1 + 1))
    last = cand[np.arange(B), np.maximum(n_take - 1, 0)]
    alive = (n_take == n_new) & (last != eos) & (n_take < remaining)
    return n_take.astype(np.int64), alive


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _prompt_bucket(n: int) -> int:
    """Prompt pad width (16-multiples): compiled prefill variants are
    keyed on (Tp, max_len), so the bucket bounds their number."""
    return max(16, _round_up(n, 16))


def _cache_bucket(n: int) -> int:
    """Cache length rounding (64-multiples), for the same reason."""
    return _round_up(n, 64)


def _params_device(params):
    """The one device ``params`` live on, or None when they span several
    devices (or are not device arrays yet)."""
    leaves = jax.tree.leaves(params)
    if not leaves or not isinstance(leaves[0], jax.Array):
        return None
    devs = leaves[0].devices()
    return next(iter(devs)) if len(devs) == 1 else None


def _as_max_new_array(mn, B: int) -> np.ndarray:
    if isinstance(mn, (list, tuple, np.ndarray)):
        arr = np.asarray(mn, np.int64)
        if arr.shape != (B,):
            raise ValueError(f"max_new_tokens shape {arr.shape} != ({B},)")
        return arr
    return np.full(B, int(mn), np.int64)


class _MatcherMirror:
    """The host's copy of the fused rounds' matcher state: which rows are
    carried and where each row's next feed starts. It follows the
    device's ``RoundState`` through the same update rules
    (``matcher_feeds``, ``advance_feed``) from the engine's own sync,
    admission and eviction bookkeeping and each round's downloaded
    result, so counting how rows are fed costs no download."""

    def __init__(self, n_rows: int, m: int, rows_carried, rows_full,
                 full_rounds) -> None:
        self.m = m
        self.carried = np.zeros(n_rows, bool)
        self.feed_from = np.zeros(n_rows, np.int64)
        self._forget_after = False
        self._counters = (rows_carried, rows_full, full_rounds)

    def feeds(self, K: int, active, roots, budgets) -> np.ndarray:
        """Rows a round dispatched now feeds; counts them by how."""
        if K == 0:
            return np.zeros_like(self.carried)
        fed = matcher_feeds(active, roots >= 0, budgets, self.carried,
                            self.feed_from, self.m)
        n_carried = int((fed & self.carried).sum())
        n_full = int(fed.sum()) - n_carried
        rows_carried, rows_full, full_rounds = self._counters
        rows_carried.inc(float(n_carried))
        rows_full.inc(float(n_full))
        if n_full:
            full_rounds.inc()
        return fed

    def advance(self, fed, alive, n_take) -> None:
        """Apply a consumed round (and a reset queued behind it)."""
        self.carried, self.feed_from = advance_feed(
            np, fed, self.carried, self.feed_from, alive, n_take, self.m
        )
        if self._forget_after:
            self._forget_after = False
            self.forget()

    def forget(self, rows=None, *, in_flight: bool = False) -> None:
        """``forget_matches`` on the device: every row, or ``rows``. With
        a round in flight the reset lands after it (``advance``)."""
        if in_flight:
            self._forget_after = True
        elif rows is None:
            self.carried[:] = False
            self.feed_from[:] = 0
        else:
            self.carried[rows] = False
            self.feed_from[rows] = 0

    def admit(self, rows) -> None:
        """Admitted rows wait for their tree's upload."""
        self.carried[rows] = False
        self.feed_from[rows] = self.m


class SpecEngine:
    """Speculative rollout engine: draft (host) → verify (device)."""

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        engine: Optional[EngineConfig] = None,
        drafter: Optional[SuffixDrafter] = None,
        length_policy: Optional[LengthPolicy] = None,
        latency: Optional[LatencyModel] = None,
        telemetry=None,
    ) -> None:
        self.params = params
        # Everything the engine allocates (slot pool, round state, packed
        # forest) is committed beside the params: a worker whose params
        # sit on its own chip serves entirely from that chip.
        self.device = _params_device(params)
        self.cfg = cfg
        self.engine = engine or EngineConfig()
        self.drafter = drafter or SuffixDrafter(DrafterConfig())
        self.length_policy = length_policy or LengthPolicy()
        if self.drafter.remote is not None:
            # Remote-backed drafter: pooled cross-worker response-length
            # telemetry merges into THIS engine's length policy on every
            # sync, so classify_length thresholds warm N-workers times
            # faster than local observation alone.
            self.drafter.remote.attach(length_policy=self.length_policy)
        self.latency = latency or LatencyModel(c_base=1.0, c_tok=0.002)
        self._recurrent = M.has_recurrent(cfg)
        self._verify_jit: Dict[int, Any] = {}
        self._prefill_jit: Dict[Tuple[int, int], Any] = {}
        self._fused_jit: Dict[int, Any] = {}
        self._copy_rows_fn = None
        self._admit_state_fn = None
        self._evict_state_fn = None
        self._forget_fn = None
        # Per-(problem, partial-length) budget memo: with G samples per
        # problem the per-row LengthPolicy calls are G-way duplicated
        # every verify round; keyed on the history version so any new
        # observation invalidates.
        self._budget_memo: Dict[Tuple[Any, int], int] = {}
        self._pred_memo: Dict[Any, float] = {}
        self._memo_version = -1
        self.epoch = 0
        # Telemetry (repro.obs): NULL by default, so the instrumented
        # paths cost a handful of no-op calls per round unless a real
        # Telemetry is injected (or the process default was enabled).
        self.telemetry = (
            telemetry if telemetry is not None else obs.get_telemetry()
        )
        self._init_obs()

    def _init_obs(self) -> None:
        """Resolve registry handles once; hot paths touch handles only.

        The drafter (and, through it, the remote history client) adopts
        this engine's telemetry so one worker's `/metrics` endpoint
        aggregates engine + drafter + client + fault gauges.
        """
        tel = self.telemetry
        self.drafter.attach_telemetry(tel)
        c, h = tel.counter, tel.histogram
        self._mx = {
            "rounds": c("das_rounds_total", "Verify rounds dispatched"),
            "fwd": c("das_fwd_total", "Forward passes (prefill + verify)"),
            "proposed": c("das_tokens_proposed_total",
                          "Block tokens proposed over active rows"),
            "drafted": c("das_tokens_drafted_total",
                         "Draft tokens offered for verification"),
            "accepted": c("das_tokens_accepted_total",
                          "Draft tokens accepted by verification"),
            "emitted": c("das_tokens_emitted_total",
                         "Tokens emitted into finished outputs"),
            "h2d": c("das_h2d_transfers_total",
                     "Host-to-device array crossings"),
            "d2h": c("das_d2h_transfers_total",
                     "Device-to-host array crossings"),
            "round_host": h("das_round_host_seconds",
                            "Host bookkeeping time per round dispatch"),
            "forest_upload": c("das_forest_upload_bytes_total",
                               "Bytes of packed suffix forest and row "
                               "roots uploaded to the device"),
            "queue_wait": h("das_queue_wait_rounds",
                            "Rounds from a request's submission to its "
                            "admission into a slot",
                            buckets=obs.exp_buckets(1.0, 2.0, 12)),
            "resumed": c("das_resumed_tokens_total",
                         "Tokens salvaged into resumed rollouts (journal "
                         "recovery / preemption re-admission)"),
        }
        self._preempt_fam = tel.registry.counter_family(
            "das_preemptions_total",
            "Resident rollouts evicted from their slot, by reason",
            ("reason",),
        )
        feed_fam = tel.registry.counter_family(
            "das_matcher_rows_total",
            "Rows the fused round's draft matcher fed, by how: from "
            "carried registers (the new tokens) or in full (the whole "
            "tail from the root)",
            ("feed",),
        )
        self._matcher_counters = (
            feed_fam.labels("carried"), feed_fam.labels("full"),
            c("das_matcher_full_rounds_total",
              "Fused rounds in which any row was fed in full"),
        )
        fam = tel.registry.histogram_family(
            "das_accepted_tokens",
            "Accepted tokens per active row per round, by the row's "
            "current LengthPolicy class",
            ("length_class",), buckets=obs.TOKEN_BUCKETS,
        )
        self._accept_class_hist = tuple(
            fam.labels(name) for name in CLASS_NAMES
        )
        self._active_gauge = tel.gauge(
            "das_active_slots", "Rows active in the current round"
        )
        tel.registry.callback_gauge(
            "das_problem_acceptance",
            "Per-problem draft acceptance rate (accepted/drafted) from "
            "the drafter's history store",
            self._problem_acceptance_gauge,
        )
        tel.registry.callback_gauge(
            "das_compiled_programs",
            "compile_count(): jit programs attributable to this engine",
            lambda: float(self.compile_count()),
        )

    def _problem_acceptance_gauge(self):
        store = getattr(self.drafter, "store", None)
        if store is None:
            return {}
        try:
            keys = list(store.keys())
        except Exception:  # dascheck: disable=DAS303 -- scrape-time gauge: a store mid-mutation must not break /metrics
            return {}
        # Bounded cardinality: acceptance drift for the first 64 problem
        # keys (deterministic order) — enough for dashboards without
        # letting a million-problem run explode the exposition.
        out = {}
        for k in keys[:64]:
            try:
                out[(("problem", str(k)),)] = float(store.acceptance(k))
            except Exception:  # dascheck: disable=DAS303 -- scrape-time gauge: one bad problem key must not break /metrics
                continue
        return out

    def _to_device(self, tree):
        """Commit host (or default-device) arrays to the params' device."""
        return jax.device_put(tree, self.device)

    def _init_pool(self, n_slots: int, max_len: int):
        """Zero slot-pool cache, allocated on the params' device."""
        with jax.default_device(self.device):
            cache = M.init_cache(
                self.cfg, n_slots, max_len, self.engine.cache_headroom
            )
        return self._to_device(cache)

    # -- jitted device steps ------------------------------------------------
    def _get_prefill(self, Tp: int, max_len: int):
        fn = self._prefill_jit.get((Tp, max_len))
        if fn is None:
            @jax.jit
            def prefill_fn(params, toks, mask):
                return M.prefill(
                    params, self.cfg, toks, mask,
                    max_len=max_len, headroom=self.engine.cache_headroom,
                )
            fn = prefill_fn
            self._prefill_jit[(Tp, max_len)] = fn
        return fn

    def _get_verify(self, K: int):
        """Jitted verify step for a draft-block bucket of size K (the
        host-draft round's verify dispatch; the fused program traces the
        same ``verify_step`` body)."""
        fn = self._verify_jit.get(K)
        if fn is None:
            temp = self.engine.temperature
            recurrent = self._recurrent
            attn_impl = self.engine.attn_impl
            cfg = self.cfg

            @jax.jit
            def verify_fn(params, cache, block, budgets, active, key):
                return verify_step(
                    params, cfg, cache, block, budgets, active, key,
                    temperature=temp, recurrent=recurrent,
                    attn_impl=attn_impl,
                )

            fn = verify_fn
            self._verify_jit[K] = fn
        return fn

    def _get_fused(self, K: int, R: int):
        """Jitted fused round program for bucket K.

        One program per (K-bucket, forest/cache geometry): geometry
        changes retrace via jax's shape keying, the K bucket keys this
        dict. ``R`` is always 1 (one round per dispatch); it stays in
        the signature for callers that pass it."""
        fn = self._fused_jit.get(K)
        if fn is None:
            e = self.engine
            fn = build_fused_round(
                self.cfg, K=K,
                temperature=e.temperature, eos_token=e.eos_token,
                recurrent=self._recurrent, attn_impl=e.attn_impl,
                min_match=self.drafter.cfg.min_match,
            )
            self._fused_jit[K] = fn
        return fn

    def _get_copy_rows(self):
        """Jitted batched admission write: k freshly prefilled cache
        rows scatter into their pool slots in one donated update (one
        retrace per admission-chunk size)."""
        if self._copy_rows_fn is None:
            cfg = self.cfg

            def write_fn(dst, src, slots):
                return M.copy_cache_rows(cfg, dst, src, slots)

            self._copy_rows_fn = jax.jit(write_fn, donate_argnums=(0,))
        return self._copy_rows_fn

    def _get_admit_state(self):
        """Jitted fused-state admission write: newly admitted rows'
        head/tail/limit/emitted scatter into the device ``RoundState``
        (``emitted`` is 1 for fresh admissions, the salvaged length for
        journal/preemption resumes). Their matchers reset and wait for
        the next sync to upload their trees' roots. ``slots`` may be
        padded with ``n_slots`` (out-of-range scatters drop)."""
        if self._admit_state_fn is None:
            def write_fn(state, slots, heads, tails, max_new, emitted):
                state = forget_matches(state, slots)
                return state._replace(
                    head=state.head.at[slots].set(heads),
                    tails=state.tails.at[slots].set(tails),
                    active=state.active.at[slots].set(True),
                    emitted=state.emitted.at[slots].set(emitted),
                    max_new=state.max_new.at[slots].set(max_new),
                    feed_from=state.feed_from.at[slots].set(tails.shape[1]),
                )

            self._admit_state_fn = jax.jit(write_fn, donate_argnums=(0,))
        return self._admit_state_fn

    def _get_evict_state(self):
        """Jitted fused-state eviction write: preempted / cancelled /
        expired rows' ``active`` bits clear in one donated scatter (the
        other columns are dead once inactive — the next admission into
        the slot overwrites them) and their matchers reset. ``slots``
        may be padded with ``n_slots`` (out-of-range scatters drop)."""
        if self._evict_state_fn is None:
            def evict_fn(state, slots):
                state = forget_matches(state, slots)
                return state._replace(
                    active=state.active.at[slots].set(False)
                )

            self._evict_state_fn = jax.jit(evict_fn, donate_argnums=(0,))
        return self._evict_state_fn

    def _get_forget_matches(self):
        """Jitted donated reset of every row's carried matcher, enqueued
        behind the round in flight whenever a new forest or new roots
        are uploaded (registers name nodes of the forest they were fed
        in)."""
        if self._forget_fn is None:
            self._forget_fn = jax.jit(forget_matches, donate_argnums=(0,))
        return self._forget_fn

    def compile_count(self) -> int:
        """Total jit compilations attributable to this engine (plus the
        module-level suffix-match dispatches) — the steady-state
        recompile guard's probe: after warmup, serving a mixed workload
        must not grow this."""
        from repro.kernels.suffix_match import ops as sm_ops
        from repro.kernels.suffix_match import ref as sm_ref

        fns = (
            list(self._prefill_jit.values())
            + list(self._verify_jit.values())
            + list(self._fused_jit.values())
        )
        for f in (self._copy_rows_fn, self._admit_state_fn,
                  self._evict_state_fn, self._forget_fn):
            if f is not None:
                fns.append(f)
        fns += [sm_ops._dispatch, sm_ref.suffix_match_propose_ref]
        total = 0
        for f in fns:
            size = getattr(f, "_cache_size", None)
            total += int(size()) if callable(size) else 0
        return total

    def _bucket(self, k: int) -> int:
        for b in self.engine.block_buckets:
            if k <= b:
                return b
        return self.engine.max_draft

    # -- budgets --------------------------------------------------------------
    def _round_budgets(
        self, problem_ids, emitted_lens, active, remaining
    ) -> np.ndarray:
        """Per-row draft budgets for one verify round.

        Only *active* rows are evaluated (and, for the Eq. 7/9 solver,
        only active rows enter the coupled solve — dead slots cost no
        forward passes, so they must not drag the optimum). Per-row
        ``LengthPolicy`` calls are memoized on (problem, partial length)
        keyed to the history version: with G samples per problem the
        lock-step engine used to recompute identical posteriors G times
        per round.
        """
        e = self.engine
        B = len(problem_ids)
        budgets = np.zeros(B, np.int64)
        if not e.spec_enabled:
            return budgets
        active = np.asarray(active, bool)
        if e.unlimited_budget:
            return np.where(active, e.max_draft, 0)
        idx = np.nonzero(active)[0]
        if idx.size == 0:
            return budgets
        ver = self.length_policy.history_size()
        if ver != self._memo_version:
            self._memo_version = ver
            self._budget_memo.clear()
            self._pred_memo.clear()
        bm = self._budget_memo
        # Length-class budget (paper §4.2.3) per row …
        cls_budget = np.empty(idx.size, np.int64)
        for j, i in enumerate(idx):
            k = (problem_ids[i], int(emitted_lens[i]))
            v = bm.get(k)
            if v is None:
                v = bm[k] = int(self.length_policy.budget(k[0], k[1]))
            cls_budget[j] = v
        if e.use_budget_solver and ver >= 8:
            # … refined by the Eq. 7/9 solver on predicted remaining length:
            # the class decides WHO speculates (Short rows skip, Obs. 2),
            # the solver decides HOW MUCH (p* spread over expected rounds).
            pm = self._pred_memo
            pred_rem = np.empty(idx.size, np.float64)
            for j, i in enumerate(idx):
                pid = problem_ids[i]
                el = pm.get(pid)
                if el is None:
                    el = pm[pid] = float(self.length_policy.expected_length(pid))
                pred_rem[j] = max(8.0, el - float(emitted_lens[i]))
            p_star, _ = solve_budgets(pred_rem, self.latency)
            per_round = np.ceil(
                p_star / np.maximum(pred_rem, 1.0) * e.max_draft
            ).astype(np.int64)
            solver_budget = np.where(p_star > 0, np.maximum(per_round, 1), 0)
            cls_budget = np.where(
                cls_budget > 0,
                np.minimum(cls_budget, np.maximum(solver_budget, 1)),
                0,
            )
        b = np.clip(cls_budget, 0, e.max_draft)
        b = np.minimum(b, np.maximum(np.asarray(remaining)[idx] - 1, 0))
        budgets[idx] = b
        return budgets

    # -- the round loop -------------------------------------------------------
    # das: hot-path — the serving round loop; admit/dispatch/consume nested
    # below inherit the marker
    def serve(
        self,
        requests: Iterable[Request],
        *,
        slots: Optional[int] = None,
        key: Optional[jax.Array] = None,
        stats: Optional[RolloutStats] = None,
        collect_effective_batch: bool = False,
        watchdog=None,
        journal=None,
        drain=None,
        preemption=None,
        clock=None,
    ) -> Iterator[Request]:
        """The engine's round loop (generator of finished requests).

        A fixed pool of ``slots`` device slots is fed from an admission
        queue ordered longest-predicted-first (``SlotScheduler``). The
        moment a row finishes, its slot is re-prefilled (coalesced
        bucketed prefill + ``copy_cache_rows`` scatter) with the next
        pending request, so the effective batch stays full through the
        long tail.

        Rounds are double-buffered: after the jitted verify for round
        *t* is dispatched, the host (a) observes rollouts that finished
        in earlier rounds — the drafter/length-policy updates benefit
        still-running stragglers mid-serve — repacking any mutated
        suffix trees for the device drafter (``bds.prewarm``), and (b)
        pre-solves round *t+1* budgets from bounded-staleness emitted
        counts (re-clamped against fresh limits before dispatch).
        The round's result is only materialized when the next dispatch
        needs it, so the device round overlaps all of that host work.
        Rows admitted in round *t* draft from round *t+1* on.

        The drafter decides the round (``bds.device``): device drafts
        run the whole round as one fused dispatch with per-slot session
        state on device; host per-row sessions (scope ``problem+request``)
        propose on the host before a jitted verify. Greedy verification
        is lossless, so per-request outputs are token-identical to plain
        decoding at temperature 0, whatever the pool size.

        ``stats`` counters (rounds, forwards, drafted/accepted, emitted
        tokens, wall time) aggregate across the serve; the per-row
        arrays are request-order views that only the ``generate``
        wrapper fills.

        Durability / lifecycle (all optional, all off by default):

        * ``journal`` — a ``repro.fault.RolloutJournal``. Every request
          gets a ``begin`` record up front; each consumed round's
          accepted tokens buffer as one ``round`` record per request and
          group-commit once per round from the post-consume host window
          (never inside a jitted dispatch). Requests arriving with
          ``resume_tokens`` (journal recovery, or a preemption earlier
          in this serve) re-admit via prefix re-prefill of
          ``prompt + resume_tokens[:-1]`` with the last salvaged token
          as the head — token-identical at T=0 to the uninterrupted run.
        * ``drain`` — a ``repro.fault.DrainController``. Once draining,
          admissions stop; residents run to completion until the drain
          deadline, at which point they are preempted (progress
          journaled, state PREEMPTED, not re-queued) and the serve
          returns early with the journal fsynced.
        * ``preemption`` — a ``scheduler.PreemptionPolicy``. Victims are
          evicted post-consume, re-queued with remaining-length
          priority, and resume later via the same prefix re-prefill —
          slot oversubscription without losing long-tail progress.
        * ``clock`` — a ``repro.fault.Clock`` driving per-request
          ``deadline_s`` expiry, drain deadlines and the preemption
          policy's deadline margin (``VirtualClock`` in tests).

        Requests cancelled (``cancel_requested``) / expired / drained
        end in a non-FINISHED terminal state with their partial
        ``output`` preserved, and are yielded without being observed
        into the drafter/length history (a truncated rollout must not
        poison the policy).
        """
        e = self.engine
        tel_obs = self.telemetry
        reqs = list(requests)
        if stats is None:
            stats = RolloutStats()
        if not reqs:
            return
        # ``stats`` may accumulate across serve() calls: mirror the
        # transfer counters into the registry as end-of-serve deltas.
        h2d0, d2h0 = stats.n_h2d, stats.n_d2h
        n_slots = max(1, min(int(slots) if slots else len(reqs), len(reqs)))
        sched = SlotScheduler(n_slots, self.length_policy, clock=clock)
        has_deadlines = any(r.deadline_s is not None for r in reqs)
        # Flight recorder (repro.obs.flight): trace IDs mint up front —
        # journal begin records carry them even when nobody records
        # locally, so a LATER process (crash recovery, requeue survivor)
        # continues the same trace. Event capture itself is guarded by
        # ``rec_flight`` and rides the post-consume host windows only.
        flt = getattr(tel_obs, "flight", None) or NULL_FLIGHT
        rec_flight = flt.enabled
        for r in reqs:
            if r.trace is None:
                r.trace = flt.new_trace()
        if journal is not None:
            for r in reqs:
                if r.journal_key is None:
                    r.journal_key = str(r.rid)
                journal.begin(
                    r.journal_key, r.prompt, problem_id=r.problem_id,
                    max_new_tokens=r.max_new_tokens,
                    resume=bool(r.resume_tokens), trace=r.trace,
                )
        for r in reqs:
            r.submit_round = 0
            sched.submit(r)
            if rec_flight:
                flt.record(r.trace, "queued", rid=r.rid)
        if key is None:
            key = jax.random.key(0)

        def _eff_prompt_len(r: Request) -> int:
            # A resumed request prefills prompt + salvaged[:-1]; size
            # the pool for that effective context.
            rt = r.resume_tokens
            return len(r.prompt) + (max(len(rt) - 1, 0) if rt else 0)

        # One pool cache sized for the worst admitted request.
        max_tp = max(_prompt_bucket(_eff_prompt_len(r)) for r in reqs)
        pool_len = _cache_bucket(
            max_tp + max(int(r.max_new_tokens) for r in reqs)
            + e.max_draft + 2
        )
        cache = self._init_pool(n_slots, pool_len)
        copy_rows = self._get_copy_rows()

        head = np.zeros(n_slots, np.int32)
        emitted = np.zeros(n_slots, np.int64)
        max_new_arr = np.ones(n_slots, np.int64)
        active = np.zeros(n_slots, bool)
        pids: List[Any] = [None] * n_slots
        bds = self.drafter.batched_sessions(n_slots)
        fused = bds.device

        # Device drafts: per-slot session state (head / context tails /
        # emitted / limits) lives on DEVICE between rounds; the host
        # mirrors above only drive budget solving and bookkeeping.
        state = None
        forest = forest_src = None
        roots = roots_dev = None
        last_ver = -1
        mirror = None
        if fused:
            state = self._to_device(make_state(
                head, np.full((n_slots, bds.tail_len), -1, np.int32),
                active, emitted, max_new_arr,
            ))
            stats.n_h2d += 5
            mirror = _MatcherMirror(n_slots, bds.tail_len,
                                    *self._matcher_counters)

        pending = None  # in-flight round (see dispatch/consume)
        finalize_q = collections.deque()  # finished; observation deferred
        done_q = collections.deque()  # observed; ready to yield
        round_no = 0

        t_serve0 = time.perf_counter()

        def finish(req: Request) -> None:
            if req.output and req.output[-1] == e.eos_token:
                req.output.pop()
            req.emitted = len(req.output)
            req.finish_round = round_no
            req.session = None
            stats.n_toks_emitted += req.emitted
            sched.release(req)
            if journal is not None:
                journal.finish(req.journal_key, n_emitted=req.emitted)
            finalize_q.append(req)
            if rec_flight:
                flt.record(
                    req.trace, "finish", rid=req.rid, status="finished",
                    emitted=req.emitted,
                    rounds=req.finish_round - req.admit_round,
                )
            if tel_obs.enabled:
                self._mx["emitted"].inc(req.emitted)
                tel_obs.emit(
                    "request_done", rid=req.rid, slot=req.slot,
                    emitted=req.emitted,
                    rounds=req.finish_round - req.admit_round,
                )

        roots_dirty = True  # row→tree mapping changed since last upload

        def _admit_chunk(Tp: int, sub, admitted: List[Request]) -> None:
            """One coalesced admission chunk: batched prefill, one
            vectorized cache-row scatter, per-request bookkeeping.

            The ``prefill`` span covers dispatch → first-token download
            (the device sync), with the ``cache_commit`` scatter nested
            — so the attribution report's "prefill" component is real
            span time, not an inferred residue.
            """
            nonlocal cache, key
            k = len(sub)
            tp0 = time.perf_counter()
            with tel_obs.span("prefill") as sp_pf:
                sp_pf.set(n=k, Tp=Tp)
                toks = np.zeros((k, Tp), np.int32)
                mask = np.zeros((k, Tp), bool)
                for j, (req, ctx) in enumerate(sub):
                    n_p = len(ctx)
                    toks[j, Tp - n_p:] = ctx
                    mask[j, Tp - n_p:] = True
                last_logits, rows_cache = self._get_prefill(
                    Tp, pool_len
                )(self.params, toks, mask)
                stats.n_h2d += 2
                slots_arr = np.array(
                    [r.slot for r, _ in sub], np.int32
                )
                with tel_obs.span("cache_commit"):
                    cache = copy_rows(cache, rows_cache, slots_arr)
                stats.n_h2d += 1
                row_keys = None
                if e.temperature > 0:  # per-request key stream
                    row_keys = []
                    for _ in sub:
                        key, k0 = jax.random.split(key)
                        row_keys.append(k0)
                first_toks = np.asarray(sample_token_rows(  # dascheck: disable=DAS001 -- admission prefill download, off the steady-state round path
                    last_logits[:, : self.cfg.vocab_size],
                    temperature=e.temperature,
                    keys=(jnp.stack(row_keys)
                          if row_keys is not None else None),
                ))
                stats.n_d2h += 1
            prefill_s = time.perf_counter() - tp0
            stats.n_fwd += 1
            if tel_obs.enabled:
                self._mx["queue_wait"].observe_many(
                    round_no - req.submit_round for req, _ in sub
                )
            stats.n_toks_proposed += int(
                sum(len(c) for _, c in sub)
            )
            for j, (req, _ctx) in enumerate(sub):
                s = req.slot
                req.admit_round = round_no
                rt = req.resume_tokens
                if rt:
                    # Prefix re-prefill resume: the head is
                    # the last salvaged token (at T=0 it IS
                    # what the prefill's logits argmax to),
                    # not a fresh sample.
                    rt = [int(t) for t in rt]
                    req.resume_tokens = None
                    req.output = list(rt)
                    tok = rt[-1]
                    req.head = tok
                    self._mx["resumed"].inc(float(len(rt)))
                    if journal is not None:
                        # a fresh journal file (recovery
                        # onto a new path) has none of the
                        # salvaged prefix yet; re-note the
                        # missing suffix so ITS recovery is
                        # self-contained
                        have = journal.recorded_tokens(
                            req.journal_key
                        )
                        if have < len(rt):
                            journal.note(
                                req.journal_key, rt[have:]
                            )
                    if rec_flight:
                        flt.record(
                            req.trace, "resume", dur=prefill_s / k,
                            rid=req.rid, slot=s, round=round_no,
                            salvaged=len(rt),
                        )
                    if tel_obs.enabled:
                        tel_obs.emit(
                            "resume", rid=req.rid, slot=s,
                            round=round_no, salvaged=len(rt),
                        )
                    if (tok == e.eos_token
                            or len(rt) >= req.max_new_tokens):
                        finish(req)  # salvaged tail was done
                        continue
                    bds.open(s, req.problem_id, req.prompt)
                    bds.feed(s, rt)
                    pids[s] = req.problem_id
                    head[s] = tok
                    emitted[s] = len(rt)
                    max_new_arr[s] = req.max_new_tokens
                    active[s] = True
                    admitted.append(req)
                    continue
                tok = int(first_toks[j])
                req.head = tok
                if tok == e.eos_token or req.max_new_tokens <= 0:
                    if req.max_new_tokens > 0:
                        req.output.append(tok)
                    finish(req)  # freed; outer loop re-admits
                    continue
                req.output.append(tok)
                if journal is not None:
                    journal.note(req.journal_key, [tok])
                if req.max_new_tokens <= 1:  # head fills limit
                    finish(req)
                    continue
                bds.open(s, req.problem_id, req.prompt)
                bds.feed(s, [tok])
                pids[s] = req.problem_id
                head[s] = tok
                emitted[s] = 1
                max_new_arr[s] = req.max_new_tokens
                active[s] = True
                admitted.append(req)
                if rec_flight:
                    flt.record(
                        req.trace, "admit", dur=prefill_s / k,
                        rid=req.rid, slot=s, round=round_no,
                    )
                if tel_obs.enabled:
                    tel_obs.emit(
                        "admit", rid=req.rid, slot=s,
                        round=round_no,
                    )

        def admit() -> None:
            """Fill free slots from the queue with COALESCED prefills.

            Admissions sharing a prompt bucket run as ONE batched
            prefill (binary-decomposed into power-of-two chunks so the
            compiled-variant set stays bounded) and their cache rows
            commit via one vectorized scatter (``copy_cache_rows``).
            PRNG keys are still split per *request*, so sampled first
            tokens are independent of the grouping. Immediate-EOS
            admissions release their slot and the loop re-admits into
            it. In fused mode the new rows' head/tail/limit are
            batch-written into the device ``RoundState``.

            Requests carrying ``resume_tokens`` (journal recovery or an
            earlier preemption) re-admit via prefix re-prefill: the
            context is ``prompt + salvaged[:-1]`` and the head is the
            last salvaged token — the cache and drafter state land
            exactly where the uninterrupted run had them, so the
            continuation is token-identical at T=0.
            """
            nonlocal state, roots_dirty
            while True:
                newly = sched.next_admissions()
                if not newly:
                    return
                with tel_obs.span("admission_coalesce") as sp_adm:
                    groups: Dict[int, List[Tuple[Request, List[int]]]] = {}
                    for req in newly:
                        rt = req.resume_tokens
                        ctx = (list(req.prompt) + [int(t) for t in rt[:-1]]
                               if rt else req.prompt)
                        Tp = _prompt_bucket(len(ctx))
                        groups.setdefault(Tp, []).append((req, ctx))
                    admitted: List[Request] = []
                    for Tp in sorted(groups):
                        greqs = groups[Tp]
                        i0 = 0
                        while i0 < len(greqs):
                            k = 1 << ((len(greqs) - i0).bit_length() - 1)
                            _admit_chunk(Tp, greqs[i0 : i0 + k], admitted)
                            i0 += k
                    sp_adm.set(n=len(newly), admitted=len(admitted))
                    if fused and admitted:
                        kk = len(admitted)
                        kb = 1 << max(kk - 1, 0).bit_length()  # pow2 ceiling
                        # padding rows scatter out of range (dropped)
                        slots_pad = np.full(kb, n_slots, np.int32)
                        heads_pad = np.zeros(kb, np.int32)
                        tails_pad = np.full(
                            (kb, bds.tail_len), -1, np.int32
                        )
                        mn_pad = np.ones(kb, np.int32)
                        em_pad = np.ones(kb, np.int32)
                        for j, req in enumerate(admitted):
                            slots_pad[j] = req.slot
                            heads_pad[j] = req.head
                            tails_pad[j] = bds.tail_row(req.slot)
                            mn_pad[j] = req.max_new_tokens
                            em_pad[j] = emitted[req.slot]  # 1, or salvaged len
                        with tel_obs.span("cache_commit"):
                            state = self._get_admit_state()(
                                state, slots_pad, heads_pad, tails_pad,
                                mn_pad, em_pad,
                            )
                        stats.n_h2d += 5
                        mirror.admit(slots_pad[:kk])
                        roots_dirty = True

        def consume() -> None:
            """Materialize the in-flight round (device sync point) and
            apply its bookkeeping.

            Mirror updates (emitted / head / active) are vectorized; the
            per-row loop that remains is the unavoidable per-request
            ``output.extend`` plus telemetry and finish handling. In
            fused mode the round result arrives as ONE packed download —
            emit scan, acceptance and next-round session state were
            already computed on device."""
            nonlocal pending
            if pending is None:
                return
            if pending[0] == "fused":
                _, outs_dev, K, mask, fed = pending
                pending = None
                outs = np.asarray(outs_dev)  # dascheck: disable=DAS001 -- the fused round's one download
                stats.n_d2h += 1
                t_h = time.perf_counter()
                cand, accepted, n_take, alive, budgets = unpack_round_out(
                    outs, K
                )
                alive = alive & mask
                mirror.advance(fed, alive, n_take)
            else:
                _, res, block, budgets, mask = pending
                pending = None
                accepted = np.asarray(res.accepted).astype(np.int64)  # dascheck: disable=DAS001 -- the host-draft round's sanctioned acceptance download
                next_tok = np.asarray(res.next_token).astype(np.int32)  # dascheck: disable=DAS001 -- paired with the acceptance download above
                stats.n_d2h += 2
                t_h = time.perf_counter()
                cand = np.zeros((n_slots, block.shape[1]), np.int32)
                cand[:, :-1] = block[:, 1:]
                cand[np.arange(n_slots), accepted] = next_tok
                n_take, alive = _emit_scan(
                    cand, accepted + 1, max_new_arr - emitted, e.eos_token
                )
                alive &= mask
                head[:] = np.where(alive, next_tok, head)
            stats.n_toks_proposed += int((1 + budgets[mask]).sum())
            stats.n_drafted += int(budgets[mask].sum())
            stats.n_accepted += int(accepted[mask].sum())
            stats.round_accepts.append(
                float(accepted[mask].mean()) if mask.any() else 0.0
            )
            if tel_obs.enabled:
                # rounds/fwd already counted at dispatch; mirror the
                # token counters + length-class histograms here where
                # acceptance is known
                mx = self._mx
                mx["proposed"].inc(float((1 + budgets[mask]).sum()))
                mx["drafted"].inc(float(budgets[mask].sum()))
                mx["accepted"].inc(float(accepted[mask].sum()))
                lp = self.length_policy
                by_cls: List[List[float]] = [[], [], []]
                for s in np.nonzero(mask)[0]:
                    by_cls[lp.classify_length(float(emitted[s]))].append(
                        float(accepted[s])
                    )
                for cls_i, vals in enumerate(by_cls):
                    if vals:
                        self._accept_class_hist[cls_i].observe_many(vals)
            emitted[mask] += n_take[mask]
            active[mask & ~alive] = False
            if not fused:  # device tails advance inside the fused round
                bds.feed_rows(np.nonzero(alive)[0], cand, n_take)
            tel = np.nonzero(mask & (budgets > 0))[0]
            if tel.size:  # per-prompt acceptance telemetry, batched
                self.drafter.note_draft_rows(
                    [pids[s] for s in tel], budgets[tel], accepted[tel]
                )
            if rec_flight and mask.any():
                # ONE batched raw append for the whole pool's round
                # (explodes into per-trace events at drain time): the
                # per-rollout accept trail costs O(1) on the round loop.
                rows_f = np.nonzero(mask)[0]
                flt.record_round(
                    round_no,
                    [sched.slots[s].trace for s in rows_f],
                    accepted[rows_f].tolist(), budgets[rows_f].tolist(),
                )
            for s in np.nonzero(mask & (n_take > 0))[0]:
                req = sched.slots[s]
                take = cand[s, : n_take[s]].tolist()
                req.output.extend(take)
                if journal is not None:  # buffered; committed post-consume
                    journal.note(req.journal_key, take)
            for s in np.nonzero(mask & ~alive)[0]:
                req = sched.slots[s]
                bds.close(s)
                pids[s] = None
                finish(req)
            stats.host_time_s += time.perf_counter() - t_h

        def teardown_slot(req: Request) -> int:
            """Host-side eviction of a resident row; the fused device
            ``active`` bit clears in one batched scatter afterwards."""
            s = req.slot
            bds.close(s)
            pids[s] = None
            active[s] = False
            req.session = None
            return s

        def finish_terminal(req: Request, status: str) -> None:
            """CANCELLED/EXPIRED terminal: partial ``output`` preserved,
            journal closed with the terminal status, yielded WITHOUT
            being observed into the drafter/length history (a truncated
            rollout must not poison the policy)."""
            req.emitted = len(req.output)
            req.finish_round = round_no
            if journal is not None:
                journal.finish(
                    req.journal_key, status=status, n_emitted=req.emitted
                )
            done_q.append(req)
            if rec_flight:
                flt.record(
                    req.trace, "finish", rid=req.rid, status=status,
                    emitted=req.emitted,
                )
            if tel_obs.enabled:
                tel_obs.emit(
                    "request_done", rid=req.rid, status=status,
                    emitted=req.emitted,
                )

        def preempt_req(req: Request, reason: str, requeue: bool) -> None:
            """Evict a resident: its progress is already journaled round
            by round, so the victim only needs its salvage prefix staged
            (``resume_tokens``) and — unless draining — a re-queue with
            remaining-length priority."""
            sched.preempt(req)
            req.resume_tokens = list(req.output)
            req.head = -1
            req.predicted_len = sched.remaining_len(req)
            if requeue:
                req.submit_round = round_no
                sched.submit(req)
            self._preempt_fam.labels(reason).inc()
            if rec_flight:
                flt.record(
                    req.trace, "preempt", rid=req.rid, reason=reason,
                    emitted=len(req.output), round=round_no,
                    requeued=requeue,
                )
                if requeue:
                    flt.record(req.trace, "requeue", rid=req.rid,
                               round=round_no)
            if tel_obs.enabled:
                tel_obs.emit(
                    "preempt", rid=req.rid, reason=reason,
                    emitted=len(req.output), round=round_no,
                    requeued=requeue,
                )

        def service_lifecycle() -> None:
            """Post-consume lifecycle pass: cancellations, per-request
            deadlines, drain expiry, preemption-policy victims. Runs
            only while no round is in flight (``pending is None``), so
            an evicted slot can never receive a stale round result."""
            nonlocal state
            evicted: List[int] = []
            now = None
            if has_deadlines or (
                preemption is not None and preemption.deadline_margin_s > 0
            ):
                now = sched.clock.now()
            for req in sched.running() + sched.queued_requests():
                if req.cancel_requested:
                    if req.slot >= 0:
                        evicted.append(teardown_slot(req))
                    sched.cancel(req)
                    finish_terminal(req, CANCELLED)
            if has_deadlines:
                for req in sched.due_requests(now):
                    if req.slot >= 0:
                        evicted.append(teardown_slot(req))
                    sched.expire(req)
                    finish_terminal(req, EXPIRED)
            if drain is not None and drain.draining and drain.expired():
                # journal-and-exit: residents go PREEMPTED but are NOT
                # re-queued; their journal sessions stay in flight, so
                # the next process resumes them token-identically.
                for req in sched.running():
                    evicted.append(teardown_slot(req))
                    preempt_req(req, "drain", requeue=False)
            elif preemption is not None:
                mrr = preemption.max_resident_rounds
                for req in sched.preemption_victims(
                    preemption, round_no, now
                ):
                    reason = (
                        "slot_pressure"
                        if mrr is not None
                        and round_no - req.admit_round >= mrr
                        else "deadline"
                    )
                    evicted.append(teardown_slot(req))
                    preempt_req(req, reason, requeue=True)
            if fused and evicted:
                kb = 1 << max(len(evicted) - 1, 0).bit_length()
                pad = np.full(kb, n_slots, np.int32)  # OOB pads drop
                pad[: len(evicted)] = evicted
                state = self._get_evict_state()(state, pad)
                stats.n_h2d += 1
                mirror.forget(evicted)

        def precompute_budgets():
            """Round t+1 budgets from bounded-staleness emitted counts —
            runs in the overlap window while the device verifies round t.
            The occupant snapshot guards against slot recycling: a budget
            precomputed for a slot's previous request must not be applied
            to the request admitted into it afterwards."""
            if not active.any():
                return None
            with tel_obs.span("budget_solve"):
                rem = max_new_arr - emitted
                return (
                    self._round_budgets(pids, emitted, active, rem),
                    active.copy(),
                    list(sched.slots),
                )

        def solve_budgets(pre) -> np.ndarray:
            """Round budgets for currently-active rows (post-consume):
            merge the overlap-window precompute where the slot occupant
            is unchanged, solve fresh for the rest, clamp against fresh
            emission limits."""
            with tel_obs.span("budget_solve"):
                remaining = max_new_arr - emitted
                budgets = np.zeros(n_slots, np.int64)
                if pre is not None:
                    pb, pmask, pocc = pre
                    same = np.fromiter(
                        (sched.slots[s] is pocc[s] for s in range(n_slots)),
                        bool, n_slots,
                    )
                    use = pmask & active & same
                    budgets[use] = pb[use]
                    fresh_rows = active & ~use
                else:
                    fresh_rows = active.copy()
                if fresh_rows.any():  # rows recycled since the precompute
                    fb = self._round_budgets(
                        pids, emitted, fresh_rows, remaining
                    )
                    budgets[fresh_rows] = fb[fresh_rows]
                return np.where(
                    active,
                    np.minimum(budgets, np.maximum(remaining - 1, 0)), 0,
                )

        def sync_forest() -> None:
            """Refresh the packed forest + per-row root handles after
            tree mutations (finalize observations) or slot turnover
            (admissions). Called from the overlap window so the repack
            and the roots upload hide behind the in-flight round; the
            dispatch-side call is a startup/late-repack fallback. The
            rows' carried matchers reset behind the round in flight."""
            nonlocal forest, forest_src, roots, roots_dev, last_ver, \
                roots_dirty, state
            with tel_obs.span("history_sync") as sp_s:
                bds.prewarm()
                last_ver = bds.repack_version
                roots_dirty = False
                up = 0
                if bds.forest_arrays() is not forest_src:
                    forest_src = bds.forest_arrays()
                    forest = self._to_device(forest_src)
                    up = sum(a.nbytes for a in forest_src)
                roots = bds.roots_array()
                roots_dev = self._to_device(roots)
                state = self._get_forget_matches()(state)
                mirror.forget(in_flight=pending is not None)
                if tel_obs.enabled:
                    self._mx["forest_upload"].inc(float(up + roots.nbytes))
                stats.n_h2d += 1
                sp_s.set(h2d=1)

        def dispatch(budgets, prop_handle, fresh_roots: bool = False) -> None:
            nonlocal pending, cache, key, round_no, state
            t_h = time.perf_counter()
            K = self._bucket(int(budgets.max(initial=0)))
            if fused:
                # ---- ONE fused dispatch: propose → block → verify →
                # commit → next-round state, all device-side. The host
                # uploads the (B,) budget vector (plus roots when the
                # row→tree mapping or the packed forest changed — the
                # overlap window usually refreshed those already) and
                # nothing else. Rows admitted THIS iteration carry
                # budget 0 (they draft from their next round on), so a
                # stale root entry for them is inert — only the startup
                # branch, whose budgets were solved post-admission,
                # needs roots synced right here.
                if roots_dev is None or (
                    fresh_roots
                    and (roots_dirty or bds.repack_version != last_ver)
                ):
                    sync_forest()  # startup / post-admission solve
                kv = key
                if e.temperature > 0:  # greedy verify never uses the key
                    key, kv = jax.random.split(key)
                if K > 0:  # solve_budgets zeroes inactive rows
                    self.drafter.stats["batched_proposes"] += 1
                stats.host_time_s += time.perf_counter() - t_h
                stats.n_h2d += 1  # the (B,) budget vector
                fed = mirror.feeds(K, active, roots, budgets)
                cache, state, outs_dev = self._get_fused(K, 1)(
                    self.params, forest, cache, state, roots_dev,
                    budgets.astype(np.int32), kv,
                )
                pending = ("fused", outs_dev, K, active.copy(), fed)
            else:
                block = np.zeros((n_slots, K + 1), np.int32)
                block[:, 0] = head
                props = bds.consume(prop_handle)
                for s in np.nonzero(active)[0]:
                    prop = props[s]
                    budgets[s] = len(prop)
                    if prop:
                        block[s, 1 : 1 + len(prop)] = prop
                kv = key
                if e.temperature > 0:  # greedy verify never uses the key
                    key, kv = jax.random.split(key)
                block_dev = jnp.asarray(block)
                budgets_dev = jnp.asarray(budgets.astype(np.int32))
                active_dev = jnp.asarray(active)
                stats.host_time_s += time.perf_counter() - t_h
                stats.n_h2d += 3  # block + budgets + active uploads
                res, cache = self._get_verify(K)(
                    self.params, cache, block_dev, budgets_dev,
                    active_dev, kv,
                )
                pending = ("plain", res, block, budgets, active.copy())
            round_no += 1
            stats.n_rounds += 1
            stats.n_fwd += 1
            if tel_obs.enabled:
                self._mx["rounds"].inc()
                self._mx["fwd"].inc()
                self._active_gauge.set(float(active.sum()))
            if collect_effective_batch:
                stats.effective_batch.append(int(active.sum()))
            for s in np.nonzero(active)[0]:
                sched.slots[s].rounds += 1

        if watchdog is not None:
            watchdog.arm()
        while sched.has_work() or pending is not None:
            if watchdog is not None:
                watchdog.check("serve round")
            host0 = stats.host_time_s
            with tel_obs.span("serve_round"):
                # ---- overlap window: the device executes the in-flight
                # round; the host observes finished rollouts (their
                # drafts immediately help still-running stragglers) and
                # pre-solves the next round's budgets.
                if finalize_q:
                    with tel_obs.span("history_publish") as sp_p:
                        n_fin = 0
                        while finalize_q:
                            req = finalize_q.popleft()
                            self._finalize_request(req)
                            done_q.append(req)
                            n_fin += 1
                        # repack mutated trees while the round is in
                        # flight so the next dispatch stays cache-hit
                        # (once, after ALL of the round's observations
                        # mutated trees)
                        bds.prewarm()
                        sp_p.set(finished=n_fin)
                if fused and (roots_dirty or bds.repack_version != last_ver):
                    # also in the overlap window: the roots/forest
                    # upload for last iteration's admissions rides the
                    # in-flight round (their budgets stay 0 until the
                    # next solve)
                    sync_forest()
                pre = precompute_budgets() if pending is not None else None
                # device sync: bookkeeping needs the round result
                with tel_obs.span("consume"):
                    consume()
                if watchdog is not None:
                    watchdog.progress()  # the in-flight round completed
                if journal is not None:
                    # THE post-consume group commit: one write + flush
                    # per round, fsync batched (das_journal_* meter it)
                    t_h = time.perf_counter()
                    journal.commit()
                    stats.host_time_s += time.perf_counter() - t_h
                service_lifecycle()
                draining = drain is not None and drain.draining
                # ---- host drafts: the per-row sessions propose for the
                # rows that survived the round, before admissions. Device
                # drafts: the propose runs inside the round dispatch
                # below. Either way, rows admitted below draft from their
                # next round on (one draft-free warmup round per
                # admission).
                budgets = prop_handle = None
                if active.any():
                    t_h = time.perf_counter()
                    budgets = solve_budgets(pre)
                    if not fused:
                        prop_handle = bds.dispatch(budgets)
                    stats.host_time_s += time.perf_counter() - t_h
                if not draining:  # drain: stop admissions, run down
                    admit()  # recycle freed slots before the next round
                if active.any():
                    fresh_roots = False
                    if budgets is None:
                        # The pool was empty before admissions (startup
                        # or full drain): nothing was in flight to
                        # overlap with, so solve + propose for the
                        # freshly admitted batch now — warm history
                        # drafts from round one.
                        t_h = time.perf_counter()
                        budgets = solve_budgets(None)
                        if not fused:
                            prop_handle = bds.dispatch(budgets)
                        stats.host_time_s += time.perf_counter() - t_h
                        fresh_roots = True
                    with tel_obs.span("verify_dispatch"):
                        dispatch(budgets, prop_handle, fresh_roots)
            if tel_obs.enabled:
                self._mx["round_host"].observe(stats.host_time_s - host0)
            while done_q:
                yield done_q.popleft()
            if (drain is not None and drain.draining
                    and pending is None and not active.any()):
                # Drained out: residents finished (or were journaled and
                # preempted at the deadline); whatever is still queued
                # stays QUEUED with its journal session in flight.
                break
        while done_q:  # lifecycle terminals from the final iteration
            yield done_q.popleft()
        while finalize_q:  # tail: rows that finished in the last round
            req = finalize_q.popleft()
            self._finalize_request(req)
            yield req
        if journal is not None:
            journal.commit()  # tail finish records
            if drain is not None and drain.draining:
                journal.sync()  # drain exit: force power-loss durability
        stats.n_h2d += bds.xfers.pop("h2d", 0)
        stats.n_d2h += bds.xfers.pop("d2h", 0)
        stats.wall_time_s = time.perf_counter() - t_serve0
        if tel_obs.enabled:
            self._mx["h2d"].inc(float(stats.n_h2d - h2d0))
            self._mx["d2h"].inc(float(stats.n_d2h - d2h0))

    def _finalize_request(self, req: Request) -> None:
        """Observe a finished rollout (drafter window + length history).

        The request's trace ID rides the history publish, so the shard
        side of the fleet can stamp a ``publish`` flight event onto the
        same trace the worker recorded the rollout under.
        """
        self.drafter.observe_rollout(
            req.problem_id, list(req.prompt) + req.output, self.epoch,
            response_len=len(req.output), trace=req.trace,
        )
        self.length_policy.observe(req.problem_id, len(req.output))

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        problem_ids: Optional[Sequence] = None,
        *,
        slots: Optional[int] = None,
        max_new_tokens=None,
        key: Optional[jax.Array] = None,
        collect_effective_batch: bool = False,
        watchdog=None,
        journal=None,
        journal_keys: Optional[Sequence[str]] = None,
        resume: Optional[Dict[str, Any]] = None,
    ) -> Tuple[List[List[int]], RolloutStats]:
        """Roll out a batch through ``serve``; returns (outputs in prompt
        order, EOS-exclusive token lists; stats).

        ``slots=None`` gives every prompt its own slot: lock-step, where
        finished rows ride along idle until the stragglers drain. Fewer
        ``slots`` recycle early finishers' slots. ``max_new_tokens`` is a
        scalar or one limit per prompt; ``n_rounds`` is the makespan in
        verify rounds.

        ``watchdog``/``journal`` pass through to ``serve``;
        ``journal_keys`` names the rows' journal sessions (default the
        row index). ``resume`` maps journal keys to salvaged progress (a
        ``JournalSession`` or a token list): those rows re-admit by
        prefix re-prefill, and rows whose salvage already finished
        return without device work.
        """
        t0 = time.perf_counter()
        B = len(prompts)
        if problem_ids is None:
            problem_ids = list(range(B))
        max_new_arr = _as_max_new_array(
            self.engine.max_new_tokens if max_new_tokens is None
            else max_new_tokens, B)
        reqs = [
            Request(
                rid=i, problem_id=problem_ids[i], prompt=list(prompts[i]),
                max_new_tokens=int(max_new_arr[i]),
                journal_key=(None if journal_keys is None
                             else str(journal_keys[i])),
            )
            for i in range(B)
        ]
        to_serve = reqs
        if resume:
            from repro.fault.journal import JournalSession, resume_requests

            to_serve, pre_done = resume_requests(reqs, {
                str(k): v if isinstance(v, JournalSession)
                else JournalSession(key=str(k), tokens=list(v))
                for k, v in resume.items()
            })
            if pre_done and self.telemetry.enabled:
                self.telemetry.emit(
                    "resume", pre_done=len(pre_done),
                    salvaged=sum(len(r.output) for r in pre_done),
                )
        stats = RolloutStats()
        for _ in self.serve(
            to_serve, slots=slots, key=key, stats=stats,
            collect_effective_batch=collect_effective_batch,
            watchdog=watchdog, journal=journal,
        ):
            pass
        outputs = [r.output for r in reqs]
        stats.n_toks_emitted = int(sum(len(o) for o in outputs))
        stats.per_row_rounds = np.array([r.rounds for r in reqs], np.int64)
        stats.per_row_emitted = np.array([len(o) for o in outputs])
        stats.wall_time_s = time.perf_counter() - t0
        return outputs, stats

    def begin_iteration(self, epoch: int, update_norm: float = 0.0) -> None:
        self.epoch = epoch
        self.drafter.begin_iteration(epoch, update_norm)

    def set_params(self, params) -> None:
        """Policy updated by the learner — the drafter adapts via its
        sliding window; nothing to retrain (the paper's Insight-3). The
        new params move to this engine's device."""
        self.params = params if self.device is None \
            else self._to_device(params)
