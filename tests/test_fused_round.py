"""Fused device-resident rounds vs the host-draft round.

The contract under test: with a shared PRNG stream, the fused program
(propose → block build → verify → commit → state update in ONE dispatch,
``core/fused_round.py``) emits *bit-identical* tokens to the round that
drafts the same ``problem``-scope trees through per-row host sessions
(``conftest.host_drafts``) at temperature 0 AND under seeded sampling —
lock-step and with slot recycling — and steady-state serving never
triggers a fresh jit compile after warmup.
"""

import jax
import numpy as np
import pytest

from conftest import host_drafts, make_params
from repro.configs.base import ModelConfig
from repro.core.drafter import DrafterConfig, SuffixDrafter
from repro.core.fused_round import emit_scan_device
from repro.core.spec_engine import EngineConfig, SpecEngine, _emit_scan

BASE = dict(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
    vocab_size=64, vocab_pad_multiple=8, dtype="float32",
)
DENSE = ModelConfig(name="t", family="dense", **BASE)
PROMPTS = [
    [2, 3, 4, 5], [7, 8], [9, 10, 11, 12, 13, 14], [5, 6],
    [3, 3, 3], [4, 4, 9, 2], [2, 2], [11, 12, 13],
]
PIDS = ["a", "b", "c", "d", "e", "a", "b", "c"]
LIMITS = [14, 9, 22, 7, 5, 11, 3, 18]


def _engine(params, cfg, *, host=False, temperature=0.0, window_size=16):
    """Fused rounds, or with ``host`` the host-draft round on the same
    drafts."""
    eng = SpecEngine(
        params, cfg,
        EngineConfig(
            max_new_tokens=24, max_draft=4, block_buckets=(0, 2, 4),
            eos_token=1, temperature=temperature,
        ),
        drafter=SuffixDrafter(
            DrafterConfig(scope="problem", min_match=1,
                          window_size=window_size)
        ),
    )
    return host_drafts(eng) if host else eng


def _two_epochs(eng, *, mode, key0=5, key1=7):
    """Epoch 0 lock-step (builds history), epoch 1 lock-step
    (``generate``) or through 3 recycled slots (``continuous``); returns
    (epoch-0 outputs, epoch-1 outputs, epoch-1 stats)."""
    eng.begin_iteration(0)
    o0, _ = eng.generate(PROMPTS, PIDS, max_new_tokens=LIMITS,
                         key=jax.random.key(key0))
    eng.begin_iteration(1)
    if mode == "generate":
        o1, st = eng.generate(PROMPTS, PIDS, max_new_tokens=LIMITS,
                              key=jax.random.key(key1))
    else:
        o1, st = eng.generate(
            PROMPTS, PIDS, slots=3, max_new_tokens=LIMITS,
            key=jax.random.key(key1),
        )
    return o0, o1, st


@pytest.mark.parametrize("mode", ["generate", "continuous"])
def test_fused_token_identity_greedy(mode):
    """T=0: fused rounds must be token-identical to the host-draft round
    lock-step and with slot recycling (warm drafter, real proposals in
    flight)."""
    params = make_params(DENSE)
    runs = {}
    for fuse in ("on", "off"):
        runs[fuse] = _two_epochs(
            _engine(params, DENSE, host=fuse == "off"), mode=mode
        )
    assert runs["on"][0] == runs["off"][0]
    assert runs["on"][1] == runs["off"][1]
    st = runs["on"][2]
    assert st.n_drafted > 0, "warm drafter must actually speculate"


@pytest.mark.parametrize("mode", ["generate", "continuous"])
def test_fused_token_identity_seeded_sampling(mode):
    """T>0 with a fixed seed: the fused path consumes the PRNG stream
    exactly like the host-draft round (per-round verify keys,
    per-request admission keys), so sampled outputs are bit-identical
    too."""
    params = make_params(DENSE)
    runs = {}
    for fuse in ("on", "off"):
        runs[fuse] = _two_epochs(
            _engine(params, DENSE, host=fuse == "off", temperature=0.8),
            mode=mode,
        )
    assert runs["on"][0] == runs["off"][0]
    assert runs["on"][1] == runs["off"][1]


def test_fused_ssm_family_runs_and_matches():
    """The fused program composes the staged-state recurrent commit
    (collect_states + commit_staged_cache) exactly like the host-draft
    round's verify."""
    cfg = ModelConfig(
        name="t-ssm", family="ssm", block_pattern=("mlstm", "slstm"),
        **{**BASE, "d_ff": 0, "rnn_width": 64},
    )
    params = make_params(cfg)
    runs = {}
    for fuse in ("on", "off"):
        runs[fuse] = _two_epochs(
            _engine(params, cfg, host=fuse == "off"), mode="generate"
        )
    assert runs["on"][1] == runs["off"][1]


def _matcher_values(tel):
    reg = tel.registry
    return (
        reg.value("das_matcher_rows_total", (("feed", "carried"),)),
        reg.value("das_matcher_rows_total", (("feed", "full"),)),
        reg.value("das_matcher_full_rounds_total"),
    )


def _serve_with_cancel(eng, stats):
    """Serve 24 requests on the epoch-0 problems, their prompts extended
    so rollouts stray from the history, into 4 slots (admissions
    mid-stream; finished rollouts publish history, so the forest repacks
    between rounds); when the first request finishes, cancel the
    lowest-rid resident one (an eviction). Returns outputs by rid."""
    from repro.core.scheduler import Request

    reqs = [Request(rid=i, problem_id=PIDS[i % 8],
                    prompt=PROMPTS[i % 8] + [2 + i // 8],
                    max_new_tokens=24 - i % 5) for i in range(24)]
    cancelled = False
    for done in eng.serve(reqs, slots=4, key=jax.random.key(7),
                          stats=stats):
        if not cancelled:
            resident = [r for r in reqs if r.slot >= 0 and r is not done
                        and r.finish_round < 0]
            if resident:
                resident[0].cancel_requested = cancelled = True
    return {r.rid: list(r.output) for r in reqs}


@pytest.mark.parametrize("tail", [8, 64])
def test_fused_carried_matcher_serve_parity_and_feed_counts(tail):
    """Serving with admissions mid-stream, forest repacks between rounds
    and an eviction: the fused round, whose matcher carries its
    registers across rounds, stays round-for-round identical to the
    fused round that feeds every row its whole tail from the root each
    round (a tail of 8 makes copied text outgrow it), and, where the
    tail holds every context, to the host-draft round. The host's ``das_matcher_*`` counters equal what the device state each
    round starts from says, and a round feeds rows in full exactly when
    a new forest or new roots were uploaded since the last round that
    fed: admitted rows wait for that sync unfed, evicted rows leave."""
    from repro import obs
    from repro.core.fused_round import matcher_feeds
    from repro.core.spec_engine import RolloutStats

    params = make_params(DENSE)
    runs = {}
    covers = tail > max(map(len, PROMPTS)) + 1 + 24  # every context fits
    for fuse in ("on", "whole", "host") if covers else ("on", "whole"):
        tel = obs.Telemetry()
        eng = SpecEngine(
            params, DENSE,
            EngineConfig(max_new_tokens=24, max_draft=4,
                         block_buckets=(0, 2, 4), eos_token=1),
            drafter=SuffixDrafter(DrafterConfig(
                scope="problem", min_match=1, window_size=16,
                device_tail=tail)),
            telemetry=tel,
        )
        if fuse == "host":
            host_drafts(eng)
        eng.begin_iteration(0)
        eng.generate(PROMPTS, PIDS, max_new_tokens=LIMITS,
                     key=jax.random.key(5))
        eng.begin_iteration(1)
        rounds, events = [], []
        base = _matcher_values(tel)  # epoch 0's feeds
        if fuse == "whole":
            get_fused, get_forget = eng._get_fused, eng._get_forget_matches

            def whole(K, R, get_fused=get_fused, get_forget=get_forget):
                fn = get_fused(K, R)

                def call(params, forest, cache, state, *rest):
                    return fn(params, forest, cache, get_forget()(state),
                              *rest)

                return call

            eng._get_fused = whole
        if fuse == "on":
            get_fused, get_forget = eng._get_fused, eng._get_forget_matches

            def fused(K, R, get_fused=get_fused, tel=tel, events=events):
                fn = get_fused(K, R)

                def call(params, forest, cache, state, roots, budgets, key):
                    node = np.asarray(state.match.node)
                    fed = np.zeros_like(node, bool)
                    if K > 0:
                        fed = matcher_feeds(
                            np.asarray(state.active), np.asarray(roots) >= 0,
                            np.asarray(budgets), node >= 0,
                            np.asarray(state.feed_from), tail)
                    rounds.append((K, int((fed & (node >= 0)).sum()),
                                   int((fed & (node < 0)).sum()),
                                   _matcher_values(tel)))
                    events.append("round")
                    return fn(params, forest, cache, state, roots, budgets,
                              key)

                return call

            def forget(get_forget=get_forget, events=events):
                events.append("sync")
                return get_forget()

            eng._get_fused, eng._get_forget_matches = fused, forget
        stats = RolloutStats()
        runs[fuse] = (_serve_with_cancel(eng, stats), stats, rounds, events,
                      eng, base)
    o_on, st_on, rounds, events, eng, base = runs["on"]
    for ref in runs:
        o_ref, st_ref = runs[ref][:2]
        assert o_on == o_ref, ref
        assert st_on.n_drafted == st_ref.n_drafted > 0, ref
        assert st_on.n_accepted == st_ref.n_accepted, ref
        assert st_on.round_accepts == st_ref.round_accepts, ref
    assert eng._evict_state_fn is not None  # the cancel evicted a row
    # host counters, round by round, equal the device state's truth
    prev = base
    for K, n_carried, n_full, vals in rounds:
        assert vals[0] - prev[0] == n_carried
        assert vals[1] - prev[1] == n_full
        assert vals[2] - prev[2] == (n_full > 0)
        prev = vals
    assert prev[0] > base[0] and prev[1] > base[1]
    # full rounds: exactly the rounds that feed after a sync
    full, synced, after_sync = [], False, []
    it = iter(rounds)
    for ev in events:
        if ev == "sync":
            synced = True
            continue
        K, _, n_full, _ = next(it)
        full.append(n_full > 0)
        if K > 0:
            after_sync.append(synced)
            synced = False
        else:
            after_sync.append(False)
    if tail >= max(LIMITS):  # a carried row can never drop off its tail
        assert full == after_sync
    else:
        assert all(f for f, a in zip(full, after_sync) if a)
    assert 0 < sum(full) <= events.count("sync")


def test_fused_respects_exact_limits_and_head_only_rows():
    """Per-row max_new_tokens stays a hard cap through the fused emit
    scan, including limit=1 (head token fills it, no round)."""
    params = make_params(DENSE)
    limits = [1, 2, 7, 1, 3, 5, 1, 4]
    outs = {}
    for fuse in ("on", "off"):
        eng = _engine(params, DENSE, host=fuse == "off")
        outs[fuse], _ = eng.generate(
            PROMPTS, PIDS, max_new_tokens=limits, key=jax.random.key(4)
        )
    assert outs["on"] == outs["off"]
    for o, lim in zip(outs["on"], limits):
        assert len(o) <= lim


def test_emit_scan_device_matches_host():
    """The device emit scan is the bit-exact twin of the host
    ``_emit_scan`` (EOS, limits, append-then-check)."""
    rng = np.random.default_rng(0)
    for _ in range(50):
        B, K1 = int(rng.integers(1, 6)), int(rng.integers(1, 6))
        cand = rng.integers(0, 4, size=(B, K1)).astype(np.int32)
        n_new = rng.integers(1, K1 + 1, size=B).astype(np.int64)
        remaining = rng.integers(0, 8, size=B).astype(np.int64)
        h_take, h_alive = _emit_scan(cand, n_new, remaining, eos=1)
        d_take, d_alive = emit_scan_device(
            cand, n_new.astype(np.int32), remaining.astype(np.int32), 1
        )
        assert np.array_equal(h_take, np.asarray(d_take))
        assert np.array_equal(h_alive, np.asarray(d_alive))


@pytest.mark.parametrize("device", ["on", "off"])
def test_steady_state_serve_never_recompiles(device):
    """Recompile guard: after a warmup serving epoch over mixed-length
    requests, further epochs of the same workload must trigger ZERO new
    jit compilations — with fused rounds on device drafts and with the
    host-draft round alike. (RL training serves the same problem set every
    epoch; a bucket flip or shape wobble here would recompile mid-run.)
    """
    params = make_params(DENSE)
    # Small sliding window: steady state = saturated windows (sizes
    # oscillate inside the compaction cycle, where the monotone bucket
    # floors guarantee stable kernel geometry). While windows are still
    # FILLING the forest legitimately grows and may cross a pow2 bucket
    # — that is warmup, not steady state.
    eng = _engine(params, DENSE, host=device == "off", window_size=4)

    def serve_epoch(epoch):
        eng.begin_iteration(epoch)
        outs, _ = eng.generate(
            PROMPTS, PIDS, slots=4, max_new_tokens=LIMITS,
            key=jax.random.key(11 + epoch),
        )
        return outs

    for epoch in range(5):  # compile variants + saturate every window
        serve_epoch(epoch)
    n0 = eng.compile_count()
    assert n0 > 0
    for epoch in (5, 6):
        serve_epoch(epoch)
        assert eng.compile_count() == n0, (
            f"epoch {epoch} recompiled in steady state "
            f"(device drafts {device})"
        )


def test_fused_strictly_fewer_transfers_per_round():
    """The fused round's host↔device traffic: one budget upload + one
    packed result download per round, vs the host-draft round's
    block/budget/flag uploads and two-array download. Crossings outside
    the round are counted apart: per admission chunk the prefill's two
    uploads, the cache-row scatter and the first-token download; for
    fused rounds the round state's upload (5 arrays), each admission's
    state write (5) and each forest/roots sync (1)."""
    from repro.core.scheduler import Request
    from repro.core.spec_engine import RolloutStats

    params = make_params(DENSE)
    per_round = {}
    for fuse in ("on", "off"):
        eng = _engine(params, DENSE, host=fuse == "off")
        eng.begin_iteration(0)
        eng.generate(PROMPTS, PIDS, max_new_tokens=LIMITS,
                     key=jax.random.key(5))
        eng.begin_iteration(1)
        calls = {}
        for name in ("_get_prefill", "_get_admit_state",
                     "_get_forget_matches"):
            def counted(*a, name=name, get=getattr(eng, name)):
                fn = get(*a)

                def call(*args):
                    calls[name] = calls.get(name, 0) + 1
                    return fn(*args)

                return call

            setattr(eng, name, counted)
        reqs = [
            Request(rid=i, problem_id=PIDS[i], prompt=list(PROMPTS[i]),
                    max_new_tokens=LIMITS[i])
            for i in range(len(PROMPTS))
        ]
        stats = RolloutStats()
        list(eng.serve(reqs, slots=4, key=jax.random.key(7), stats=stats))
        outside = 4 * calls["_get_prefill"]
        if fuse == "on":
            outside += (5 + 5 * calls["_get_admit_state"]
                        + calls["_get_forget_matches"])
        assert stats.n_rounds > 0
        per_round[fuse] = (
            stats.n_h2d + stats.n_d2h - outside) / stats.n_rounds
    assert per_round == {"on": 2.0, "off": 5.0}, per_round
    assert per_round["on"] < per_round["off"], per_round


def test_fused_rounds_draft_with_the_xla_core(monkeypatch):
    """The main path drafts with the XLA scalar core on every backend:
    serving with fused rounds and real proposals traces no Pallas
    kernel (none can run interpreted on CPU, and none reaches the TPU
    lowering that refuses them)."""
    from jax.experimental import pallas as pl

    def refuse(*args, **kwargs):
        raise AssertionError("the main path reached pallas_call")

    monkeypatch.setattr(pl, "pallas_call", refuse)
    eng = _engine(make_params(DENSE), DENSE)
    _, _, st = _two_epochs(eng, mode="continuous")
    assert st.n_drafted > 0
    assert eng._fused_jit and not eng._verify_jit


PHASES = ("propose", "forward", "accept", "commit")


def _scoped_instructions(hlo_text):
    """(instruction name, first phase scope or None) of every HLO
    instruction that carries ``op_name`` metadata."""
    import re

    out = []
    for name, op in re.findall(
        r'^\s*(?:ROOT )?%(\S+) = [^\n]*?metadata=\{op_name="([^"]*)"',
        hlo_text, re.M,
    ):
        parts = op.split("/")
        out.append((name, next((p for p in parts if p in PHASES), None)))
    return out


@pytest.mark.parametrize("program", ["fused", "verify"])
def test_round_phases_are_named_scopes(program):
    """The compiled round names its phases in every instruction's
    ``op_name``: the fused program all four, the host-draft round's
    verify (the same ``verify_step``) forward, accept and commit. The layer scan's
    ``while`` falls under ``forward``."""
    from repro.core.fused_round import build_fused_round, make_state
    from repro.kernels.suffix_match import ops as sm_ops
    from repro.models import model as M

    params = make_params(DENSE)
    B, K = 4, 4
    cache = M.init_cache(DENSE, B, 64, 0)
    key = jax.random.key(0)
    if program == "fused":
        fn = build_fused_round(
            DENSE, K=K, temperature=0.0, eos_token=1,
            recurrent=False, attn_impl="xla", min_match=1,
        )
        forest, _ = sm_ops.pack_forest([])
        state = make_state(np.zeros(B), np.full((B, 8), -1),
                           np.zeros(B, bool), np.zeros(B), np.ones(B))
        args = (params, forest, cache, state, np.full(B, -1, np.int32),
                np.zeros(B, np.int32), key)
        want = set(PHASES)
    else:
        fn = _engine(params, DENSE, host=True)._get_verify(K)
        args = (params, cache, np.zeros((B, K + 1), np.int32),
                np.zeros(B, np.int32), np.ones(B, bool), key)
        want = {"forward", "accept", "commit"}
    scoped = _scoped_instructions(fn.lower(*args).compile().as_text())
    assert {s for _, s in scoped} - {None} == want
    assert any(n.startswith("while") and s == "forward" for n, s in scoped)
