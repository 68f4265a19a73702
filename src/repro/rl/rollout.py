"""Rollout phase: batched generation with G samples per problem.

Wraps the speculative engine for RL: replicates each problem G times
(all G samples share the same per-problem suffix tree — exactly the
reuse the paper exploits), computes verifiable rewards, and packs the
result into a GRPO training batch. The baseline (no speculation) is the
same code path with ``spec_enabled=False`` so timing comparisons are
apples-to-apples.

With ``continuous=True`` the worker streams the N = problems × G
requests through the engine's fixed slot pool (``slots`` device rows,
longest-predicted-first admission, slot recycling) instead of one giant
padded lock-step batch — the long tail no longer pins dead slots, and
finished groups' rollouts sharpen the drafter for still-running
stragglers mid-rollout. Outputs are token-identical at temperature 0.
"""

from __future__ import annotations

import collections
import logging
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.spec_engine import RolloutStats, SpecEngine
from repro.data.tasks import Problem, Task
from repro.data.tokenizer import PAD
from repro.fault.watchdog import StallError
from repro.rl.grpo import group_advantages

log = logging.getLogger("repro.rl.rollout")


@dataclass
class RolloutBatch:
    tokens: np.ndarray  # (N, S) prompt+response, right-padded
    resp_mask: np.ndarray  # (N, S) bool, True on response tokens
    advantages: np.ndarray  # (N,)
    rewards: np.ndarray  # (N,)
    responses: List[List[int]]
    problems: List[Problem]
    stats: RolloutStats
    gen_time_s: float


def pack_train_arrays(
    prompts: Sequence[Sequence[int]], outs: Sequence[Sequence[int]]
) -> Tuple[np.ndarray, np.ndarray]:
    """Right-padded (tokens, resp_mask) train arrays (bucketed width to
    bound train-step recompiles) — shared by the single- and
    multi-worker rollout paths."""
    N = len(prompts)
    S = max(len(p) + len(o) for p, o in zip(prompts, outs)) + 1
    S = ((S + 31) // 32) * 32
    tokens = np.full((N, S), PAD, np.int32)
    resp_mask = np.zeros((N, S), bool)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = list(p) + list(o)
        tokens[i, : len(seq)] = seq
        resp_mask[i, len(p) : len(seq)] = True
    return tokens, resp_mask


class RolloutWorker:
    def __init__(
        self,
        engine: SpecEngine,
        task: Task,
        group_size: int = 8,
        *,
        continuous: bool = False,
        slots: Optional[int] = None,
        watchdog=None,
        journal=None,
    ):
        self.engine = engine
        self.task = task
        self.G = group_size
        self.continuous = continuous
        self.slots = slots  # pool size; None = one slot per request
        # Optional repro.fault.RolloutWatchdog: deadlines this worker's
        # verify rounds; a stall raises StallError out of rollout(),
        # which the fault-tolerant MultiWorkerRollout turns into a
        # re-queue to the surviving workers.
        self.watchdog = watchdog
        # Optional repro.fault.RolloutJournal: every rollout's accepted
        # tokens become crash-durable round by round under the stable
        # key "{pid}#{g}", so a dead worker's in-flight progress is
        # salvageable (``journal.live_sessions()``) instead of lost.
        self.journal = journal

    def rollout(
        self,
        problems: Sequence[Problem],
        *,
        key,
        max_new_tokens: Optional[int] = None,
        collect_effective_batch: bool = False,
        resume=None,
    ) -> RolloutBatch:
        """Roll out ``problems`` × G samples.

        ``resume`` maps journal keys (``"{pid}#{g}"``) to salvaged
        sessions from a failed worker's journal: matching rows re-admit
        via the engine's prefix re-prefill (token-identical at T=0)
        instead of regenerating from token zero. A resumed rollout
        always takes the worker's slot pool (``slots``), as
        ``continuous=True`` does; at T=0 the outputs are the same as
        lock-step's.
        """
        t0 = time.perf_counter()
        prompts, pids, probs, jkeys = [], [], [], []
        for p in problems:
            for g in range(self.G):
                prompts.append(list(p.prompt))
                pids.append(p.pid)
                probs.append(p)
                jkeys.append(f"{p.pid}#{g}")
        outs, stats = self.engine.generate(
            prompts, pids,
            slots=self.slots if self.continuous or resume else None,
            max_new_tokens=max_new_tokens, key=key,
            collect_effective_batch=collect_effective_batch,
            watchdog=self.watchdog, journal=self.journal,
            journal_keys=jkeys, resume=resume,
        )
        gen_time = time.perf_counter() - t0
        rewards = np.array(
            [self.task.reward(pr, o) for pr, o in zip(probs, outs)],
            np.float32,
        )
        adv = group_advantages(rewards, self.G)
        tokens, resp_mask = pack_train_arrays(prompts, outs)
        return RolloutBatch(
            tokens=tokens,
            resp_mask=resp_mask,
            advantages=adv.astype(np.float32),
            rewards=rewards,
            responses=outs,
            problems=probs,
            stats=stats,
            gen_time_s=gen_time,
        )


def merge_rollout_stats(parts: Sequence[RolloutStats]) -> RolloutStats:
    """Sum per-worker rollout stats into one fleet view (counters add,
    traces concatenate; per-row views are reassembled by the caller)."""
    out = RolloutStats()
    for st in parts:
        out.n_rounds += st.n_rounds
        out.n_fwd += st.n_fwd
        out.n_toks_proposed += st.n_toks_proposed
        out.n_toks_emitted += st.n_toks_emitted
        out.n_drafted += st.n_drafted
        out.n_accepted += st.n_accepted
        out.wall_time_s += st.wall_time_s
        out.host_time_s += st.host_time_s
        out.n_h2d += st.n_h2d
        out.n_d2h += st.n_d2h
        out.effective_batch.extend(st.effective_batch)
        out.round_accepts.extend(st.round_accepts)
    return out


class MultiWorkerRollout:
    """N rollout workers sharing one batch — the multi-worker rollout
    phase over the pooled history service.

    Each call partitions the problem batch across the workers
    (round-robin, **rotated** every call so a problem's rollouts come
    from a different worker each step — with a static partition every
    worker would only ever revisit its own history and pooling would be
    pointless). Workers run their slices through their own engines;
    with remote-backed drafters each worker's publishes are flushed
    before the next worker starts, so later slices draft against trees
    the earlier slices just warmed (the in-process stand-in for the
    fleet's concurrent publish stream — ordering per problem stays
    deterministic, which keeps shard trees oracle-identical).

    The merged ``RolloutBatch`` is in the original request order with
    group advantages recomputed over the merged rewards, so the trainer
    cannot tell it from a single-worker batch.

    With ``fault_tolerant=True`` a worker that stalls (``StallError``
    from its watchdog), dies mid-slice, or loses its shards (``OSError``)
    does not sink the step: the worker is expired for this call and its
    slice re-queues — with the slice's ORIGINAL sampling key — to a
    survivor,
    so at T=0 the merged batch is token-identical to the no-failure run
    (greedy verification makes outputs worker-independent; at T>0 the
    sampling stream is slice-bound, so determinism per slice holds
    too). A ``supervisor`` (``repro.fault.ShardSupervisor``) is polled
    once per call and after every failure, so dead shards restart at
    step granularity even without the background supervision thread.
    The only residual effect of a mid-slice failure is duplicate
    publishes from the dead worker's completed rows — which the shards
    dedup, and which could only influence drafting (acceptance), never
    verified tokens.
    """

    def __init__(
        self,
        workers: Sequence[RolloutWorker],
        rotate: bool = True,
        *,
        fault_tolerant: bool = False,
        supervisor=None,
        flush_timeout: float = 10.0,
        flush_retries: int = 3,
        telemetry=None,
    ):
        from repro import obs

        if not workers:
            raise ValueError("MultiWorkerRollout needs >= 1 worker")
        gs = {w.G for w in workers}
        if len(gs) != 1:
            raise ValueError(f"workers disagree on group size: {gs}")
        self.workers = list(workers)
        self.G = self.workers[0].G
        self.rotate = bool(rotate)
        self.fault_tolerant = bool(fault_tolerant)
        self.supervisor = supervisor
        self.flush_timeout = float(flush_timeout)
        self.flush_retries = int(flush_retries)
        self.telemetry = (
            telemetry if telemetry is not None else obs.get_telemetry()
        )
        # Counter-shaped fleet view mirrored into the registry — the
        # existing ``mw.stats["worker_failures"]`` reads are unchanged.
        self.stats = obs.MirroredCounter(
            sink=self.telemetry.mirror_sink(
                "das_rollout_stat_total", "MultiWorkerRollout counters"
            )
        )
        self._calls = 0

    @property
    def engine(self):
        """Lead worker's engine (trainer introspection compatibility)."""
        return self.workers[0].engine

    def _flush_worker(self, worker: RolloutWorker) -> None:
        remote = worker.engine.drafter.remote
        if remote is None or remote.flush(timeout=self.flush_timeout):
            return
        if not self.fault_tolerant:
            # The barrier is what keeps shard trees oracle-identical;
            # proceeding with unacked publishes would silently diverge.
            raise RuntimeError(
                "history-service publish flush timed out: a shard is "
                "unreachable and the epoch barrier cannot be enforced"
            )
        # Fault-tolerant: force-restart dead shards between attempts
        # (the client's outbox resends, shards dedup), then degrade —
        # a weaker barrier only staggers when peers see this worker's
        # history, which affects drafting, never tokens.
        for _ in range(self.flush_retries):
            if self.supervisor is not None:
                self.supervisor.poll(force=True)
            if remote.flush(timeout=self.flush_timeout):
                return
        self.stats["degraded_flushes"] += 1
        self.telemetry.emit(
            "degraded_flush", retries=self.flush_retries,
            timeout_s=self.flush_timeout,
        )
        log.warning(
            "publish flush still timing out after %d shard-restart "
            "attempts; continuing with a degraded epoch barrier (peers "
            "see this worker's rollouts late)", self.flush_retries,
        )

    def rollout(
        self,
        problems: Sequence[Problem],
        *,
        key,
        max_new_tokens: Optional[int] = None,
        collect_effective_batch: bool = False,
    ) -> RolloutBatch:
        t0 = time.perf_counter()
        N = len(self.workers)
        off = (self._calls % N) if self.rotate else 0
        self._calls += 1
        # problem j -> worker (j + off) % N; slices keep problem order
        assign = [[] for _ in range(N)]
        for j, p in enumerate(problems):
            assign[(j + off) % N].append(j)
        keys = jax.random.split(key, N)
        if self.supervisor is not None:
            self.supervisor.poll()  # restart dead shards before the step
        # Work queue of (worker, slice, slice key, salvage): a failed
        # worker's slice goes back on the queue addressed to a
        # survivor, carrying whatever progress the dead worker's
        # journal holds so the survivor resumes instead of regenerating.
        queue = collections.deque(
            (w, idxs, keys[w], None) for w, idxs in enumerate(assign)
            if idxs
        )
        expired: set = set()
        slices: List[Tuple[List[int], RolloutBatch]] = []
        while queue:
            w, idxs, wkey, salvage = queue.popleft()
            try:
                part = self.workers[w].rollout(
                    [problems[j] for j in idxs], key=wkey,
                    max_new_tokens=max_new_tokens,
                    collect_effective_batch=collect_effective_batch,
                    resume=salvage,
                )
            except (StallError, OSError) as exc:
                # StallError: the watchdog expired the worker (or it died
                # at a journal commit). OSError: its history-service
                # connection failed. Anything else — a JAX runtime error
                # such as a device OOM among them — is a fault of the
                # program, not of one worker: it propagates.
                if not self.fault_tolerant:
                    raise
                expired.add(w)
                self.stats["worker_failures"] += 1
                survivors = [v for v in range(N) if v not in expired]
                if not survivors:
                    raise  # nobody left to hand the work to
                if self.supervisor is not None:
                    # the root cause may be a dead shard, not the worker
                    self.supervisor.poll()
                # Salvage the dead worker's journaled in-flight progress
                # (in-memory mirror — no file round-trip needed while
                # the journal object is still reachable), merged over
                # whatever salvage this slice already carried.
                jrnl = getattr(self.workers[w], "journal", None)
                if jrnl is not None:
                    merged = dict(salvage) if salvage else {}
                    merged.update(jrnl.live_sessions())
                    salvage = merged or None
                n_salvaged = (
                    sum(len(s.tokens) for s in salvage.values())
                    if salvage else 0
                )
                self.stats["salvaged_tokens"] += n_salvaged
                # Re-queue under the slice's ORIGINAL key: outputs stay
                # identical at T=0 regardless of executor, and at T>0
                # the sampling stream follows the slice, not the worker.
                v = survivors[w % len(survivors)]
                queue.append((v, idxs, wkey, salvage))
                self.stats["requeued_problems"] += len(idxs)
                flt = getattr(self.telemetry, "flight", None)
                if flt is not None and flt.enabled:
                    # Trace handoff: ONE ``handoff`` event per salvaged
                    # in-flight trace — the survivor's resume continues
                    # the dead worker's trace, and the Perfetto flow
                    # arrow crosses worker tracks exactly here.
                    traced = [
                        s.trace for s in (salvage or {}).values()
                        if s.trace is not None and not s.finished
                    ]
                    for tr in traced:
                        flt.record(
                            tr, "handoff", from_worker=w, to_worker=v,
                            error=type(exc).__name__,
                        )
                    if not traced:  # never silently absent
                        flt.record(
                            None, "handoff", from_worker=w, to_worker=v,
                            n_problems=len(idxs),
                            error=type(exc).__name__,
                        )
                self.telemetry.emit(
                    "watchdog_requeue", worker=w, to_worker=v,
                    n_problems=len(idxs), error=str(exc),
                    salvaged_tokens=n_salvaged,
                )
                log.warning(
                    "rollout worker %d expired (%s); re-queued %d "
                    "problem(s) to worker %d (%d journaled tokens "
                    "salvaged)", w, exc, len(idxs), v, n_salvaged,
                )
                continue
            # Epoch barrier semantics: the next worker (and the next
            # trainer step) must see these rollouts on the shards.
            self._flush_worker(self.workers[w])
            slices.append((idxs, part))

        # -- reassemble in original problem order --------------------------
        G = self.G
        outs: List[List[int]] = [None] * (len(problems) * G)
        rewards = np.zeros(len(problems) * G, np.float32)
        probs: List[Problem] = [None] * (len(problems) * G)
        prompts: List[List[int]] = [None] * (len(problems) * G)
        for idxs, part in slices:
            for local, j in enumerate(idxs):
                for g in range(G):
                    src = local * G + g
                    dst = j * G + g
                    outs[dst] = part.responses[src]
                    rewards[dst] = part.rewards[src]
                    probs[dst] = part.problems[src]
                    prompts[dst] = list(problems[j].prompt)
        adv = group_advantages(rewards, G)
        tokens, resp_mask = pack_train_arrays(prompts, outs)
        stats = merge_rollout_stats([part.stats for _, part in slices])
        stats.per_row_emitted = np.array([len(o) for o in outs])
        return RolloutBatch(
            tokens=tokens,
            resp_mask=resp_mask,
            advantages=adv.astype(np.float32),
            rewards=rewards,
            responses=outs,
            problems=probs,
            stats=stats,
            gen_time_s=time.perf_counter() - t0,
        )
