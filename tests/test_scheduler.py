"""Continuous-batching scheduler + engine: admission order, slot
recycling, lock-step parity, and the long-tail makespan win."""

import jax
import numpy as np
import pytest

from conftest import make_params
from repro.configs.base import ModelConfig
from repro.core.drafter import DrafterConfig, SuffixDrafter
from repro.core.length_policy import LengthPolicy
from repro.core.scheduler import FINISHED, Request, SlotScheduler
from repro.core.spec_engine import EngineConfig, RolloutStats, SpecEngine

BASE = dict(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
    vocab_size=64, vocab_pad_multiple=8, dtype="float32",
)
CFG = ModelConfig(name="t", family="dense", **BASE)
PROMPTS = [[2, 3, 4, 5], [7, 8], [9, 10, 11, 12, 13, 14], [5, 6], [3, 3, 3]]
PIDS = ["a", "b", "c", "d", "e"]


def _warmed_policy():
    lp = LengthPolicy()
    for _ in range(5):
        for pid, L in [("s", 5.0), ("m", 20.0), ("l", 80.0)]:
            lp.observe(pid, L)
    return lp


# -- scheduler unit tests ----------------------------------------------------

def test_admission_order_longest_predicted_first():
    sched = SlotScheduler(2, _warmed_policy())
    reqs = [
        Request(rid=i, problem_id=pid)
        for i, pid in enumerate(["s", "m", "l", "s", "l"])
    ]
    for r in reqs:
        sched.submit(r)
    first = sched.next_admissions()
    # Both long requests admitted first (LPT), into the lowest free slots.
    assert [r.problem_id for r in first] == ["l", "l"]
    assert [r.rid for r in first] == [2, 4]  # ties resolve by submission
    assert [r.slot for r in first] == [0, 1]
    assert sched.next_admissions() == []  # pool full
    assert sched.n_queued == 3 and sched.n_running == 2


def test_slot_recycling_on_release():
    sched = SlotScheduler(2, _warmed_policy())
    reqs = [
        Request(rid=i, problem_id=pid)
        for i, pid in enumerate(["s", "m", "l", "s"])
    ]
    for r in reqs:
        sched.submit(r)
    first = sched.next_admissions()  # l, m
    assert [r.problem_id for r in first] == ["l", "m"]
    freed = sched.release(first[0])
    assert first[0].state == FINISHED and first[0].slot == -1
    nxt = sched.next_admissions()
    assert len(nxt) == 1 and nxt[0].slot == freed  # recycled slot
    assert nxt[0].problem_id == "s" and nxt[0].rid == 0
    sched.release(first[1])
    sched.release(nxt[0])
    last = sched.next_admissions()
    assert [r.rid for r in last] == [3]
    for r in last:
        sched.release(r)
    assert not sched.has_work() and sched.n_finished == 4


def test_scheduler_priority_fallbacks():
    sched = SlotScheduler(1)  # no length policy: token limit is priority
    a = Request(rid=0, max_new_tokens=8)
    b = Request(rid=1, max_new_tokens=64)
    c = Request(rid=2, max_new_tokens=16, predicted_len=1000.0)
    for r in (a, b, c):
        sched.submit(r)
    order = []
    while sched.has_work():
        got = sched.next_admissions()[0]
        order.append(got.rid)
        sched.release(got)
    assert order == [2, 1, 0]  # explicit prediction > larger limit > rest


# -- engine integration ------------------------------------------------------

def _engines(spec=True, max_new=30):
    params = make_params(CFG)
    def mk():
        return SpecEngine(
            params, CFG,
            EngineConfig(
                spec_enabled=spec, max_new_tokens=max_new, eos_token=1,
                use_budget_solver=False,
            ),
            drafter=SuffixDrafter(
                DrafterConfig(scope="problem+request", min_match=2)
            ),
        )
    return mk(), mk()


def test_continuous_parity_with_lockstep_greedy():
    lock, cont = _engines(spec=True)
    out0, st0 = lock.generate(PROMPTS, PIDS, key=jax.random.key(5))
    out1, st1 = cont.generate(
        PROMPTS, PIDS, slots=2, key=jax.random.key(11)
    )
    assert out0 == out1, "continuous batching must be lossless at T=0"
    assert st1.n_toks_emitted == st0.n_toks_emitted
    assert st1.per_row_emitted.tolist() == st0.per_row_emitted.tolist()


def test_continuous_recycles_on_eos_and_token_limit():
    _, eng = _engines(spec=True)
    limits = [4, 9, 2, 7, 5]
    reqs = [
        Request(rid=i, problem_id=PIDS[i], prompt=list(PROMPTS[i]),
                max_new_tokens=limits[i])
        for i in range(len(PROMPTS))
    ]
    stats = RolloutStats()
    done = list(eng.serve(reqs, slots=2, key=jax.random.key(3), stats=stats))
    assert len(done) == len(reqs)  # every request finishes exactly once
    assert sorted(r.rid for r in done) == list(range(len(reqs)))
    for r in reqs:
        assert r.state == FINISHED and r.slot == -1 and r.session is None
        assert r.emitted == len(r.output) <= r.max_new_tokens
        assert 0 <= r.admit_round <= r.finish_round
    # 5 requests through 2 slots: someone must have been admitted into a
    # recycled slot after round 0 (the EOS/limit release path).
    assert max(r.admit_round for r in reqs) > 0
    assert stats.n_toks_emitted == sum(len(r.output) for r in reqs)
    assert stats.n_rounds >= max(r.finish_round for r in reqs)


def test_continuous_makespan_beats_lockstep_waves_on_long_tail():
    """The acceptance bar: >=2x length spread, equal slots, >=20% fewer
    verify rounds, token-identical outputs."""
    slots = 4
    lengths = [40, 28, 20, 14, 12, 10, 9, 8, 7, 6, 5, 4]  # 10x spread
    n = len(lengths)
    rng = np.random.default_rng(0)
    prompts = [[2] + list(rng.integers(4, 60, size=3)) for _ in range(n)]
    pids = [f"p{i}" for i in range(n)]
    params = make_params(CFG)

    def mk():
        eng = SpecEngine(
            params, CFG,
            # eos that never fires: rounds are governed by the limits
            EngineConfig(spec_enabled=False, eos_token=-5),
        )
        for i, pid in enumerate(pids):  # LPT predictions from history
            for _ in range(4):
                eng.length_policy.observe(pid, float(lengths[i]))
        return eng

    lock = mk()
    order = sorted(range(n), key=lambda i: -lengths[i])
    lock_rounds = 0
    outs_lock = [None] * n
    for w0 in range(0, n, slots):
        wave = order[w0 : w0 + slots]
        o, st = lock.generate(
            [prompts[i] for i in wave], [pids[i] for i in wave],
            max_new_tokens=[lengths[i] for i in wave],
            key=jax.random.key(7),
        )
        lock_rounds += st.n_rounds
        for i, oi in zip(wave, o):
            outs_lock[i] = oi

    cont = mk()
    outs_cont, st = cont.generate(
        prompts, pids, slots=slots, max_new_tokens=lengths,
        key=jax.random.key(7),
    )
    assert outs_cont == outs_lock, "slot recycling must not change tokens"
    assert [len(o) for o in outs_cont] == lengths  # eos never fired
    reduction = 1.0 - st.n_rounds / max(lock_rounds, 1)
    assert reduction >= 0.20, (
        f"continuous must cut makespan rounds by >=20%: lock={lock_rounds} "
        f"cont={st.n_rounds} reduction={reduction:.2f}"
    )


def test_per_row_token_limits_are_exact():
    """max_new_tokens is a hard cap in both modes, including limit=1
    (the head token fills it — no bonus round)."""
    lock, cont = _engines(spec=True)
    limits = [1, 2, 7, 1, 3]
    o0, _ = lock.generate(PROMPTS, PIDS, max_new_tokens=limits,
                          key=jax.random.key(4))
    o1, _ = cont.generate(PROMPTS, PIDS, slots=2,
                          max_new_tokens=limits, key=jax.random.key(4))
    assert o0 == o1
    for o, lim in zip(o0, limits):
        assert len(o) <= lim


def test_generate_continuous_default_slots_and_stats():
    _, eng = _engines(spec=False, max_new=12)
    outs, st = eng.generate(PROMPTS, PIDS, key=jax.random.key(1))
    assert len(outs) == len(PROMPTS)
    assert st.per_row_rounds.shape == (len(PROMPTS),)
    assert st.n_toks_emitted == sum(len(o) for o in outs)
    assert all(len(o) <= 12 for o in outs)
    # effective batch never exceeds the pool
    _, eng2 = _engines(spec=False, max_new=12)
    _, st2 = eng2.generate(
        PROMPTS, PIDS, slots=2, key=jax.random.key(1),
        collect_effective_batch=True,
    )
    assert st2.effective_batch and max(st2.effective_batch) <= 2
