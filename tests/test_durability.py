"""Durability suite: write-ahead token journal, preemptible slots,
graceful drain.

The contract under test: an in-flight rollout can die anywhere — worker
crash, SIGKILL-grade process death, slot preemption, drain deadline —
and the journaled prefix resumes **token-identically at T=0** via
prefix re-prefill. The journal itself loses at most the final un-synced
round (torn tail truncates, never raises); corruption before the tail
quarantines; a future schema refuses loudly without quarantining.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from conftest import host_drafts, make_params
from repro.core.scheduler import (
    CANCELLED,
    EXPIRED,
    FINISHED,
    PREEMPTED,
    QUEUED,
    RUNNING,
    PreemptionPolicy,
    Request,
    SchedulerStateError,
    SlotScheduler,
)
from repro.core.spec_engine import EngineConfig, SpecEngine
from repro.fault import (
    DrainController,
    FaultPlan,
    JournalCorruptError,
    JournalError,
    RolloutJournal,
    VirtualClock,
    resume_requests,
    tear_journal_tail,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# journal file format
# ---------------------------------------------------------------------------
class TestJournal:
    def test_round_trip(self, tmp_path):
        p = str(tmp_path / "j.wal")
        j = RolloutJournal(p)
        j.begin("a", [1, 2, 3], problem_id="p0", max_new_tokens=8)
        j.note("a", [5, 6])
        j.commit()
        j.note("a", [7])
        j.finish("a", n_emitted=3)
        j.commit()
        j.close()
        sess = RolloutJournal.recover(p)
        s = sess["a"]
        assert s.tokens == [5, 6, 7]
        assert s.finished and s.status == FINISHED
        assert s.prompt == [1, 2, 3]
        assert s.problem_id == "p0" and s.max_new_tokens == 8
        assert not s.resumable

    def test_torn_tail_truncates_never_raises(self, tmp_path):
        p = str(tmp_path / "j.wal")
        j = RolloutJournal(p)
        j.begin("a", [1], max_new_tokens=8)
        j.note("a", [5, 6])
        j.commit()
        j.note("a", [7, 8])
        j.commit()
        j.close()
        tear_journal_tail(p, drop_bytes=3)  # rip into the final frame
        sess = RolloutJournal.recover(p)
        # at most the final record lost; everything before it survives
        assert sess["a"].tokens == [5, 6]
        assert sess["a"].resumable
        # the tear was truncated in place: a second recovery is clean
        # and byte-stable
        size = os.path.getsize(p)
        sess2 = RolloutJournal.recover(p)
        assert sess2["a"].tokens == [5, 6]
        assert os.path.getsize(p) == size

    def test_pre_tail_corruption_quarantines(self, tmp_path):
        p = str(tmp_path / "j.wal")
        j = RolloutJournal(p)
        j.begin("a", [1], max_new_tokens=8)
        for r in range(6):
            j.note("a", [10 + r])
            j.commit()
        j.close()
        with open(p, "r+b") as f:  # bit rot mid-file, not at the tail
            f.seek(os.path.getsize(p) // 2)
            f.write(b"\xff" * 8)
        with pytest.raises(JournalCorruptError):
            RolloutJournal.recover(p)
        assert not os.path.exists(p)
        assert os.path.exists(p + ".corrupt")

    def test_future_schema_raises_without_quarantine(self, tmp_path):
        import struct
        import zlib

        p = str(tmp_path / "j.wal")
        payload = json.dumps({"k": "h", "v": 999}).encode()
        with open(p, "wb") as f:
            f.write(struct.pack("<II", len(payload), zlib.crc32(payload)))
            f.write(payload)
        with pytest.raises(JournalError) as ei:
            RolloutJournal.recover(p)
        assert not isinstance(ei.value, JournalCorruptError)
        assert os.path.exists(p)  # a rollback must not eat a newer WAL
        assert not os.path.exists(p + ".corrupt")

    def test_begin_resets_stale_key_resume_continues(self, tmp_path):
        # Stable keys ("pid#g") are reused across training steps: a
        # plain begin() starts a new logical rollout (no token leakage
        # from the previous step or from a stale crashed tail), while
        # begin(resume=True) continues the unfinished accumulation.
        p = str(tmp_path / "j.wal")
        j = RolloutJournal(p)
        j.begin("k", [1], max_new_tokens=8)
        j.note("k", [11, 12])
        j.commit()  # crash here: "k" left unfinished
        j.begin("k", [2], max_new_tokens=8)  # next step, same key
        j.note("k", [21])
        j.commit()
        j.close()
        sess = RolloutJournal.recover(p)
        assert sess["k"].tokens == [21]  # old tail did NOT leak
        assert sess["k"].prompt == [2]

        j2 = RolloutJournal(p)
        j2.adopt(sess)
        j2.begin("k", [2], max_new_tokens=8, resume=True)
        j2.note("k", [22])
        j2.commit()
        j2.close()
        sess2 = RolloutJournal.recover(p)
        assert sess2["k"].tokens == [21, 22]  # resume continued

    def test_group_commit_batches_and_fsync_amortizes(self, tmp_path):
        p = str(tmp_path / "j.wal")
        j = RolloutJournal(p, fsync_every=4)
        j.begin("a", [1])
        j.begin("b", [2])
        j.note("a", [5])
        j.note("b", [6])
        assert j.pending_records == 4
        assert j.commit() == 4  # one write for the whole round
        assert j.pending_records == 0
        assert j.commit() == 0  # nothing buffered -> no I/O
        j.close()


# ---------------------------------------------------------------------------
# scheduler lifecycle
# ---------------------------------------------------------------------------
class TestLifecycle:
    def _sched(self, n=2):
        return SlotScheduler(n, clock=VirtualClock())

    def test_full_legal_cycle_and_counters(self):
        s = self._sched()
        r = Request(rid=0, prompt=[1], max_new_tokens=8)
        s.submit(r)
        assert r.state == QUEUED
        (adm,) = s.next_admissions()
        assert adm is r and r.state == RUNNING and r.slot == 0
        s.preempt(r)
        assert r.state == PREEMPTED and r.slot == -1 and r.n_preempted == 1
        s.submit(r)  # PREEMPTED -> QUEUED is the one legal re-entry
        assert r.state == QUEUED
        (adm,) = s.next_admissions()
        s.release(adm)
        assert r.state == FINISHED
        assert s.n_preempted == 1 and s.n_finished == 1

    def test_illegal_transitions_raise_taxonomy_rooted(self):
        s = self._sched()
        r = Request(rid=0, prompt=[1])
        s.submit(r)
        (r,) = s.next_admissions()
        s.release(r)
        with pytest.raises(SchedulerStateError):
            s.release(r)  # FINISHED is terminal
        with pytest.raises(SchedulerStateError):
            s.submit(r)
        with pytest.raises(SchedulerStateError):
            s.cancel(r)
        assert issubclass(SchedulerStateError, ValueError)

    def test_cancel_and_expire_preserve_partial_output(self):
        s = self._sched(1)
        a, b = Request(rid=0, prompt=[1]), Request(rid=1, prompt=[2])
        s.submit(a)
        s.submit(b)
        (ra,) = s.next_admissions()  # one slot: only a admits
        ra.output.extend([7, 8])
        s.cancel(ra)
        assert ra.state == CANCELLED and ra.output == [7, 8]
        s.expire(b)  # still queued
        assert b.state == EXPIRED
        assert s.n_cancelled == 1 and s.n_expired == 1
        # b's queue entry is dead: nothing left to admit
        assert s.next_admissions() == []

    def test_due_requests_on_virtual_clock(self):
        clk = VirtualClock()
        s = SlotScheduler(1, clock=clk)
        r = Request(rid=0, prompt=[1], deadline_s=5.0)
        s.submit(r)
        assert s.due_requests() == []
        clk.advance(6.0)
        assert s.due_requests() == [r]

    def test_preemption_victims_capped_by_waiters(self):
        s = self._sched(2)
        res = [Request(rid=i, prompt=[1], max_new_tokens=32)
               for i in range(2)]
        for r in res:
            s.submit(r)
        s.next_admissions()
        for r in res:
            r.admit_round = 0
        pol = PreemptionPolicy(max_resident_rounds=4)
        # no waiters -> never evict (nobody would backfill the slot)
        assert s.preemption_victims(pol, round_no=10) == []
        w = Request(rid=9, prompt=[2], max_new_tokens=8)
        s.submit(w)
        victims = s.preemption_victims(pol, round_no=10)
        assert len(victims) == 1  # capped at n_waiting
        assert victims[0].slot == 0  # deterministic tie-break


# ---------------------------------------------------------------------------
# serve-level durability (token identity under preempt/crash/drain)
# ---------------------------------------------------------------------------
ECFG = dict(max_new_tokens=48, max_draft=8, eos_token=1)


def _mk_requests():
    rng = np.random.default_rng(0)
    return [
        Request(
            rid=i, problem_id=f"p{i % 3}",
            prompt=[int(t) for t in rng.integers(2, 60, size=5 + i % 4)],
            max_new_tokens=16 + 8 * (i % 3),
        )
        for i in range(6)
    ]


def _serve(eng, reqs, *, slots=3, **kw):
    for _ in eng.serve(reqs, slots=slots, key=jax.random.key(1), **kw):
        pass
    return {r.rid: list(r.output) for r in reqs}


@pytest.fixture(scope="module")
def served_baseline(tiny_dense):
    """Uninterrupted serve of the canonical request set — the token-
    identity reference every durability test compares against."""
    params = make_params(tiny_dense)
    eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
    reqs = _mk_requests()
    base = _serve(eng, reqs)
    assert all(len(v) > 0 for v in base.values())
    return params, base


class TestServeDurability:
    def test_preempt_resume_parity_fused(self, tiny_dense, served_baseline):
        params, base = served_baseline
        eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        reqs = _mk_requests()
        out = _serve(eng, reqs, slots=2,
                     preemption=PreemptionPolicy(max_resident_rounds=2))
        assert sum(r.n_preempted for r in reqs) > 0
        assert out == base

    def test_preempt_resume_parity_unfused(self, tiny_dense,
                                           served_baseline):
        params, base = served_baseline
        eng = host_drafts(
            SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        )
        reqs = _mk_requests()
        out = _serve(eng, reqs, slots=2,
                     preemption=PreemptionPolicy(max_resident_rounds=3))
        assert sum(r.n_preempted for r in reqs) > 0
        assert out == base

    def test_journal_round_trip_and_crash_recovery(
        self, tiny_dense, served_baseline, tmp_path
    ):
        params, base = served_baseline
        jp = str(tmp_path / "serve.wal")
        j = RolloutJournal(jp, fsync_every=4)
        eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        reqs = _mk_requests()
        out = _serve(eng, reqs, journal=j)
        j.close()
        assert out == base
        sess = RolloutJournal.recover(jp)
        assert all(s.finished for s in sess.values())
        for r in reqs:  # journal replay == served output, token for token
            assert sess[str(r.rid)].tokens == r.output

        # crash stand-in: throw away the last 55% of the file, recover,
        # resume — must converge to the exact uninterrupted outputs
        with open(jp, "r+b") as f:
            f.truncate(int(os.path.getsize(jp) * 0.45))
        sess = RolloutJournal.recover(jp)
        assert any(s.resumable and s.tokens for s in sess.values())
        reqs2 = _mk_requests()
        to_serve, pre_done = resume_requests(reqs2, sess)
        assert len(to_serve) + len(pre_done) == len(reqs2)
        j2 = RolloutJournal(jp)
        j2.adopt(sess)
        eng2 = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        _serve(eng2, to_serve, journal=j2)
        j2.close()
        assert {r.rid: list(r.output) for r in reqs2} == base
        # the resumed engine reported salvaged tokens
        # (mirror of das_resumed_tokens_total)
        sess3 = RolloutJournal.recover(jp)
        assert all(s.finished for s in sess3.values())

    def test_drain_deadline_on_virtual_clock(self, tiny_dense,
                                             served_baseline, tmp_path):
        params, base = served_baseline
        clk = VirtualClock()
        jp = str(tmp_path / "drain.wal")
        j = RolloutJournal(jp)
        drain = DrainController(deadline_s=5.0, clock=clk)
        eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        reqs = _mk_requests()
        served = []
        for fin in eng.serve(reqs, slots=2, key=jax.random.key(1),
                             journal=j, drain=drain, clock=clk):
            served.append(fin.rid)
            if len(served) == 1:
                drain.request("test")  # stop admissions...
                clk.advance(10.0)  # ...and blow the drain deadline
        j.close()
        states = {r.state for r in reqs}
        assert FINISHED in states  # whoever finished pre-drain
        assert PREEMPTED in states or QUEUED in states  # journal-and-exit
        assert drain.expired()

        # the drained residue resumes token-identically on a new engine
        sess = RolloutJournal.recover(jp)
        rest = [r for r in reqs if r.state in (QUEUED, PREEMPTED)]
        to_serve, _ = resume_requests(rest, sess)
        eng2 = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        _serve(eng2, to_serve)
        assert {r.rid: list(r.output) for r in reqs} == base

    def test_deadline_expiry_and_cancel_keep_partial_output(
        self, tiny_dense, served_baseline
    ):
        params, base = served_baseline
        clk = VirtualClock()
        eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        reqs = _mk_requests()
        reqs[1].deadline_s = 0.0  # already due on the VirtualClock
        reqs[4].cancel_requested = True
        out = _serve(eng, reqs, clock=clk)
        assert reqs[1].state == EXPIRED
        assert reqs[4].state == CANCELLED
        # unaffected requests still match the uninterrupted run
        for r in reqs:
            if r.state == FINISHED:
                assert out[r.rid] == base[r.rid]
        # partial output of a terminal non-FINISHED request is a prefix
        # of the uninterrupted output (T=0 determinism, just truncated)
        for r in (reqs[1], reqs[4]):
            assert r.output == base[r.rid][: len(r.output)]

    def test_subprocess_crash_recovers_token_identical(
        self, tiny_dense, served_baseline, tmp_path
    ):
        params, base = served_baseline
        jp = str(tmp_path / "child.wal")
        child = os.path.join(REPO_ROOT, "tests", "_journal_child.py")
        proc = subprocess.run(
            [sys.executable, child, jp, "3"],
            capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 9, proc.stderr  # died at commit 3
        assert os.path.getsize(jp) > 0
        sess = RolloutJournal.recover(jp)
        live = {k: s for k, s in sess.items() if s.resumable}
        assert live and any(s.tokens for s in live.values())
        # journaled prefixes are true prefixes of the reference outputs
        for k, s in sess.items():
            want = base[int(k)]
            if s.finished:
                assert s.tokens == want
            else:
                assert s.tokens == want[: len(s.tokens)]
        # resume in this process (identical params via the shared seed)
        reqs = _mk_requests()
        to_serve, _ = resume_requests(reqs, sess)
        j2 = RolloutJournal(jp)
        j2.adopt(sess)
        eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG))
        _serve(eng, to_serve, journal=j2)
        j2.close()
        assert {r.rid: list(r.output) for r in reqs} == base


# ---------------------------------------------------------------------------
# multi-worker: watchdog requeue resumes from the dead worker's journal
# ---------------------------------------------------------------------------
def test_watchdog_requeue_resumes_from_journal(tiny_dense, tmp_path):
    from repro import obs
    from repro.core.drafter import DrafterConfig, SuffixDrafter
    from repro.data.tasks import PatternTask
    from repro.rl.rollout import MultiWorkerRollout, RolloutWorker

    params = make_params(tiny_dense)
    task = PatternTask(n_problems=4, mean_len=6.0, max_len=10, seed=0)
    problems = task.problems()

    def mk_worker(journal=None, hook=None, tel=None):
        eng = SpecEngine(
            params, tiny_dense,
            EngineConfig(spec_enabled=True, max_new_tokens=10, eos_token=1,
                         use_budget_solver=False),
            drafter=SuffixDrafter(DrafterConfig(scope="problem",
                                                min_match=2)),
            telemetry=tel,
        )
        if journal is not None:
            journal = RolloutJournal(journal, fault_hook=hook)
        return RolloutWorker(eng, task, group_size=2, journal=journal)

    baseline = mk_worker().rollout(problems, key=jax.random.key(1))

    tel = obs.Telemetry()
    plan = FaultPlan(seed=0, telemetry=tel).crash_journal(at=2, mode="raise")
    dying = mk_worker(journal=str(tmp_path / "w0.wal"),
                      hook=plan.journal_hook(), tel=tel)
    survivor = mk_worker(journal=str(tmp_path / "w1.wal"), tel=tel)
    mw = MultiWorkerRollout([dying, survivor], fault_tolerant=True,
                            telemetry=tel)
    merged = mw.rollout(problems, key=jax.random.key(1))

    assert mw.stats["worker_failures"] == 1
    assert plan.fired and plan.fired[0]["kind"] == "journal"
    # the dead worker HAD journaled progress, and all of it was salvaged
    assert mw.stats["salvaged_tokens"] > 0
    committed = RolloutJournal.recover(str(tmp_path / "w0.wal"))
    n_committed = sum(
        len(s.tokens) for s in committed.values() if s.resumable
    )
    assert mw.stats["salvaged_tokens"] >= n_committed > 0
    # ...and the merged batch is token-identical to the no-fault run
    assert merged.responses == baseline.responses
    np.testing.assert_array_equal(merged.tokens, baseline.tokens)
    np.testing.assert_array_equal(merged.rewards, baseline.rewards)
    # the survivor's engine reported the resumed tokens
    assert tel.registry.value("das_resumed_tokens_total") > 0
