"""Flight-recorder suite: fleet-unique traces and their propagation.

The contract under test: one rollout is ONE trace fleet-wide. The trace
ID minted at admission survives every process boundary the repo has —
journal crash→recover→resume (same trace continues in a new process),
watchdog requeue (survivor adopts the dead worker's trace via exactly
one ``handoff`` event), and the history wire protocol (publish/sync
frames carry the trace as an optional, version-gated field that
old-schema peers simply never see). The Perfetto export turns the
merged recording into a trace-event document whose flow arrows cross
worker tracks exactly at those handoffs, and the attribution report
decomposes makespan into per-length-class components from the same
events.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from conftest import make_params
from repro import obs
from repro.core.scheduler import PREEMPTED, QUEUED, Request, SlotScheduler
from repro.core.spec_engine import EngineConfig, SpecEngine
from repro.fault import FaultPlan, RolloutJournal, VirtualClock, resume_requests
from repro.history.service import HistoryShard
from repro.obs.attrib import attribute, attribute_journals, render_report
from repro.obs.flight import (
    EVENT_KINDS,
    NULL_FLIGHT,
    FlightRecorder,
    NullFlightRecorder,
    merge_events,
    new_trace_id,
)
from repro.obs.perfetto import (
    export_trace,
    to_chrome_trace,
    validate_chrome_trace,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ECFG = dict(max_new_tokens=48, max_draft=8, eos_token=1)


def _mk_requests():
    # mirrors tests/_journal_child.py — the subprocess test resumes
    # the child's exact request set
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


# ---------------------------------------------------------------------------
# recorder mechanics (no engine)
# ---------------------------------------------------------------------------
class TestRecorder:
    def test_record_drain_and_query(self):
        fr = FlightRecorder(worker="wA")
        t1, t2 = fr.new_trace(), fr.new_trace()
        fr.record(t1, "queued", rid=0)
        fr.record(t1, "admit", dur=0.25, rid=0, slot=1)
        fr.record(t2, "queued", rid=1)
        fr.record(t1, "finish", rid=0, status="finished", emitted=7)
        evs = fr.events()
        assert [e["kind"] for e in evs] == ["queued", "admit", "queued",
                                           "finish"]
        # every event carries the owner track and a monotone seq
        assert all(e["worker"] == "wA" and e["shard"] is None for e in evs)
        assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
        admit = fr.events(trace=t1, kind="admit")[0]
        assert admit["dur"] == pytest.approx(0.25) and admit["slot"] == 1
        assert fr.events(trace=t2) == [evs[2]]
        assert fr.traces() == [t1, t2]
        assert all(e["kind"] in EVENT_KINDS for e in evs)

    def test_record_round_explodes_per_trace(self):
        fr = FlightRecorder(worker="wA")
        trs = [fr.new_trace() for _ in range(3)]
        fr.record_round(4, trs, accepted=[2, 0, 5], drafted=[6, 6, 8],
                        dur=0.01)
        evs = fr.events(kind="round")
        assert len(evs) == 3  # one raw append -> one event per resident
        assert [e["trace"] for e in evs] == trs
        assert [e["accepted"] for e in evs] == [2, 0, 5]
        assert [e["drafted"] for e in evs] == [6, 6, 8]
        assert all(e["round"] == 4 for e in evs)

    def test_drained_kinds_counted_in_registry(self):
        tel = obs.Telemetry()
        fr = tel.attach_flight(worker="wA")
        tr = fr.new_trace()
        fr.record(tr, "queued")
        fr.record(tr, "finish")
        fr.record_round(0, [tr], [1], [2])
        fr.drain()
        val = tel.registry.value
        assert val("das_flight_events_total", (("kind", "queued"),)) == 1
        assert val("das_flight_events_total", (("kind", "round"),)) == 1
        assert val("das_flight_events_total", (("kind", "finish"),)) == 1

    def test_cap_drops_oldest_and_counts(self):
        fr = FlightRecorder(worker="wA", cap=8)
        tr = fr.new_trace()
        for i in range(8):
            fr.record(tr, "round", round=i)
        fr.drain()
        for i in range(8, 20):
            fr.record(tr, "round", round=i)
        evs = fr.events()
        assert len(evs) == 8 and fr.dropped > 0
        # the newest events survive, the oldest dropped
        assert evs[-1]["round"] == 19

    def test_null_recorder_mints_real_traces_records_nothing(self):
        fr = NullFlightRecorder()
        assert not fr.enabled
        t1, t2 = fr.new_trace(), fr.new_trace()
        assert t1 != t2 and isinstance(t1, str) and t1
        fr.record(t1, "queued")
        fr.record_round(0, [t1], [1], [1])
        assert fr.events() == [] and fr.traces() == []
        assert NULL_FLIGHT.new_trace()  # module singleton mints too

    def test_trace_ids_fleet_unique_and_tagged(self):
        ids = {new_trace_id("w3") for _ in range(512)}
        assert len(ids) == 512
        assert all(i.startswith("w3-") for i in ids)
        # pid is embedded: a forked process cannot collide
        assert f"{os.getpid():x}" in next(iter(ids))

    def test_merge_events_orders_fleet_wide(self):
        a, b = FlightRecorder(worker="w0"), FlightRecorder(worker="w1")
        tr = a.new_trace()
        a.record(tr, "admit")
        b.record(tr, "resume")
        a.record(tr, "finish")
        evs = merge_events([a, b])
        assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
        assert {e["worker"] for e in evs} == {"w0", "w1"}


# ---------------------------------------------------------------------------
# trace minting + continuity (scheduler, journal — no engine)
# ---------------------------------------------------------------------------
class TestTraceContinuity:
    def test_scheduler_mints_once_resubmit_keeps(self):
        s = SlotScheduler(1, clock=VirtualClock())
        r = Request(rid=0, prompt=[1], max_new_tokens=8)
        assert r.trace is None
        s.submit(r)
        assert r.trace is not None
        minted = r.trace
        (r,) = s.next_admissions()
        s.preempt(r)
        assert r.state == PREEMPTED
        s.submit(r)  # re-entry keeps the trace: one rollout, one trace
        assert r.state == QUEUED and r.trace == minted

    def test_journal_roundtrips_trace(self, tmp_path):
        p = str(tmp_path / "j.wal")
        j = RolloutJournal(p)
        j.begin("a", [1, 2], max_new_tokens=8, trace="w0-abc-1")
        j.note("a", [5])
        j.commit()
        j.close()
        sess = RolloutJournal.recover(p)
        assert sess["a"].trace == "w0-abc-1"
        req = Request(rid=0, prompt=[1, 2], max_new_tokens=8)
        req.journal_key = "a"
        to_serve, _ = resume_requests([req], sess)
        assert to_serve and req.trace == "w0-abc-1"

    def test_old_schema_journal_without_trace_still_recovers(self, tmp_path):
        # pre-flight journals have no "tr" field on begin records —
        # recovery and resume must behave exactly as before
        p = str(tmp_path / "j.wal")
        j = RolloutJournal(p)
        j.begin("a", [1, 2], max_new_tokens=8)
        j.note("a", [5])
        j.commit()
        j.close()
        sess = RolloutJournal.recover(p)
        assert sess["a"].trace is None and sess["a"].tokens == [5]
        req = Request(rid=0, prompt=[1, 2], max_new_tokens=8)
        req.journal_key = "a"
        to_serve, _ = resume_requests([req], sess)
        assert to_serve and req.trace is None  # serve will mint fresh


# ---------------------------------------------------------------------------
# wire protocol: optional trace field, version-gated
# ---------------------------------------------------------------------------
class TestWireCompat:
    def _roll(self, key, trace=None):
        r = {"key": key, "tokens": [2, 3, 4, 1], "epoch": 0, "rlen": 3}
        if trace is not None:
            r["trace"] = trace
        return r

    def test_old_schema_frames_round_trip(self):
        sh = HistoryShard(window_size=4)
        out = sh.publish("s0", "w0", 1, rollouts=[self._roll("p0")],
                         drafts=[{"key": "p0", "drafted": 4, "accepted": 2}])
        assert out["ok"]
        assert sh.stats["traced_rollouts"] == 0
        resp = sh.sync("s1", "w1")
        assert resp["deltas"]  # the rollout replicated normally
        assert all("trace" not in t for t in resp["tel"])

    def test_traced_frames_carry_and_stamp(self):
        sh = HistoryShard(window_size=4)
        sh.flight = FlightRecorder(worker="hs0", shard="s0")
        sh.publish("s0", "w0", 1,
                   rollouts=[self._roll("p0", trace="w0-x-1"),
                             self._roll("p1")])
        assert sh.stats["traced_rollouts"] == 1
        # the shard stamped a publish event onto the rollout's trace
        (pub,) = sh.flight.events(kind="publish")
        assert pub["trace"] == "w0-x-1" and pub["shard"] == "s0"
        assert pub["origin"] == "w0" and pub["tokens"] == 4
        # sync frames carry the trace back only where it existed
        tel = sh.sync("s1", "w1")["tel"]
        by_key = {t["key"]: t for t in tel if "len" in t}
        assert by_key["p0"]["trace"] == "w0-x-1"
        assert "trace" not in by_key["p1"]

    def test_traced_publish_without_recorder_is_fine(self):
        sh = HistoryShard(window_size=4)  # flight stays None
        sh.publish("s0", "w0", 1, rollouts=[self._roll("p0", trace="t")])
        assert sh.stats["traced_rollouts"] == 1

    def test_client_applies_traced_sync_frames(self):
        # an old client never sets trace; a new client must tolerate
        # traced tel entries coming back from the shard
        from repro.history.client import HistoryClient
        from repro.history.service import HistoryService

        svc = HistoryService.spawn_in_process(n_shards=2, window_size=4)
        c0 = c1 = None
        try:
            c0 = HistoryClient(svc.addresses, worker_id="w0")
            c0.publish_rollout("p0", [2, 3, 4, 1], 0, response_len=3,
                               trace="w0-x-9")
            assert c0.flush()
            c1 = HistoryClient(svc.addresses, worker_id="w1")
            c1.sync()
            # traced frame parsed, length pooled into the peer
            assert c1.stats["tel_lengths"] >= 1
        finally:
            for c in (c0, c1):
                if c is not None:
                    c.close()
            svc.stop()


# ---------------------------------------------------------------------------
# serve lifecycle: queued -> admit -> rounds -> finish
# ---------------------------------------------------------------------------
def test_serve_records_full_lifecycle(tiny_dense):
    params = make_params(tiny_dense)
    tel = obs.Telemetry()
    tel.attach_flight(worker="w0")
    eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG),
                     telemetry=tel)
    reqs = _mk_requests()
    _serve(eng, reqs)
    fr = tel.flight
    for r in reqs:
        assert r.trace is not None
        evs = fr.events(trace=r.trace)
        kinds = [e["kind"] for e in evs]
        assert kinds.count("queued") == 1
        assert kinds.count("admit") >= 1
        assert kinds.count("finish") == 1, kinds
        rounds = [e for e in evs if e["kind"] == "round"]
        assert rounds and all(
            e["accepted"] >= 0 and e["drafted"] >= 0 for e in rounds
        )
        fin = evs[-1]
        assert fin["kind"] == "finish" and fin["emitted"] == len(r.output)
    # one trace per request, all distinct
    assert len({r.trace for r in reqs}) == len(reqs)
    # drained kinds surface as das_flight_events_total{kind}
    assert tel.registry.value(
        "das_flight_events_total", (("kind", "finish"),)
    ) == len(reqs)
    # flight events ride the snapshot export for offline attribution
    snap = tel.snapshot(spans=64, flight=1024)
    assert snap["flight"] and snap["flight_worker"] == "w0"
    report = attribute(snap["flight"], snap.get("spans", ()))
    assert report["n_rollouts"] == len(reqs)
    assert report["makespan_s"] > 0


# ---------------------------------------------------------------------------
# journal crash -> recover -> resume: the SAME trace continues
# ---------------------------------------------------------------------------
def test_subprocess_crash_resume_continues_trace(tiny_dense, tmp_path):
    params = make_params(tiny_dense)
    jp = str(tmp_path / "child.wal")
    child = os.path.join(REPO_ROOT, "tests", "_journal_child.py")
    proc = subprocess.run(
        [sys.executable, child, jp, "3"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 9, proc.stderr  # died at commit 3
    sess = RolloutJournal.recover(jp)
    # the child ran with NULL telemetry — minting is NOT gated on
    # recording, so every journaled session still carries a trace
    born = {k: s.trace for k, s in sess.items()}
    assert born and all(t is not None for t in born.values())
    assert len(set(born.values())) == len(born)

    reqs = _mk_requests()
    to_serve, _ = resume_requests(reqs, sess)
    for r in to_serve:
        k = str(r.rid)
        if k in sess and sess[k].resumable:
            assert r.trace == born[k]  # continuation adopts, not mints

    tel = obs.Telemetry()
    tel.attach_flight(worker="w1")
    j2 = RolloutJournal(jp)
    j2.adopt(sess)
    eng = SpecEngine(params, tiny_dense, EngineConfig(**ECFG),
                     telemetry=tel)
    _serve(eng, to_serve, journal=j2)
    j2.close()

    # the resumed process recorded resume/finish ON the child's traces
    fr = tel.flight
    resumed = [k for k, s in sess.items() if s.resumable and s.tokens]
    for k in resumed:
        evs = fr.events(trace=born[k])
        kinds = [e["kind"] for e in evs]
        assert "resume" in kinds, (k, kinds)
        assert kinds.count("finish") == 1
    # and the re-written journal still carries the ORIGINAL trace IDs
    final = RolloutJournal.recover(jp)
    for k in resumed:
        assert final[k].trace == born[k]


# ---------------------------------------------------------------------------
# watchdog requeue: survivor adopts the dead worker's traces
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def chaos_run(tiny_dense, tmp_path_factory):
    """One dying/survivor rollout with per-worker flight recorders.

    Worker 0's journal hook raises mid-slice; the supervisor salvages
    its journaled progress and requeues onto worker 1. Shared by the
    handoff-semantics test and the Perfetto-export test (the scenario
    is expensive: three engine builds)."""
    from repro.core.drafter import DrafterConfig, SuffixDrafter
    from repro.data.tasks import PatternTask
    from repro.rl.rollout import MultiWorkerRollout, RolloutWorker

    tmp = tmp_path_factory.mktemp("chaos")
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

    tels = [obs.Telemetry(), obs.Telemetry()]
    tels[0].attach_flight(worker="w0")
    tels[1].attach_flight(worker="w1")
    plan = FaultPlan(seed=0, telemetry=tels[0]).crash_journal(
        at=2, mode="raise"
    )
    dying = mk_worker(journal=str(tmp / "w0.wal"),
                      hook=plan.journal_hook(), tel=tels[0])
    survivor = mk_worker(journal=str(tmp / "w1.wal"), tel=tels[1])
    # the supervisor records handoffs on the DEAD worker's telemetry:
    # the flow arrow then leaves w0's track exactly where w0 died
    mw = MultiWorkerRollout([dying, survivor], fault_tolerant=True,
                            telemetry=tels[0])
    merged = mw.rollout(problems, key=jax.random.key(1))
    return {"tels": tels, "mw": mw, "merged": merged,
            "baseline": baseline}


def test_requeue_emits_exactly_one_handoff_per_trace(chaos_run):
    tels = chaos_run["tels"]
    mw = chaos_run["mw"]
    assert mw.stats["worker_failures"] == 1
    evs = merge_events([t.flight for t in tels])
    handoffs = [e for e in evs if e["kind"] == "handoff"]
    assert handoffs, "requeue must never be silent in the recording"
    traced = [e for e in handoffs if e["trace"] is not None]
    assert traced, "salvaged in-flight sessions carry traces"
    # EXACTLY one handoff per salvaged trace
    per_trace = {}
    for e in traced:
        per_trace[e["trace"]] = per_trace.get(e["trace"], 0) + 1
    assert all(n == 1 for n in per_trace.values()), per_trace
    for e in traced:
        assert e["from_worker"] == 0 and e["to_worker"] == 1
        assert e["error"]
    # the survivor CONTINUED each handed-off trace (resume for journaled
    # progress, admit when the prefix was empty) — on ITS recorder
    w1 = tels[1].flight
    for tr in per_trace:
        kinds = {e["kind"] for e in w1.events(trace=tr)}
        assert kinds & {"resume", "admit"}, (tr, kinds)
        assert "finish" in kinds
    # fault tolerance did not cost token identity
    assert chaos_run["merged"].responses == chaos_run["baseline"].responses


def test_perfetto_export_crosses_worker_tracks(chaos_run, tmp_path):
    tels = chaos_run["tels"]
    # 2 shard-side recorders: publish instants land on shard tracks
    shards = []
    all_traces = sorted(
        set(tels[0].flight.traces()) | set(tels[1].flight.traces())
    )
    for i in range(2):
        sh = HistoryShard(shard_id=i, n_shards=2, window_size=4)
        sh.flight = FlightRecorder(worker=f"hs{i}", shard=f"s{i}")
        for j, tr in enumerate(all_traces[i::2]):
            sh.publish("s", "w0", j + 1, rollouts=[{
                "key": f"p{i}-{j}", "tokens": [2, 3, 1], "epoch": 0,
                "rlen": 2, "trace": tr,
            }])
        shards.append(sh)

    out = str(tmp_path / "trace.json")
    doc = export_trace(out, tels, names=["w0", "w1"],
                       shards=[sh.flight for sh in shards])
    with open(out) as f:
        loaded = json.load(f)
    assert loaded == doc
    assert validate_chrome_trace(doc) == []

    evs = doc["traceEvents"]
    # one process track per worker and per shard
    names = {
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert {"worker w0", "worker w1", "shard s0", "shard s1"} <= names
    # spans made it over (round spans on the rounds thread)
    assert any(e["ph"] == "X" and e.get("cat") == "span" for e in evs)
    # publish instants landed on shard tracks
    pid_of = {}
    for e in evs:
        if e["ph"] == "M" and e["name"] == "process_name":
            pid_of[e["args"]["name"]] = e["pid"]
    shard_pids = {pid_of["shard s0"], pid_of["shard s1"]}
    assert any(
        e["ph"] == "i" and e["name"] == "publish" and e["pid"] in shard_pids
        for e in evs
    )
    # flow arrows exist, and at least one handoff arrow CROSSES pids
    starts = {e["id"]: e for e in evs if e["ph"] == "s"}
    finishes = [e for e in evs if e["ph"] == "f"]
    assert finishes and starts
    assert any(
        e["id"] in starts and starts[e["id"]]["pid"] != e["pid"]
        for e in finishes
    ), "a requeued rollout's flow arrow must cross worker tracks"


# ---------------------------------------------------------------------------
# perfetto: synthetic-document validation
# ---------------------------------------------------------------------------
class TestPerfettoUnit:
    def test_synthetic_round_trip(self):
        t0 = 1000.0
        w0 = [
            {"worker": "w0", "shard": None, "seq": 0, "trace": "t-1",
             "kind": "queued", "ts": t0, "dur": 0.0},
            {"worker": "w0", "shard": None, "seq": 1, "trace": "t-1",
             "kind": "admit", "ts": t0 + 0.1, "dur": 0.05, "slot": 0},
            {"worker": "w0", "shard": None, "seq": 2, "trace": "t-1",
             "kind": "handoff", "ts": t0 + 0.5, "dur": 0.0,
             "from_worker": 0, "to_worker": 1},
        ]
        w1 = [
            {"worker": "w1", "shard": None, "seq": 0, "trace": "t-1",
             "kind": "resume", "ts": t0 + 0.6, "dur": 0.02, "slot": 2},
            {"worker": "w1", "shard": None, "seq": 1, "trace": "t-1",
             "kind": "finish", "ts": t0 + 0.9, "dur": 0.0, "emitted": 9},
        ]
        spans = [{"name": "round", "parent": None, "depth": 0,
                  "t0": 10.0, "dur_s": 0.2, "attrs": {"n": 3}}]
        doc = to_chrome_trace([
            {"name": "w0", "spans": spans, "flight": w0,
             "perf_offset": t0 - 10.0},
            {"name": "w1", "spans": [], "flight": w1, "perf_offset": 0.0},
        ])
        assert validate_chrome_trace(doc) == []
        evs = doc["traceEvents"]
        # the span was shifted onto the wall axis by perf_offset
        span = next(e for e in evs if e.get("cat") == "span")
        assert span["ts"] == pytest.approx(t0 * 1e6, abs=1.0)
        # handoff -> resume flow crosses from w0's pid to w1's pid
        s = next(e for e in evs if e["ph"] == "s")
        f = next(e for e in evs if e["ph"] == "f")
        assert s["id"] == f["id"] and s["pid"] != f["pid"]
        assert f["bp"] == "e"

    def test_validator_catches_malformed(self):
        bad = {"traceEvents": [
            {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0},
            {"ph": "i", "name": "b", "pid": 1},               # missing tid
            {"ph": "s", "name": "c", "pid": 1, "tid": 1,
             "ts": 0.0, "id": 7},                             # unmatched
        ]}
        problems = validate_chrome_trace(bad)
        assert any("without numeric dur" in p for p in problems)
        assert any("missing" in p for p in problems)
        assert any("unmatched" in p for p in problems)
        assert validate_chrome_trace({}) == [
            "traceEvents missing or not a list"
        ]


# ---------------------------------------------------------------------------
# makespan attribution
# ---------------------------------------------------------------------------
def _synthetic_fleet(t0=1000.0):
    """Two workers, four rollouts; r3 is the long tail and migrates."""
    evs = []
    seq = iter(range(1000))

    def ev(worker, trace, kind, ts, dur=0.0, **f):
        e = {"worker": worker, "shard": None, "seq": next(seq),
             "trace": trace, "kind": kind, "ts": ts, "dur": dur}
        e.update(f)
        return e

    for i, (w, length, n_rounds) in enumerate(
        [("w0", 4, 2), ("w0", 6, 3), ("w1", 8, 4), ("w1", 40, 12)]
    ):
        tr = f"t-{i}"
        evs.append(ev(w, tr, "queued", t0))
        evs.append(ev(w, tr, "admit", t0 + 0.05, dur=0.02, slot=i))
        for r in range(n_rounds):
            evs.append(ev(w, tr, "round", t0 + 0.1 + 0.1 * r, dur=0.08,
                          round=r, accepted=length // n_rounds,
                          drafted=4 + (2 if length > 10 else 0)))
        if i == 3:  # the tail migrates: handoff then resume on w0
            evs.append(ev("w1", tr, "handoff", t0 + 1.35,
                          from_worker=1, to_worker=0))
            evs.append(ev("w0", tr, "resume", t0 + 1.5, dur=0.03, slot=0))
            for r in range(n_rounds, n_rounds + 4):
                evs.append(ev("w0", tr, "round", t0 + 1.6 + 0.1 * r,
                              dur=0.08, round=r, accepted=3, drafted=6))
        end = t0 + 0.1 + 0.1 * n_rounds + (2.2 if i == 3 else 0.0)
        evs.append(ev(w if i != 3 else "w0", tr, "finish", end,
                      status="finished", emitted=length))
    spans = [
        {"name": "verify_dispatch", "parent": "serve_round", "depth": 1,
         "t0": 1.0, "dur_s": 0.6},
        {"name": "budget_solve", "parent": "serve_round", "depth": 1,
         "t0": 2.0, "dur_s": 0.2},
        {"name": "consume", "parent": "serve_round", "depth": 1,
         "t0": 3.0, "dur_s": 0.2},
        {"name": "prefill", "parent": None, "depth": 0,
         "t0": 0.0, "dur_s": 0.3},
        # nested same-phase child must NOT double-bill
        {"name": "cache_commit", "parent": "prefill", "depth": 1,
         "t0": 0.1, "dur_s": 0.2},
    ]
    return evs, spans


class TestAttribution:
    def test_synthetic_report_decomposes_the_tail(self):
        evs, spans = _synthetic_fleet()
        rep = attribute(evs, spans)
        assert rep["n_rollouts"] == 4 and rep["n_workers"] == 2
        assert rep["makespan_s"] > 0 and rep["migrated"] == 1
        assert set(rep["classes"]) <= set(("short", "medium", "long"))
        total_n = sum(c["n"] for c in rep["classes"].values())
        assert total_n == 4
        # components are exactly the documented taxonomy
        for c in rep["classes"].values():
            assert set(c["components_s"]) == set(
                ("queue_wait", "prefill", "verify", "draft_host",
                 "accept_consume", "stall_recovery")
            )
        # the tail (length 40) dominates: top decile owns most wall time
        td = rep["top_decile"]
        assert td["n"] == 1 and td["min_length"] == 40
        assert td["wall_share"] > 0.5
        assert 0 < td["makespan_share"] <= 1.0
        # the migrated rollout billed its handoff->resume gap as stall
        tail = next(r for r in rep["rollouts"] if r["length"] == 40)
        assert tail["migrated"] and len(tail["workers"]) == 2
        assert tail["components"]["stall_recovery"] > 0
        # span fractions routed round wall into all three loop phases
        assert tail["components"]["verify"] > tail["components"]["draft_host"]
        assert tail["components"]["draft_host"] > 0
        # budget curve reflects deeper budgets for longer rollouts
        bud = rep["curves"]["budget"]
        assert bud[-1]["mean_budget"] >= bud[0]["mean_budget"]

    def test_render_report_human_readable(self):
        evs, spans = _synthetic_fleet()
        text = render_report(attribute(evs, spans))
        assert "makespan attribution" in text
        assert "top decile" in text and "migrated" in text
        assert render_report({"n_rollouts": 0}) == "no rollouts in recording\n"

    def test_attribute_journals_round_and_token_share(self, tmp_path):
        for w, lens in enumerate([(3, 4), (2, 30)]):
            j = RolloutJournal(str(tmp_path / f"w{w}.wal"))
            for i, n in enumerate(lens):
                key = f"r{w}-{i}"
                j.begin(key, [1, 2], max_new_tokens=64,
                        trace=f"w{w}-x-{i}")
                for r in range(n):
                    j.note(key, [10 + r])
                    j.commit()
                if i == 0:
                    j.finish(key, n_emitted=n)
                    j.commit()
            j.close()
        rep = attribute_journals(str(tmp_path))
        assert rep["n_rollouts"] == 4 and rep["n_finished"] == 2
        assert all(s["trace"] for s in rep["sessions"])
        td = rep["top_decile"]
        assert td["min_length"] == 30
        assert td["token_share"] > 0.5  # the tail owns the tokens
        assert 0 < td["round_share"] <= 1.0

    def test_cli_snapshot_json(self, tmp_path, capsys):
        from repro.obs.attrib import main

        evs, spans = _synthetic_fleet()
        snap = str(tmp_path / "run.json")
        with open(snap, "w") as f:
            json.dump({"flight": evs, "spans": spans}, f)
        assert main(["--snapshot", snap, "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["n_rollouts"] == 4
        assert "rollouts" not in out  # --json emits the slim report
