"""Fault-tolerant fleet: shard supervision, degraded drafting, rollout
watchdog, and the deterministic fault-injection harness.

The load-bearing properties:

* every failure mode is **deterministic in tests** — seeded
  ``FaultPlan`` counters and ``VirtualClock`` time, no wall-clock
  sleeps orchestrating anything;
* failures degrade acceptance, never correctness: drafting falls back
  (stale replica or local fallback trees), rollouts re-queue to
  survivors, and the merged batch stays **token-identical** to the
  no-failure run at T=0;
* publish stays at-least-once on the wire and exactly-once in the
  shard (per-session seq dedup survives crash + warm restart);
* corrupt persisted history quarantines (``*.corrupt``) and
  cold-starts instead of raising.
"""

import json
import logging
import os
import socket

import numpy as np
import pytest

from repro.core.drafter import DrafterConfig, SuffixDrafter
from repro.fault import (
    DOWN,
    HEALTHY,
    RESYNCING,
    SUSPECT,
    AddressBook,
    BackoffPolicy,
    FaultPlan,
    FlakyWorker,
    RolloutWatchdog,
    ShardBackoffError,
    ShardHealth,
    ShardSupervisor,
    SilentServer,
    StallError,
    SystemClock,
    VirtualClock,
    garble_json_file,
    truncate_json_file,
)
from repro.history import persist
from repro.history.client import HistoryClient
from repro.history.service import HistoryService, HistoryShard, ShardServer

TINY_BACKOFF = BackoffPolicy(base_s=0.01, max_s=0.05, jitter=0.0)
# zero-delay: DOWN shards probe on every attempt (tests that drive the
# recovery themselves and must not race a wall-clock backoff window)
ZERO_BACKOFF = BackoffPolicy(base_s=0.0, max_s=0.0, factor=1.0, jitter=0.0)


def _docs(rng, n, length=14, vocab=8):
    return [[int(t) for t in rng.integers(0, vocab, size=length)]
            for _ in range(n)]


def _packs_equal(a, b):
    if (a is None) != (b is None):
        return False
    if a is None:
        return True
    return a.n_nodes == b.n_nodes and \
        np.array_equal(a.corpus, b.corpus) and \
        np.array_equal(a.first_child, b.first_child)


# ---------------------------------------------------------------------------
# virtual clock
# ---------------------------------------------------------------------------
def test_virtual_clock_never_blocks():
    clk = VirtualClock()
    t0 = clk.now()
    clk.sleep(1000.0)  # returns immediately, advances virtual time
    assert clk.now() == pytest.approx(t0 + 1000.0)
    clk.advance(0.5)
    assert clk.now() == pytest.approx(t0 + 1000.5)


# ---------------------------------------------------------------------------
# backoff policy + health machine
# ---------------------------------------------------------------------------
def test_backoff_policy_caps_and_is_deterministic():
    import random

    pol = BackoffPolicy(base_s=0.1, max_s=1.0, factor=2.0, jitter=0.25)
    a = [pol.delay(n, random.Random(7)) for n in range(1, 10)]
    b = [pol.delay(n, random.Random(7)) for n in range(1, 10)]
    assert a == b, "seeded jitter must replay identically"
    assert all(d <= 1.0 * 1.25 + 1e-9 for d in a), "cap + jitter bound"
    nojit = BackoffPolicy(base_s=0.1, max_s=1.0, factor=2.0, jitter=0.0)
    assert nojit.delay(1, random.Random(0)) == pytest.approx(0.1)
    assert nojit.delay(4, random.Random(0)) == pytest.approx(0.8)
    assert nojit.delay(50, random.Random(0)) == pytest.approx(1.0)


def test_health_machine_full_cycle_on_virtual_clock():
    clk = VirtualClock()
    h = ShardHealth(0, clock=clk, policy=TINY_BACKOFF, suspect_after=2)
    assert h.state == HEALTHY and h.should_attempt()
    assert h.record_failure() == SUSPECT
    assert h.should_attempt(), "SUSPECT still probes on every RPC"
    assert h.record_failure() == DOWN
    assert not h.should_attempt(), "DOWN gates inside the backoff window"
    assert h.retry_in() > 0
    clk.advance(h.retry_in() + 1e-6)
    assert h.should_attempt(), "past the deadline: one probe allowed"
    # failed probe: still DOWN, deadline pushed out again
    assert h.record_failure() == DOWN
    assert not h.should_attempt()
    clk.advance(1.0)
    assert h.record_success() is True, "success after DOWN is a recovery"
    assert h.state == RESYNCING
    h.resynced()
    assert h.state == HEALTHY
    snap = h.snapshot()
    assert snap["down_transitions"] == 1 and snap["recoveries"] == 1
    assert snap["total_failures"] == 3


def test_resync_that_fails_falls_back_to_suspect():
    clk = VirtualClock()
    h = ShardHealth(0, clock=clk, policy=TINY_BACKOFF, suspect_after=2)
    h.record_failure(), h.record_failure()
    clk.advance(1.0)
    assert h.record_success() is True
    assert h.state == RESYNCING
    assert h.record_failure() == SUSPECT, "recovery did not stick"


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------
def test_watchdog_trips_only_without_progress():
    clk = VirtualClock()
    wd = RolloutWatchdog(deadline_s=1.0, clock=clk)
    wd.arm()
    for _ in range(5):
        clk.advance(0.9)
        wd.check("round")     # under deadline every time
        wd.progress()
    clk.advance(1.5)
    with pytest.raises(StallError, match="no progress"):
        wd.check("verify round")
    assert wd.stalls == 1 and wd.checks == 6


def test_fault_plan_stalls_watchdog_at_exact_check():
    clk = VirtualClock()
    plan = FaultPlan(seed=0)
    wd = plan.stall_watchdog(
        RolloutWatchdog(deadline_s=5.0, clock=clk), at_check=3
    )
    wd.arm()
    wd.check(), wd.check()
    with pytest.raises(StallError):
        wd.check()
    assert [f["kind"] for f in plan.fired] == ["watchdog"]


# ---------------------------------------------------------------------------
# client: backoff gating, reconnect accounting, rpc timeouts
# ---------------------------------------------------------------------------
def test_down_shard_fails_fast_and_probes_after_backoff():
    clk = VirtualClock()
    c = HistoryClient([("127.0.0.1", 1)], worker_id="w0",
                      start_sender=False, rpc_timeout=0.2,
                      backoff=TINY_BACKOFF, suspect_after=2, clock=clk)
    assert c.sync() == 0          # connect refused -> SUSPECT
    assert c.shard_state(0) == SUSPECT
    assert c.sync() == 0          # second failure -> DOWN
    assert c.shard_state(0) == DOWN
    attempts = c.stats["rpc_attempts"]
    assert c.sync() == 0          # gated: no socket work at all
    assert c.stats["sync_skips"] == 1
    assert c.stats["rpc_attempts"] == attempts
    with pytest.raises(ShardBackoffError):
        c._rpc(0, {"op": "sync"})
    assert c.stats["backoff_skips"] == 1
    clk.advance(1.0)              # past the deadline: probe again
    assert c.sync() == 0
    assert c.stats["rpc_attempts"] > attempts
    # reconnect attempts are visible in the stats snapshot
    snap = c.stats_snapshot()
    assert snap["shards"][0]["state"] == DOWN
    assert snap["shards"][0]["total_failures"] >= 3


def test_silent_server_times_out_suspect_drafting_unaffected():
    srv = SilentServer()
    try:
        c = HistoryClient([srv.address], worker_id="w0",
                          start_sender=False, rpc_timeout=0.15,
                          backoff=TINY_BACKOFF, suspect_after=2)
        drafter = SuffixDrafter(
            DrafterConfig(scope="problem", min_match=1), remote=c
        )
        assert c.sync() == 0      # accepted, never replied
        assert c.stats["rpc_timeouts"] == 1
        assert c.shard_state(0) == SUSPECT
        # drafting keeps working: rollouts observed, sessions propose
        # (empty replica -> no proposals, but no raise, no stall)
        drafter.observe_rollout("p", [1, 2, 3, 1, 2], 0, response_len=5)
        bds = drafter.batched_sessions(1)
        bds.open(0, "p")
        bds.feed(0, [1, 2])
        bds.propose_batch(np.array([4]))
        assert c.sync() == 0
        assert c.shard_state(0) == DOWN
        c.close(flush_timeout=0.1)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# exactly-once publish under reply loss (kill-on-publish + warm restart)
# ---------------------------------------------------------------------------
def test_publish_reply_lost_resend_is_exactly_once():
    plan = FaultPlan(seed=1).kill_shard(0, op="publish", at=1)
    svc = HistoryService.spawn_in_process(
        1, window_size=8, fault_hooks=[plan.server_hook(0)]
    )
    sup = ShardSupervisor(svc, seed=0, policy=TINY_BACKOFF)
    try:
        c = HistoryClient(svc.book, worker_id="w0", rpc_timeout=1.0,
                          backoff=TINY_BACKOFF, suspect_after=2)
        c.publish_rollout("p", [1, 2, 3, 4], 0, response_len=4)
        # the shard APPLIES the batch, then crashes before replying:
        # the client must resend, the (warm-restarted) shard must dedup
        deadline_polls = 0
        while not c.flush(timeout=0.2):
            restarted = sup.poll(force=True)
            deadline_polls += 1
            assert deadline_polls < 100, "flush never drained"
            if restarted:
                assert restarted == [0]
        assert plan.pending() == 0 and plan.fired[0]["action"] == "kill"
        assert sup.stats["restarts"] == 1
        # warm restart carried the dedup cursor: exactly one rollout
        assert svc.servers[0].shard.store.n_rollouts == 1
        assert c.stats["publish_failures"] >= 1
        # the resend dialed a fresh connection after the crash
        assert c.stats["connects"] + c.stats["reconnects"] >= 2
        c.close()
    finally:
        sup.stop()
        svc.stop()


# ---------------------------------------------------------------------------
# supervisor: restart + address republish through the AddressBook
# ---------------------------------------------------------------------------
def test_supervisor_restart_republishes_address_and_client_resyncs():
    rng = np.random.default_rng(5)
    svc = HistoryService.spawn_in_process(2, window_size=8)
    sup = ShardSupervisor(svc, seed=0, policy=TINY_BACKOFF)
    try:
        c = HistoryClient(svc.book, worker_id="w0", start_sender=False,
                          rpc_timeout=1.0, backoff=TINY_BACKOFF)
        doc = _docs(rng, 1)[0]
        key = "p0"
        i = c.shard_of(key)
        c.publish_rollout(key, doc, 0, response_len=len(doc))
        with c._cv:
            c._seal_pending_locked()
        # drain synchronously (no sender thread): direct rpc publish
        batch = c._outbox[i].popleft()
        c._rpc(i, {"op": "publish", "session": c.session,
                   "origin": c.worker_id, "seq": batch["seq"],
                   "epoch": batch["epoch"], "rollouts": batch["rollouts"],
                   "drafts": batch["drafts"]})
        c.sync()
        before = c.pack_for(key)
        assert before is not None

        v0 = svc.book.version
        svc.servers[i].stop()
        svc.servers[i].stopped.wait(timeout=5.0)
        assert not svc.shard_alive(i)
        assert sup.poll(force=True) == [i]
        assert svc.shard_alive(i)
        assert svc.book.version > v0, "restart must republish the address"

        # client's next sync dials the NEW address from the shared book,
        # sees a fresh generation and full-resyncs the restored pack
        applied = c.sync()
        assert c.stats["shard_restarts"] == 1
        assert applied >= 1
        assert _packs_equal(c.pack_for(key), before)
        c.close()
    finally:
        sup.stop()
        svc.stop()


def test_supervisor_backoff_and_give_up_on_virtual_clock():
    class BrokenService:
        n_shards = 1
        closed = False

        def shard_alive(self, i):
            return False

        def respawn_shard(self, i, state=None):
            raise RuntimeError("no port available")

    clk = VirtualClock()
    sup = ShardSupervisor(
        BrokenService(), clock=clk, seed=0, max_restarts=2,
        policy=BackoffPolicy(base_s=1.0, max_s=8.0, jitter=0.0),
    )
    assert sup.poll() == []
    assert sup.stats["restart_failures"] == 1
    assert sup.poll() == [] and sup.stats["restart_failures"] == 1, \
        "inside the backoff window: no second attempt"
    clk.advance(1.5)
    sup.poll()
    assert sup.stats["restart_failures"] == 2
    clk.advance(10.0)
    sup.poll()
    assert sup.stats["gave_up"] == 1, "max_restarts exhausted"


# ---------------------------------------------------------------------------
# degraded drafting: local fallback trees while the owner is DOWN
# ---------------------------------------------------------------------------
def test_degraded_drafting_falls_back_then_recovers():
    rng = np.random.default_rng(9)
    svc = HistoryService.spawn_in_process(1, window_size=8)
    try:
        c = HistoryClient(svc.book, worker_id="w0", rpc_timeout=0.5,
                          backoff=ZERO_BACKOFF, suspect_after=2)
        cfg = DrafterConfig(scope="problem", window_size=8, min_match=1,
                            epoch_decay=0.9)
        drafter = SuffixDrafter(cfg, remote=c)
        warm = _docs(rng, 1, length=18)[0]
        drafter.observe_rollout("p", warm, 0, response_len=len(warm))
        assert c.flush()
        c.sync()
        frozen = c.pack_for("p")
        assert frozen is not None

        # kill the only shard; drive health to DOWN via failed syncs
        svc.servers[0].stop()
        svc.servers[0].stopped.wait(timeout=5.0)
        c.sync(), c.sync()
        assert c.shard_state(0) == DOWN
        assert c.degraded_for("p")

        # new rollouts now ALSO feed a local fallback tree, and
        # pack_for prefers it over the frozen replica
        fresh = _docs(rng, 2, length=18)
        for e, doc in enumerate(fresh, start=1):
            drafter.observe_rollout("p", doc, e, response_len=len(doc))
        assert drafter.stats["degraded_rollouts"] == 2
        fb = drafter.pack_for("p")
        assert fb is not None and drafter.stats["degraded_packs"] >= 1
        assert not _packs_equal(fb, frozen), \
            "fallback tree must reflect the outage-time rollouts"

        # recovery: restart the shard, next sync flips health back and
        # pack_for returns to the replicated (authoritative) pack
        svc.respawn_shard(0)
        c.sync()
        assert c.shard_state(0) in (HEALTHY, RESYNCING)
        assert not c.degraded_for("p")
        assert c.stats["shard_recoveries"] == 1
        assert c.stats["hedged_resyncs"] == 1
        back = drafter.pack_for("p")
        assert _packs_equal(back, c.pack_for("p")), \
            "after recovery the fallback tree must stand down"
        c.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# outbox overflow: episode logging + drops reported to the shard
# ---------------------------------------------------------------------------
def test_overflow_episode_logs_once_and_reports_drops(caplog):
    import threading

    svc = HistoryService.spawn_in_process(1, window_size=8)
    try:
        c = HistoryClient(svc.book, worker_id="w0", outbox_cap=2,
                          start_sender=False, rpc_timeout=1.0)
        for i in range(5):
            c.publish_rollout("p", [i, i + 1], 0, response_len=2)
            with c._cv:
                c._seal_pending_locked()
        assert c.stats["dropped_batches"] == 3
        assert c.stats["dropped_batches_s0"] == 3
        # now start the sender: the surviving batches drain, the first
        # ack piggybacks the drop count into shard telemetry, and the
        # episode closes with exactly ONE warning
        with caplog.at_level(logging.WARNING, logger="repro.history.client"):
            c._sender = threading.Thread(
                target=c._sender_loop, daemon=True
            )
            c._sender.start()
            assert c.flush(timeout=5.0)
        overflow_logs = [r for r in caplog.records
                        if "overflowed" in r.getMessage()]
        assert len(overflow_logs) == 1
        assert "dropped 3" in overflow_logs[0].getMessage()
        assert c.stats["overflow_episodes"] == 1
        assert c._drops_unreported[0] == 0
        assert svc.servers[0].shard.stats["client_dropped_batches"] == 3
        c.close()
    finally:
        svc.stop()


def test_close_warns_and_returns_unflushed_batches(caplog):
    c = HistoryClient([("127.0.0.1", 1)], worker_id="w0",
                      start_sender=False, rpc_timeout=0.1,
                      backoff=TINY_BACKOFF)
    for i in range(2):
        c.publish_rollout("p", [i], 0, response_len=1)
        with c._cv:
            c._seal_pending_locked()
    with caplog.at_level(logging.WARNING, logger="repro.history.client"):
        n = c.close(flush_timeout=0.05)
    assert n == 2
    assert c.stats["unflushed_batches"] == 2
    assert any("unflushed" in r.getMessage() for r in caplog.records)


# ---------------------------------------------------------------------------
# quarantine: corrupt persisted history cold-starts instead of raising
# ---------------------------------------------------------------------------
def _save_sharded(tmp_path, n=3):
    shards = []
    for i in range(n):
        sh = HistoryShard(shard_id=i, n_shards=n, window_size=4)
        sh.publish(session=f"s{i}", origin=f"w{i}", seq=0,
                   rollouts=[{"key": i, "tokens": [1, 2, i], "epoch": 0,
                              "rlen": 3}])
        shards.append(sh)
    persist.save_service_history(
        str(tmp_path), [s.state_dict() for s in shards]
    )
    return shards


def test_truncated_shard_file_quarantined_others_survive(tmp_path):
    _save_sharded(tmp_path, n=3)
    victim = os.path.join(str(tmp_path), persist.shard_filename(1))
    truncate_json_file(victim, keep_fraction=0.5)
    loaded = persist.load_service_history(str(tmp_path))
    assert loaded["n_shards"] == 3
    assert loaded["shards"][1] is None, "corrupt shard cold-starts"
    assert loaded["shards"][0] is not None
    assert loaded["shards"][2] is not None
    assert loaded["quarantined"] == [victim + persist.QUARANTINE_SUFFIX]
    assert os.path.exists(victim + persist.QUARANTINE_SUFFIX)
    assert not os.path.exists(victim), "original must be renamed away"
    # the service spawns over the partial restore: shard 1 is cold
    svc = HistoryService.spawn_in_process(
        3, window_size=4, states=loaded["shards"]
    )
    try:
        assert svc.servers[0].shard.store.n_rollouts == 1
        assert svc.servers[1].shard.store.n_rollouts == 0
        assert svc.servers[2].shard.store.n_rollouts == 1
    finally:
        svc.stop()


def test_garbled_manifest_cold_starts_whole_save(tmp_path):
    _save_sharded(tmp_path, n=2)
    manifest = os.path.join(str(tmp_path), persist.MANIFEST_FILENAME)
    garble_json_file(manifest, seed=3)
    loaded = persist.load_service_history(str(tmp_path))
    assert loaded["n_shards"] == 0 and loaded["shards"] == []
    assert loaded["quarantined"] == [manifest + persist.QUARANTINE_SUFFIX]
    assert os.path.exists(manifest + persist.QUARANTINE_SUFFIX)


def test_missing_schema_version_quarantined(tmp_path):
    path = str(tmp_path / persist.HISTORY_FILENAME)
    persist._atomic_write_json(path, {"store": {}})
    with pytest.raises(persist.HistoryCorruptError, match="schema"):
        persist.load_history(str(tmp_path))
    assert os.path.exists(path + persist.QUARANTINE_SUFFIX)


def test_partial_manifest_missing_shard_file(tmp_path, caplog):
    _save_sharded(tmp_path, n=3)
    os.remove(os.path.join(str(tmp_path), persist.shard_filename(2)))
    with caplog.at_level(logging.WARNING, logger="repro.history.persist"):
        loaded = persist.load_service_history(str(tmp_path))
    assert loaded["n_shards"] == 3
    assert loaded["shards"][2] is None
    assert loaded["shards"][0] is not None
    assert any("missing" in r.getMessage().lower() for r in caplog.records)


def test_future_schema_still_raises_without_quarantine(tmp_path):
    # valid JSON from a NEWER version is not corruption: refuse loudly,
    # leave the file alone (the user may downgrade back)
    path = str(tmp_path / persist.HISTORY_FILENAME)
    persist._atomic_write_json(path, {"schema_version": 99, "store": {}})
    with pytest.raises(persist.HistorySchemaError, match="schema_version"):
        persist.load_history(str(tmp_path))
    assert os.path.exists(path)
    assert not os.path.exists(path + persist.QUARANTINE_SUFFIX)


# ---------------------------------------------------------------------------
# fault-tolerant multi-worker rollout
# ---------------------------------------------------------------------------
def _mk_worker(params, cfg, task, remote=None, watchdog=None):
    from repro.core.spec_engine import EngineConfig, SpecEngine
    from repro.rl.rollout import RolloutWorker

    eng = SpecEngine(
        params, cfg,
        EngineConfig(spec_enabled=True, max_new_tokens=10, eos_token=1,
                     use_budget_solver=False),
        drafter=SuffixDrafter(
            DrafterConfig(scope="problem", min_match=2), remote=remote
        ),
    )
    return RolloutWorker(eng, task, group_size=2, watchdog=watchdog)


def test_flaky_worker_requeues_to_survivor_token_identical(tiny_dense):
    import jax

    from conftest import make_params
    from repro.data.tasks import PatternTask
    from repro.rl.rollout import MultiWorkerRollout

    params = make_params(tiny_dense)
    task = PatternTask(n_problems=4, mean_len=6.0, max_len=10, seed=0)
    problems = task.problems()

    baseline = _mk_worker(params, tiny_dense, task).rollout(
        problems, key=jax.random.key(1)
    )
    flaky = FlakyWorker(_mk_worker(params, tiny_dense, task),
                        fail_calls=(0,))
    healthy = _mk_worker(params, tiny_dense, task)
    mw = MultiWorkerRollout([flaky, healthy], fault_tolerant=True)
    merged = mw.rollout(problems, key=jax.random.key(1))
    assert mw.stats["worker_failures"] == 1
    assert mw.stats["requeued_problems"] == 2
    assert merged.responses == baseline.responses
    np.testing.assert_array_equal(merged.tokens, baseline.tokens)
    np.testing.assert_array_equal(merged.rewards, baseline.rewards)
    np.testing.assert_allclose(
        merged.advantages, baseline.advantages, atol=1e-6
    )

    # non-FT mode still fails loudly
    mw_strict = MultiWorkerRollout(
        [FlakyWorker(_mk_worker(params, tiny_dense, task)),
         _mk_worker(params, tiny_dense, task)]
    )
    with pytest.raises(StallError):
        mw_strict.rollout(problems, key=jax.random.key(2))

    # FT with NO survivors: the original stall propagates
    mw_dead = MultiWorkerRollout(
        [FlakyWorker(_mk_worker(params, tiny_dense, task))],
        fault_tolerant=True,
    )
    with pytest.raises(StallError):
        mw_dead.rollout(problems, key=jax.random.key(3))


def test_device_runtime_error_propagates_instead_of_requeue(tiny_dense):
    """A JAX runtime error (a device OOM, say) is a fault of the program,
    not of one worker: fault-tolerant mode must not hand the slice to a
    survivor, which would hit the same error or hide it."""
    import jax

    from conftest import make_params
    from repro.data.tasks import PatternTask
    from repro.rl.rollout import MultiWorkerRollout

    class DeviceErrorWorker(FlakyWorker):
        def rollout(self, *args, **kwargs):
            self.calls += 1
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: injected device OOM"
            )

    params = make_params(tiny_dense)
    task = PatternTask(n_problems=4, mean_len=6.0, max_len=10, seed=0)
    failing = DeviceErrorWorker(_mk_worker(params, tiny_dense, task))
    survivor = FlakyWorker(_mk_worker(params, tiny_dense, task),
                           fail_calls=())
    mw = MultiWorkerRollout([failing, survivor], fault_tolerant=True)
    with pytest.raises(jax.errors.JaxRuntimeError):
        mw.rollout(task.problems(), key=jax.random.key(1))
    assert failing.calls == 1
    assert survivor.calls == 0
    assert mw.stats["worker_failures"] == 0
    assert mw.stats["requeued_problems"] == 0


def test_watchdog_threads_through_engine_rounds(tiny_dense):
    import jax

    from conftest import make_params

    params = make_params(tiny_dense)
    clk = VirtualClock()
    plan = FaultPlan(seed=0)
    wd = plan.stall_watchdog(
        RolloutWatchdog(deadline_s=30.0, clock=clk), at_check=2
    )
    from repro.core.spec_engine import EngineConfig, SpecEngine

    eng = SpecEngine(
        params, tiny_dense,
        EngineConfig(spec_enabled=True, max_new_tokens=12, eos_token=1,
                     use_budget_solver=False),
        drafter=SuffixDrafter(DrafterConfig(scope="problem", min_match=2)),
    )
    with pytest.raises(StallError):
        eng.generate([[2, 3, 4, 5]], ["a"], key=jax.random.key(0),
                     watchdog=wd)
    assert wd.stalls == 1
    assert plan.fired and plan.fired[0]["kind"] == "watchdog"
    # without a stall the same engine completes (watchdog is passive)
    wd2 = RolloutWatchdog(deadline_s=30.0, clock=VirtualClock())
    outs, _ = eng.generate([[2, 3, 4, 5]], ["a"], key=jax.random.key(0),
                           watchdog=wd2)
    assert outs and wd2.checks > 0 and wd2.stalls == 0


# ---------------------------------------------------------------------------
# THE chaos test: kill + restart every shard mid-rollout, torn and
# delayed frames, fault-tolerant fleet stays token-identical
# ---------------------------------------------------------------------------
def test_chaos_every_shard_killed_rollout_token_identical(tiny_dense):
    import jax

    from conftest import make_params
    from repro.data.tasks import PatternTask
    from repro.rl.rollout import MultiWorkerRollout

    params = make_params(tiny_dense)
    task = PatternTask(n_problems=4, mean_len=6.0, max_len=10, seed=0)
    problems = task.problems()
    keys = [jax.random.key(r) for r in range(3)]

    # ---- no-fault baseline: one local worker, same greedy verify ----
    single = _mk_worker(params, tiny_dense, task)
    want = [single.rollout(problems, key=k) for k in keys]

    # ---- chaos fleet: every shard dies once, plus torn + slow frames
    plan = (
        FaultPlan(seed=42)
        .kill_shard(0, op="publish", at=1)
        .kill_shard(1, op="publish", at=2)
        .truncate_frame(0, op="sync", at=2)
        .delay_frame(1, op="sync", at=1, delay_s=0.05)
    )
    svc = HistoryService.spawn_in_process(
        2, window_size=8,
        fault_hooks=[plan.server_hook(0), plan.server_hook(1)],
    )
    sup = ShardSupervisor(svc, seed=0, policy=TINY_BACKOFF)
    clients = [
        HistoryClient(svc.book, worker_id=f"w{w}", rpc_timeout=1.0,
                      backoff=TINY_BACKOFF, suspect_after=2)
        for w in range(2)
    ]
    try:
        mw = MultiWorkerRollout(
            [_mk_worker(params, tiny_dense, task, remote=c)
             for c in clients],
            fault_tolerant=True, supervisor=sup,
            flush_timeout=2.0, flush_retries=5,
        )
        got = []
        for r, k in enumerate(keys):
            got.append(mw.rollout(problems, key=k))
            for w in mw.workers:
                w.engine.begin_iteration(r + 1)
            single.engine.begin_iteration(r + 1)

        # every declared fault actually fired mid-run
        assert plan.pending() == 0, f"unfired faults: {plan.pending()}"
        kinds = {(f["op"], str(f["action"])) for f in plan.fired
                 if f["kind"] == "shard"}
        assert ("publish", "kill") in kinds
        assert ("sync", "truncate") in kinds
        assert any(op == "sync" and "delay" in act for op, act in kinds)
        # both shards were killed and supervised back up
        assert sup.stats["restarts"] >= 2

        # the acid test: T=0 token identity with the no-fault run
        for r, (g, w) in enumerate(zip(got, want)):
            assert g.responses == w.responses, f"round {r}"
            np.testing.assert_array_equal(g.tokens, w.tokens)
            np.testing.assert_array_equal(g.rewards, w.rewards)
            np.testing.assert_allclose(
                g.advantages, w.advantages, atol=1e-6
            )
        # the fleet felt the faults (this wasn't a quiet run)
        felt = sum(
            c.stats[k] for c in clients
            for k in ("publish_failures", "frame_errors", "sync_failures",
                      "rpc_timeouts")
        )
        assert felt >= 1, "chaos run must actually exercise failure paths"
    finally:
        for c in clients:
            c.close(flush_timeout=0.5)
        sup.stop()
        svc.stop()


def test_chaos_worker_killed_midrollout_journal_salvages_90pct(
    tiny_dense, tmp_path
):
    """Durability extension of the chaos suite: a worker dies mid-rollout
    (injected crash on its journal's group commit) and the fleet requeues
    its problems on the survivor, seeding them with the dead worker's
    journaled prefixes. At least 90% of the tokens the WAL had committed
    at death must be salvaged (not regenerated), and the merged batch
    stays token-identical to the no-fault single-worker run."""
    import jax

    from conftest import make_params
    from repro.data.tasks import PatternTask
    from repro.fault import RolloutJournal
    from repro.rl.rollout import MultiWorkerRollout, RolloutWorker

    params = make_params(tiny_dense)
    task = PatternTask(n_problems=4, mean_len=6.0, max_len=10, seed=0)
    problems = task.problems()

    def mk(journal_path=None, hook=None):
        from repro.core.spec_engine import EngineConfig, SpecEngine

        eng = SpecEngine(
            params, tiny_dense,
            EngineConfig(spec_enabled=True, max_new_tokens=10, eos_token=1,
                         use_budget_solver=False),
            drafter=SuffixDrafter(DrafterConfig(scope="problem",
                                                min_match=2)),
        )
        journal = None
        if journal_path is not None:
            journal = RolloutJournal(journal_path, fault_hook=hook)
        return RolloutWorker(eng, task, group_size=2, journal=journal)

    want = mk().rollout(problems, key=jax.random.key(1))

    wal = str(tmp_path / "dead.wal")
    plan = FaultPlan(seed=7).crash_journal(at=3, mode="raise")
    mw = MultiWorkerRollout(
        [mk(wal, plan.journal_hook()), mk(str(tmp_path / "alive.wal"))],
        fault_tolerant=True,
    )
    got = mw.rollout(problems, key=jax.random.key(1))

    assert mw.stats["worker_failures"] == 1
    assert plan.pending() == 0, "the journal crash must actually fire"

    # what the WAL had durably committed when the worker died
    committed = sum(
        len(s.tokens)
        for s in RolloutJournal.recover(wal).values()
        if s.resumable
    )
    assert committed > 0, "crash fired before any journaled progress"
    assert mw.stats["salvaged_tokens"] >= 0.9 * committed

    # token identity with the no-fault run (salvage is exact, not lossy)
    assert got.responses == want.responses
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_array_equal(got.rewards, want.rewards)
