"""The host side of the phase split (``phases.py``), at the small
cell's size on the CPU: a real telemetry in the generator's engine
drives the bounded profile from the program's own spans, and the window
step's telemetry deltas give the per-round numbers. The profiler's start
and stop are recorded instead of run."""

import time

import jax
import pytest

from bench import annotate, harness, phases, smallcell
from bench.compile_meter import CompileMeter
from bench.traffic import grpo


def test_program_spans_drive_the_profile(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    made = []
    monkeypatch.setattr(annotate, "Annotations",
                        phases._profiled_telemetry(made))
    spec, mix = smallcell.SPEC, smallcell.MIX
    ctx = harness.Context(
        spec=spec, mix=mix, cfg=harness.program_config(spec), seed=2**33 + 1,
        seconds=0.0, devices=jax.devices()[:1],
        t_start=time.perf_counter(), meter=CompileMeter(),
        log=lambda m: None, trace_dir=str(tmp_path / "trace"))
    rec = grpo.run(ctx)
    assert calls == ["start", "stop"]
    assert rec["trace_expect"] == {"jit_fused": mix["profile"]["rounds"]}
    (tel,) = made
    assert tel.enabled and tel.profile is None
    before, after = tel.snapshots  # attached to the window step, detached
    ws = phases.window_step(phases._delta(before, after), rec["rounds"])
    assert ws["phase_counts"]["verify_dispatch"] == rec["rounds"]
    assert ws["queue_wait"][1] == rec["attempted"]  # one per admission
    assert ws["queue_wait_rounds"] > 0  # 24 rollouts in 8 slots
    assert ws["forest_upload_bytes"] > 0 and ws["forest_repacks"] > 0


def test_window_step_per_round_numbers():
    def snap(publish, sync, up, long_, wait):
        h = "das_phase_seconds{phase=%s}"
        return {
            "counters": {"das_forest_upload_bytes_total": up},
            "histograms": {
                h % "history_publish": {"sum": publish, "count": 1},
                h % "history_sync": {"sum": sync, "count": 1},
                "das_accepted_tokens{length_class=long}":
                    {"sum": long_[0], "count": long_[1]},
                "das_queue_wait_rounds": {"sum": wait[0], "count": wait[1]},
            },
        }

    a = snap(1.0, 0.5, 4096.0, (3.0, 10), (0.0, 64))
    b = snap(1.3, 0.6, 4096.0 + 20 * 1024, (9.0, 14), (640.0, 128))
    ws = phases.window_step(phases._delta(a, b), rounds=10)
    assert ws["history_host_ms_per_round"] == pytest.approx(40.0)
    assert ws["forest_upload_kb_per_round"] == pytest.approx(2.0)
    assert ws["long_accepted_per_round"] == pytest.approx(1.5)
    assert ws["queue_wait_rounds"] == pytest.approx(10.0)
