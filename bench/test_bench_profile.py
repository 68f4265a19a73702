"""The profile of a bounded run of rounds inside a step: it starts and
stops once, where no round is in flight, and holds exactly the rounds
dispatched inside it -- the count the trace reduction then checks the
device's executions against. Driven through the GRPO generator at the
small cell's size on the CPU, with the profiler's start and stop
recorded instead of run."""

import time

import jax

from bench import harness, smallcell
from bench.compile_meter import CompileMeter
from bench.traffic import grpo


def test_profile_holds_whole_rounds(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    spec, mix = smallcell.SPEC, smallcell.MIX
    ctx = harness.Context(
        spec=spec, mix=mix, cfg=harness.program_config(spec), seed=3,
        seconds=0.1, devices=jax.devices()[:1],
        t_start=time.perf_counter(), meter=CompileMeter(),
        log=lambda m: None, trace_dir=str(tmp_path / "trace"))
    rec = grpo.run(ctx)
    rounds = mix["profile"]["rounds"]
    assert calls == ["start", "stop"]
    assert rec["trace_expect"] == {"jit_fused": rounds}
    work = rec["traced"]
    assert work["rounds"] == rounds
    # every active row verifies its head token, plus its drafts
    assert work["block_tokens"] >= rounds
    assert work["context_reads"] > 0
    assert rec["failed"] == 0 and rec["steps"] == 1
