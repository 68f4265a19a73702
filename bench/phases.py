#!/usr/bin/env python3
"""Split a cell's profiled rounds by phase, on the chip.

    python3 bench/phases.py --workload qwen2-1.5b.grpo-recur --seed 7 \\
        [--keep-trace phases.xplane.pb]

Runs the cell as ``run.py --trace 1`` does (the generator's set-up, then
the window's first step with the mix's bounded profile of rounds), with
a real ``repro.obs.Telemetry`` in the engine in place of the benchmark's
annotation-only one: the program's own spans then mark the profile, and
its counters count. The profile starts and stops after a ``consume``
span has closed, outside every span. Prints one JSON line:

- ``step_s`` (the profile's start and stop left out), ``rounds``,
  ``trace_stop_s``;
- ``trace``: ``trace_reduce``'s numbers for the profile (programs, top
  operations, idle gaps named by the program's spans);
- ``scopes`` (``bench/scopes.py``): device seconds per phase scope of
  each program, and ``top_ops``: the fused round's largest operations
  with their scope;
- ``per_round``: the fused round's device milliseconds per profiled
  round, whole and by scope;
- ``window_step``: the step's telemetry deltas (seconds and counts per
  span, forest bytes uploaded and repacks, accepted tokens per length
  class, queue wait in rounds) and what they give per round;
- ``span_us``: host microseconds one span costs with a real telemetry
  over the null one (no profile running), and per round.

Needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _err(msg: str) -> None:
    print(f"phases: {msg}", file=sys.stderr, flush=True)


class _Hooked:
    """A program span that also drives a ``RoundProfile``: a round
    dispatched inside the profile is counted as it starts, and the
    profile may start or stop once a ``consume`` has closed."""

    __slots__ = ("_span", "_prof", "_name")

    def __init__(self, span, prof, name: str) -> None:
        self._span, self._prof, self._name = span, prof, name

    def set(self, **attrs):
        return self._span.set(**attrs)

    def __enter__(self):
        if self._name == "verify_dispatch":
            self._prof.on_dispatch()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        if self._name == "consume":
            self._prof.on_consume()


def _profiled_telemetry(made: list):
    """A real ``Telemetry`` class that carries the generator's
    ``profile`` and snapshots its registry as the profile is attached to
    a step and detached from it (outside the step's timing)."""
    from repro import obs

    class ProfiledTelemetry(obs.Telemetry):
        def __init__(self) -> None:
            super().__init__()
            self._profile = None
            self.snapshots = []
            span = self.tracer.span

            def hooked(name):
                p = self._profile
                if p is None or name not in ("verify_dispatch", "consume"):
                    return span(name)
                return _Hooked(span(name), p, name)

            self.span = hooked
            made.append(self)

        @property
        def profile(self):
            return self._profile

        @profile.setter
        def profile(self, prof) -> None:
            self.snapshots.append(self.snapshot()["metrics"])
            self._profile = prof

    return ProfiledTelemetry


def _delta(a: dict, b: dict) -> dict:
    """Counter deltas, and histogram (sum, count) deltas, ``b - a``."""
    out = {k: v - a["counters"].get(k, 0.0)
           for k, v in b["counters"].items()}
    for k, h in b["histograms"].items():
        h0 = a["histograms"].get(k, {"sum": 0.0, "count": 0})
        out[k] = (h["sum"] - h0["sum"], h["count"] - h0["count"])
    return out


def window_step(d: dict, rounds: int) -> dict:
    """What the window step's telemetry deltas ``d`` give per round."""
    phase = {k[len("das_phase_seconds{phase="):-1]: v for k, v in d.items()
             if k.startswith("das_phase_seconds{")}
    hist = phase.get("history_publish", (0.0, 0))[0] + phase.get(
        "history_sync", (0.0, 0))[0]
    by_class = {k[len("das_accepted_tokens{length_class="):-1]: v
                for k, v in d.items() if k.startswith("das_accepted_tokens{")}
    wait = d.get("das_queue_wait_rounds", (0.0, 0))
    up = d.get("das_forest_upload_bytes_total", 0.0)
    long_sum, long_n = by_class.get("long", (0.0, 0))
    return {
        "rounds": rounds,
        "phase_seconds": {k: v[0] for k, v in phase.items()},
        "phase_counts": {k: v[1] for k, v in phase.items()},
        "forest_upload_bytes": up,
        "forest_repacks": d.get("das_drafter_stat_total{key=forest_repacks}",
                                0.0),
        "accepted_by_class": by_class,
        "queue_wait": wait,
        "history_host_ms_per_round": 1000.0 * hist / rounds,
        "forest_upload_kb_per_round": up / 1024.0 / rounds,
        "long_accepted_per_round": long_sum / long_n if long_n else None,
        "queue_wait_rounds": wait[0] / wait[1] if wait[1] else None,
        "round_host_ms": 1000.0 * d.get("das_round_host_seconds",
                                        (0.0, 0))[0] / rounds,
    }


def span_cost_us(n: int = 200_000) -> float:
    """Host microseconds one span costs with a real telemetry over the
    null one, best of three runs of ``n`` (no profile running)."""
    from repro import obs

    def per_span(tel) -> float:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                with tel.span("x"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    return 1e6 * (per_span(obs.Telemetry()) - per_span(obs.NULL))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profile's .xplane.pb here")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    if jax.devices()[0].platform != "tpu":
        _err("no TPU; nothing was run")
        return 2
    from bench import annotate, harness, scopes, trace_reduce
    from bench.compile_meter import CompileMeter

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.find_cell(bench, args.workload)
    spec = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                          f"{cell['config']}.json"))
    mix = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                         f"{cell['traffic']}.json"))
    trace_dir = os.path.join(ROOT, ".bench_out",
                             f"phases-{args.workload}-{args.seed}")
    made: list = []
    # the cell's own generator, with a real telemetry in its engine
    annotate.Annotations = _profiled_telemetry(made)
    ctx = harness.Context(
        spec=spec, mix=mix, cfg=harness.program_config(spec), seed=args.seed,
        seconds=0.0, devices=jax.devices()[: cell["chips"]],
        t_start=T_START, meter=CompileMeter(), log=_err, trace_dir=trace_dir)
    rec = harness.load_generator(mix["generator"])(ctx)
    n = rec["traced"]["rounds"]
    before, after = made[-1].snapshots
    per_span = span_cost_us()
    ws = window_step(_delta(before, after), rec["rounds"])
    spans_per_round = sum(ws["phase_counts"].values()) / rec["rounds"]
    out = {
        "workload": args.workload, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "setup_s": rec["setup_s"], "step_s": rec["window_s"],
        "rounds": rec["rounds"], "host_time_s": rec["host_time_s"],
        "trace_stop_s": rec["notes"]["trace_stop_s"],
        "window_step": ws,
        "span_us": {"per_span": per_span,
                    "spans_per_round": spans_per_round,
                    "per_round": per_span * spans_per_round},
    }
    try:  # what was measured is printed even if a reduction refuses
        (path,) = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                   for f in fs if f.endswith(".xplane.pb")]
        if args.keep_trace:
            shutil.copy(path, args.keep_trace)
        out["trace"] = reduced = trace_reduce.reduce(
            trace_reduce.load(path), expect=rec["trace_expect"])
        space = scopes.load(path)
        out["top_ops"] = scopes.top_ops(space)
        out["scopes"] = by_scope = scopes.reduce(space)
        fused_s = reduced["programs"]["jit_fused"]
        fused = by_scope["jit_fused"]
        out["per_round"] = {
            "profiled_rounds": n,
            "fused_round_ms": 1000.0 * fused_s / n,
            **{f"{k}_ms": 1000.0 * v / n for k, v in fused.items()},
        }
        out["scope_sum_over_fused"] = sum(fused.values()) / fused_s
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
