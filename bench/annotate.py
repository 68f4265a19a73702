"""Host spans on the profiler's clock, and a profile of a bounded run of
rounds.

``Annotations`` is a telemetry for ``SpecEngine(telemetry=...)`` whose
spans open ``jax.profiler.TraceAnnotation``: the engine's round phases
(``serve_round``, ``consume``, ``verify_dispatch``, ``prefill``,
``history_publish``...) then appear in the device trace, where
``trace_reduce`` names each idle gap of the device by the span open
during it. It records nothing else (``enabled`` is False, so the
engine's counters and events stay the no-op kind). Used only in runs
with ``--trace 1``.

``RoundProfile`` profiles rounds ``after`` .. ``after + rounds`` of the
next ``serve`` call, counted by the engine's ``consume`` spans. When a
``consume`` span closes, the round result is on the host and nothing is
in flight on the device, so the profile starts and stops at points
where the device is quiet: the trace then holds every round dispatched
inside it, whole. The profiler's own start and stop are kept out of the
window (``trace_reduce.WINDOW``) and their seconds are returned, since
they are not the system's work. A profile of a few seconds also stays
inside the device's trace buffers, which a whole step overflows.
"""

from __future__ import annotations

import shutil
import time

from repro.obs import NullTelemetry


class _Span:
    __slots__ = ("_ta", "_name", "_owner")

    def __init__(self, name: str, owner) -> None:
        import jax

        self._ta = jax.profiler.TraceAnnotation(name)
        self._name = name
        self._owner = owner

    def __enter__(self):
        prof = self._owner.profile
        if prof is not None and self._name == "verify_dispatch":
            prof.on_dispatch()
        self._ta.__enter__()
        return self

    def __exit__(self, *exc):
        out = self._ta.__exit__(*exc)
        prof = self._owner.profile
        if prof is not None and self._name == "consume":
            prof.on_consume()
        return out

    def set(self, **_attrs) -> None:
        pass


class RoundProfile:
    """Profile ``rounds`` rounds of one ``serve`` call after its first
    ``after`` iterations. ``snapshot()`` is called at the profile's
    start and end (the generator's counters at those points)."""

    def __init__(self, trace_dir: str, after: int, rounds: int,
                 snapshot) -> None:
        self.trace_dir = trace_dir
        self.after = int(after)
        self.rounds = int(rounds)
        self.snapshot = snapshot
        self.consumed = 0
        self.dispatched = 0
        self.state = "waiting"  # -> "tracing" -> "done"
        self.start = self.end = None
        self.overhead_s = 0.0
        self.stop_s = 0.0
        self._window = None

    def on_dispatch(self) -> None:
        if self.state == "tracing":
            self.dispatched += 1

    def on_consume(self) -> None:
        import jax

        from bench import trace_reduce

        self.consumed += 1
        if self.state == "waiting" and self.consumed == self.after:
            t0 = time.perf_counter()
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            # host annotations only (the Python function tracer would
            # record every call of the serving loop), no HLO protos
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self.start = self.snapshot()
            self.overhead_s += time.perf_counter() - t0
            self._window.__enter__()
            self.state = "tracing"
        elif (self.state == "tracing"
              and self.consumed == self.after + self.rounds):
            self._window.__exit__(None, None, None)
            t0 = time.perf_counter()
            self.end = self.snapshot()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t0
            self.overhead_s += self.stop_s
            self.state = "done"


class Annotations(NullTelemetry):
    enabled = False

    def __init__(self) -> None:
        super().__init__()
        self.profile: RoundProfile | None = None
        self.span = self._span

    def _span(self, name: str) -> _Span:
        return _Span(name, self)
