"""Reduce a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

- ``busy_s``: the union of the intervals in which an XLA operation ran
  on the device, inside the window; ``window_s`` the window's length.
  The window starts at the host annotation ``WINDOW`` (a mark the
  harness opens as the trace starts) and ends where that annotation
  ends, or, for a bare mark, with the device's last event; without the
  annotation it is the span of the device's events.
- ``programs``: device seconds and executions per XLA program (module
  name without its numeric suffix, e.g. ``jit_fused``).
- ``ops``: device seconds per XLA operation, summed by its name (the
  HLO instruction name, e.g. ``convolution_tanh_fusion.3``).
- ``idle_gaps``: every gap between busy intervals inside the window,
  named by the innermost host event open at the gap's middle on the
  thread that opened the window (the engine's round-phase spans from
  ``annotate.py``, or JAX's own dispatch events), summed by name.

Device planes are averaged over the chips the trace holds. A trace
with no device operation in its window, or whose executions of a program
differ from what the run dispatched (``expect``: events the device's
trace buffers dropped, or work outside the window), is refused: its
numbers would not describe the window.
"""

from __future__ import annotations

import re
from collections import defaultdict

WINDOW = "bench_window"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def _base(name: str) -> str:
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def _op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _is_device(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def _name_at(events, points):
    """For each of the sorted ``points``, the innermost (shortest)
    event open there, by base name."""
    evs = sorted((s, e, name) for name, s, e in events if name != WINDOW)
    out, active, i = [], [], 0
    for p in points:
        while i < len(evs) and evs[i][0] <= p:
            active.append(evs[i])
            i += 1
        active = [ev for ev in active if ev[1] > p]
        inner = min(active, key=lambda ev: ev[1] - ev[0], default=None)
        out.append(_base(inner[2]) if inner else "(no host span)")
    return out


def load(path):
    """``ProfileData`` of an ``.xplane.pb`` file, or of a text proto
    (``.pbtxt``, as the tests keep theirs)."""
    from jax.profiler import ProfileData

    if path.endswith(".pbtxt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def reduce(pd, top: int = 10, expect: dict | None = None) -> dict:
    """Numbers of one trace (``ProfileData``); see the module
    docstring. ``expect`` maps program names to the executions the
    window must hold."""
    devices = []
    host_lines = []
    for plane in pd.planes:
        if _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")

    window = None
    main_events = []
    for line in host_lines:
        evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
               for ev in line.events]
        for name, a, b in evs:
            if name == WINDOW:
                window = (a, b)
                main_events = evs
                break
    per_dev = []
    for lines in devices:
        ops_line = lines.get("XLA Ops")
        mod_line = lines.get("XLA Modules")
        ops = [(_op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
               for ev in (ops_line.events if ops_line else [])]
        mods = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in (mod_line.events if mod_line else [])]
        per_dev.append((ops, mods))
    evs = [e for ops, mods in per_dev for e in (ops or mods)]
    if not evs:
        raise ValueError("the trace holds no device operation")
    last = max(e[2] for e in evs)
    if window is None:
        window = (min(e[1] for e in evs), last)
    elif window[1] - window[0] < 1e3:  # a bare mark: open to the end
        window = (window[0], last)
    lo, hi = window

    busy_total = 0.0
    programs = defaultdict(float)
    counts = defaultdict(int)
    op_time = defaultdict(float)
    gaps_by = defaultdict(float)
    n_gaps = 0
    for d, (ops, mods) in enumerate(per_dev):
        src = ops or mods
        busy = _union(_clip([(a, b) for _, a, b in src], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in mods:
            if b > lo and a < hi:
                programs[_base(name)] += (min(b, hi) - max(a, lo)) / 1e9
                counts[_base(name)] += 1
        for name, a, b in ops:
            if b > lo and a < hi:
                op_time[name] += (min(b, hi) - max(a, lo)) / 1e9
        if d:
            continue  # gaps are named on the first chip
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        n_gaps += len(gaps)
        for (a, b), name in zip(gaps, _name_at(
                main_events, [(a + b) / 2 for a, b in gaps])):
            gaps_by[name] += (b - a) / 1e9
    n = len(per_dev)
    if busy_total <= 0:
        raise ValueError("the trace holds no device operation in its "
                         "window")
    for name, want in (expect or {}).items():
        got = counts.get(name, 0)
        if got != want * n:
            raise ValueError(f"the trace holds {got / n:g} executions of "
                             f"{name} per chip, the run dispatched {want}")

    def top_items(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "n_devices": n,
        "programs": {k: v / n for k, v in programs.items()},
        "program_counts": {k: c // n for k, c in counts.items()},
        "ops": top_items(op_time),
        "idle_gaps": top_items(gaps_by),
        "n_idle_gaps": n_gaps,
    }
