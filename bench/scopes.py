"""Device time of the fused round's named phases in a profiler trace.

The fused round program runs its phases under ``jax.named_scope``
(``core/fused_round.py``): ``propose``, ``forward``, ``accept`` and
``commit``. XLA keeps the scope path in each instruction's ``op_name``
metadata (``jit(fused)/forward/while/...``), and the TPU's trace carries
it in the ``tf_op`` stat of each ``XLA Ops`` event's metadata, which
``jax.profiler.ProfileData`` does not expose; so this module reads the
``.xplane.pb`` itself with a minimal schema of the profiler's ``XSpace``
message. A ``while`` carries no ``tf_op`` there, but the operations of
its body do.

``reduce(space)`` gives, for each device program (module name without
its numeric suffix, as ``trace_reduce`` names them), the device seconds
of each scope inside the window: the union of the intervals of the
program's operations under that scope, so a ``while`` and the operations
inside it count once. ``unscoped`` is the rest of the program's time.
The window is ``trace_reduce``'s: the host annotation ``WINDOW``, a bare
mark open to the device's last event, or else the device's events. Device
planes are averaged over the chips the trace holds.

A trace in which a program of ``require`` has device time but none of
its operations carries one of the scopes is refused: a refactor that
drops the scopes fails loudly instead of reading 0.
"""

from __future__ import annotations

import functools
from collections import defaultdict

from bench.trace_reduce import WINDOW, _base, _clip, _is_device, _op_name, _union

PHASES = ("propose", "forward", "accept", "commit")
UNSCOPED = "unscoped"
SCOPE_STAT = "tf_op"


@functools.cache
def _space_class():
    """The ``XSpace`` message class, built from the fields of
    ``xplane.proto`` this module reads (maps as their wire-compatible
    repeated entries)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                             package="bench_xplane")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, kind, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=kind, label=label)
            if type_name:
                f.type_name = f".bench_xplane.{type_name}"

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, one, None),
        ("str_value", 5, F.TYPE_STRING, one, None))
    msg("XEvent", ("metadata_id", 1, F.TYPE_INT64, one, None),
        ("offset_ps", 2, F.TYPE_INT64, one, None),
        ("duration_ps", 3, F.TYPE_INT64, one, None))
    msg("XLine", ("name", 2, F.TYPE_STRING, one, None),
        ("timestamp_ns", 3, F.TYPE_INT64, one, None),
        ("events", 4, F.TYPE_MESSAGE, rep, "XEvent"))
    msg("XEventMetadata", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_STRING, one, None),
        ("stats", 5, F.TYPE_MESSAGE, rep, "XStat"))
    msg("XStatMetadata", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_STRING, one, None))
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
        ("value", 2, F.TYPE_MESSAGE, one, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
        ("value", 2, F.TYPE_MESSAGE, one, "XStatMetadata"))
    msg("XPlane", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_STRING, one, None),
        ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, rep, "EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, rep, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, rep, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def load(path: str):
    """The ``XSpace`` of an ``.xplane.pb`` file, or of a text proto
    (``.pbtxt``, as the tests keep theirs)."""
    if path.endswith(".pbtxt"):
        from jax.profiler import ProfileData

        with open(path) as f:
            data = ProfileData.text_proto_to_serialized_xspace(f.read())
    else:
        with open(path, "rb") as f:
            data = f.read()
    space = _space_class()()
    space.ParseFromString(data)
    return space


def scope_of(op_name: str) -> str | None:
    """The outermost phase scope in an ``op_name`` path (the TPU's
    ``tf_op`` ends it with a colon), or None."""
    for part in op_name.rstrip(":").split("/"):
        if part in PHASES:
            return part
    return None


def _stat(plane_stats, stats, name):
    """The string value of stat ``name`` among ``stats``, or None."""
    for st in stats:
        if plane_stats.get(st.metadata_id) == name:
            return st.str_value
    return None


def _events(plane, line_name=None, with_scope: bool = False):
    """``(name, scope, start_ns, end_ns)`` of the events of the plane's
    line ``line_name`` (of every line, without one); ``scope`` is None
    unless ``with_scope``."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    metas = {e.key: e.value for e in plane.event_metadata}
    scopes = {}
    if with_scope:
        for key, meta in metas.items():
            op = _stat(stat_names, meta.stats, SCOPE_STAT)
            scopes[key] = scope_of(op) if op else None
    out = []
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for ev in line.events:
            meta = metas.get(ev.metadata_id)
            a = line.timestamp_ns + ev.offset_ps / 1e3
            out.append((meta.name if meta else "",
                        scopes.get(ev.metadata_id), a,
                        a + ev.duration_ps / 1e3))
    return out


def _intersect(a, b):
    """Length of the overlap of two sorted unions of intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _window(space):
    """The host annotation ``WINDOW``'s interval, or None."""
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for name, _, a, b in _events(plane):
                if name == WINDOW:
                    return a, b
    return None


def reduce(space, require=("jit_fused",)) -> dict:
    """Seconds per scope of each device program inside the window; see
    the module docstring."""
    devices = [p for p in space.planes if _is_device(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    per_dev = [(_events(p, "XLA Modules"), _events(p, "XLA Ops", True))
               for p in devices]
    evs = [e for mods, ops in per_dev for e in (ops or mods)]
    if not evs:
        raise ValueError("the trace holds no device operation")
    last = max(e[3] for e in evs)
    window = _window(space)
    if window is None:
        window = (min(e[2] for e in evs), last)
    elif window[1] - window[0] < 1e3:  # a bare mark: open to the end
        window = (window[0], last)
    lo, hi = window

    out = defaultdict(lambda: defaultdict(float))
    for mods, ops in per_dev:
        by_prog = defaultdict(list)
        for name, _, a, b in mods:
            by_prog[_base(name)].append((a, b))
        scoped = defaultdict(list)
        for _, scope, a, b in ops:
            if scope is not None:
                scoped[scope].append((a, b))
        scoped = {s: _union(_clip(iv, lo, hi)) for s, iv in scoped.items()}
        every = _union([iv for ivs in scoped.values() for iv in ivs])
        for prog, ivs in by_prog.items():
            prog_iv = _union(_clip(ivs, lo, hi))
            total = sum(b - a for a, b in prog_iv)
            if total <= 0:
                continue
            for scope, iv in scoped.items():
                t = _intersect(iv, prog_iv)
                if t > 0:
                    out[prog][scope] += t / 1e9
            out[prog][UNSCOPED] += (total - _intersect(every, prog_iv)) / 1e9
    n = len(per_dev)
    result = {prog: {s: t / n for s, t in times.items()}
              for prog, times in out.items()}
    for prog in require:
        times = result.get(prog)
        if times and not any(s in times for s in PHASES):
            raise ValueError(
                f"{prog} has device time but none of its operations "
                f"carries a phase scope ({', '.join(PHASES)})")
    return result


def top_ops(space, program: str = "jit_fused", top: int = 10) -> list:
    """``[name, scope, seconds]`` of the ``top`` operations of
    ``program`` by device time in the whole trace (summed over the
    operation's events, nested ones included, averaged over chips)."""
    devices = [p for p in space.planes if _is_device(p.name)]
    by = defaultdict(float)
    scope_by = {}
    for plane in devices:
        mods = _union([(a, b) for name, _, a, b in
                       _events(plane, "XLA Modules")
                       if _base(name) == program])
        for name, scope, a, b in _events(plane, "XLA Ops", True):
            if _intersect([(a, b)], mods) > 0:
                key = _op_name(name)
                by[key] += (b - a) / 1e9
                scope_by[key] = scope
    n = max(len(devices), 1)
    return [[k, scope_by[k], v / n] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:top]]
