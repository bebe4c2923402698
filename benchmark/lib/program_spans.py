"""The program's own boundary spans and scopes, read from the traced run's
xplane, and the arithmetic the readers built on them share.

``paddle_tpu`` enters a ``jax.profiler.TraceAnnotation`` at each layer
boundary of ``Engine.fit`` and ``Router -> PagedEngine``
(``paddle_tpu/observability/trace.py``, ``BOUNDARY_SPANS``), so a recording
holds them on the ``/host:CPU`` plane, on the clock of the device's
``XLA Ops`` line. ``SPANS`` is the yardstick's copy of that table: name ->
parent. A program that enters none of them (the parent of the PR that added
them) gives every reader here nothing to read.

``ctx`` carries no path, so ``recording()`` opens the newest
``.bench_out/traces/*/plugins/profile/*/*.xplane.pb``: the one the run just
wrote. Host and device clocks agree to a few tenths of a millisecond in a
recording (a program may appear to start 0.2 ms before its launch returns).

An operation's scope is the ``tf_op`` stat of its event's metadata in the
device plane: the HLO instruction's ``op_name``, the ``jax.named_scope``
path it was traced under (``jit(engine_train_step)/jit(main)/
transpose(jvp(lm_head))/dot_general:``). ``jax.profiler.ProfileData`` does
not hand out metadata stats, so ``op_scopes`` reads them from the file's
bytes (xplane.proto is five small messages). A fusion is billed to the one
``op_name`` the compiler left on the fusion instruction.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

from benchmark.lib import harness, trace_reduce

#: boundary span -> parent (None: top level)
SPANS = {
    "fit.setup": None, "fit.step": None, "fit.next_batch": "fit.step",
    "fit.dispatch": "fit.step", "fit.post_step": "fit.step",
    "fit.epoch_sync": None, "fit.writeback": None, "io.prefetch": None,
    "router.step": None, "router.deliver": "router.step",
    "serving.tick": "router.step", "serving.admit": "serving.tick",
    "serving.plan": "serving.tick", "serving.prefill": "serving.tick",
    "serving.prefill.build": "serving.prefill",
    "serving.prefill.launch": "serving.prefill",
    "serving.prefill.wait": "serving.prefill",
    "serving.decode": "serving.tick",
    "serving.decode.build": "serving.decode",
    "serving.decode.launch": "serving.decode",
    "serving.decode.wait": "serving.decode",
    "serving.emit": "serving.tick",
}
#: spans with no child, on the thread that drives the device: idle time
#: inside one of them has an owner (io.prefetch runs beside them on the
#: producer thread and owns nothing the device waits for directly)
LEAVES = sorted(set(SPANS) - set(SPANS.values()) - {"io.prefetch"})


def newest_xplane():
    paths = glob.glob(os.path.join(
        harness.ROOT, ".bench_out", "traces", "*", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


# ------------------------------------------------------------ protobuf wire
def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one serialized message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {wire} is not in xplane.proto")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def op_scopes(path: str, plane_prefix: str = trace_reduce.DEVICE_PREFIX):
    """``{event name: op_name}`` of the first device plane: XSpace.planes(1)
    -> XPlane{name(2), event_metadata(4), stat_metadata(5)} ->
    XEventMetadata{name(2), stats(5)} -> XStat{metadata_id(1), str_value(5)
    | ref_value(7)}, the stat whose metadata is named ``tf_op``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name = next((_text(v) for n, v in _fields(plane) if n == 2), "")
        if name.startswith(plane_prefix):
            planes.append((name, plane))
    if not planes:
        return {}
    _name, plane = min(planes, key=lambda p: p[0])
    stat_names, events = {}, []
    for number, entry in _fields(plane):
        if number not in (4, 5):
            continue
        message = next((v for n, v in _fields(entry) if n == 2), None)
        if message is None:
            continue
        if number == 5:
            f = dict(_fields(message))
            stat_names[f.get(1, 0)] = _text(f.get(2, b""))
        else:
            events.append(message)
    tf_op = next((i for i, n in stat_names.items() if n == "tf_op"), None)
    if tf_op is None:
        return {}
    out = {}
    for message in events:
        name, scope = "", None
        for number, value in _fields(message):
            if number == 2:
                name = _text(value)
            elif number == 5:
                stat = dict(_fields(value))
                if stat.get(1) == tf_op:
                    scope = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
        if scope:
            out[name] = scope
    return out


# -------------------------------------------------------------- the recording
@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime: float) -> dict:
    from jax.profiler import ProfileData

    spans = {name: [] for name in SPANS}
    ops, modules = [], []
    data = ProfileData.from_file(path)
    device = min((p.name for p in data.planes
                  if p.name.startswith(trace_reduce.DEVICE_PREFIX)),
                 default=None)
    for plane in data.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        start = ev.start_ns * 1e-9
                        spans[ev.name].append(
                            (start, start + ev.duration_ns * 1e-9))
        elif plane.name == device:
            named = {line.name: [(ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9)
                                 for ev in line.events]
                     for line in plane.lines
                     if line.name in (trace_reduce.OPS_LINE,
                                      trace_reduce.MODULES_LINE)}
            ops = named.get(trace_reduce.OPS_LINE, [])
            modules = named.get(trace_reduce.MODULES_LINE, [])
    return {"spans": {n: sorted(v) for n, v in spans.items()},
            "ops": ops, "modules": modules, "scopes": op_scopes(path)}


def recording(ctx):
    """The program's spans (``{name: [(start, end)]}``, seconds on the
    trace's clock), the first chip's operations and programs under their
    full names, and ``{operation name: op_name}``: or None where the run
    has no trace or the program entered no span."""
    trace = ctx.get("trace")
    if not trace or not trace.get("devices"):
        return None
    path = newest_xplane()
    if path is None:
        return None
    rec = _load(path, os.path.getmtime(path))
    return rec if any(rec["spans"].values()) else None


# ----------------------------------------------------------------- arithmetic
def idle_gaps(ctx):
    """The first chip's idle intervals inside the traced window."""
    trace = ctx["trace"]
    first = trace["devices"][sorted(trace["devices"])[0]]
    return trace_reduce.gaps([(s, e) for _n, s, e in first["ops"]],
                             trace["t_lo"], trace["t_hi"])


def merged(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap_seconds(a, b) -> float:
    """Length of the intersection of two lists of sorted, disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside(ctx, rec, names) -> float:
    """Seconds the first chip sat idle, in the traced window, while the
    host was inside a span of one of ``names``."""
    spans = merged([iv for n in names for iv in rec["spans"].get(n, [])])
    return overlap_seconds(idle_gaps(ctx), spans)


def contained(inner, outer):
    """The intervals of ``inner`` that start inside ``outer``."""
    s0, e0 = outer
    return [iv for iv in inner if s0 <= iv[0] < e0]


def module_ms(ctx, program: str):
    """Device milliseconds a call of the program named ``jit_<program>``
    took, over the traced window (``trace["modules"]``), or None."""
    trace = ctx.get("trace")
    if not trace:
        return None
    calls, seconds = trace_reduce.seconds_matching(
        trace.get("modules", {}), (f"jit_{program}(",))
    return 1e3 * seconds / calls if calls else None


def kernel_name(hlo: str) -> str:
    """``%flash_fwd.3 = (...) custom-call(...)`` -> ``flash_fwd``: the
    compiler names a Mosaic kernel's instruction after the kernel's
    ``name=`` (and a fusion ``fusion``)."""
    return hlo.partition(" = ")[0].lstrip("%").rsplit(".", 1)[0]


def _under(scope: str):
    return re.compile(r"(?:^|[/(])%s(?:[/)]|$)" % re.escape(scope))


def scope_seconds(rec, scopes, program: str):
    """``(seconds under any of the scopes, calls of the program, the
    program's seconds)`` on the first chip: operations that ran inside a
    call of ``jit_<program>`` and whose ``op_name`` has one of ``scopes`` as
    a component of its path, bare or inside ``jvp(...)`` / ``transpose(...)``.
    None where the trace names no scope."""
    if not rec["scopes"]:
        return None
    calls = merged([(s, e) for n, s, e in rec["modules"]
                    if n.startswith(f"jit_{program}(")])
    if not calls:
        return None
    patterns = [_under(s) for s in scopes]
    starts = [c[0] for c in calls]
    under = 0.0
    for name, s, e in rec["ops"]:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= calls[k][1]:
            continue
        # tf_op is "<op_name>:<op type>"
        path = rec["scopes"].get(name, "").rsplit(":", 1)[0]
        if any(p.search(path) for p in patterns):
            under += e - s
    return under, len(calls), sum(e - s for s, e in calls)


# ------------------------------------------------ what several readers share
def idle_attributed_pct(ctx, kind: str):
    rec = recording(ctx) if ctx["kind"] == kind else None
    if rec is None:
        return None
    idle = sum(e - s for s, e in idle_gaps(ctx))
    return 100.0 * idle_inside(ctx, rec, LEAVES) / idle if idle else None


def idle_ms_per_step(ctx, names):
    """Idle milliseconds of the first chip inside the named spans, per
    ``fit.dispatch`` entered in the traced window."""
    rec = recording(ctx) if ctx["kind"] == "fit" else None
    if rec is None:
        return None
    lo, hi = ctx["trace"]["t_lo"], ctx["trace"]["t_hi"]
    steps = sum(1 for s, e in rec["spans"]["fit.dispatch"] if lo <= s < hi)
    return 1e3 * idle_inside(ctx, rec, names) / steps if steps else None


def scope_ms_per_step(ctx, scopes):
    rec = recording(ctx) if ctx["kind"] == "fit" else None
    got = rec and scope_seconds(rec, scopes, "engine_train_step")
    if not got:
        return None
    under, calls, _program_s = got
    return 1e3 * under / calls
