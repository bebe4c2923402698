"""From a ``jax.profiler`` ``.xplane.pb`` to the numbers the benchmark
reports: device busy time, the traced window, time per operation name, and
the longest idle gaps with what the host was doing in them.

Reads the file with nothing but JAX (``jax.profiler.ProfileData``). A device
plane is one whose name starts with ``/device:TPU:``; its ``XLA Ops`` line
holds one event per operation run on that chip (start and duration in
nanoseconds) and its ``XLA Modules`` line one event per program run. Host
threads are the lines of the ``/host:CPU`` plane; the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``) are found there.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
BENCH_SPAN_PREFIX = "bench."


def find_xplane(directory: str):
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo: str) -> str:
    """``%fusion.942 = (f32[...], ...) fusion(...), kind=kOutput`` ->
    ``%fusion.942 fusion``: the instruction and its opcode, without shapes
    and operands. A Mosaic kernel (``custom_call_target="tpu_custom_call"``)
    keeps what tells one kernel from another, since its instruction name is
    whatever scope it was traced under: ``%checkpoint.57 tpu_custom_call/6
    (bf16[128,1024,128],bf16[128,1024,128])`` is a call with six operands and
    two results. Names that are not HLO text are kept."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    depth, shape = 0, rest
    for i, ch in enumerate(rest):          # split off the (tuple) shape
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            shape, rest = rest[:i], rest[i + 1:]
            break
    opcode, _, operands = rest.partition("(")
    if opcode == "custom-call" and 'custom_call_target="' in rest:
        target = rest.split('custom_call_target="', 1)[1].split('"', 1)[0]
        n_in = operands.split("), ", 1)[0].count("%")
        shape = _LAYOUT.sub("", shape).replace(" ", "")
        return f"{name} {target}/{n_in} {shape}"
    return f"{name} {opcode}"


def _events(line, shorten=False):
    """``[(name, start_s, end_s)]`` of a trace line."""
    out = []
    for ev in line.events:
        start = ev.start_ns * 1e-9
        name = short_name(ev.name) if shorten else ev.name
        out.append((name, start, start + ev.duration_ns * 1e-9))
    return out


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """Idle ``(start, end)`` intervals inside ``[lo, hi]`` not covered by
    any of ``intervals``."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "async_ops": [...],
    "modules": [...]}},
    "host_spans": [(name, start, end)]}`` with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                "ops": (_events(lines[OPS_LINE], shorten=True)
                        if OPS_LINE in lines else []),
                "async_ops": (_events(lines[ASYNC_LINE], shorten=True)
                              if ASYNC_LINE in lines else []),
                "modules": (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else [])}
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host_spans += [e for e in _events(ln)
                               if e[0].startswith(BENCH_SPAN_PREFIX)]
    return {"devices": devices, "host_spans": sorted(host_spans,
                                                     key=lambda e: e[1])}


def reduce(path: str) -> dict:
    """The summary the per-layer readers work from. Per-device quantities
    are averaged over the device planes that ran anything.

    * ``window_s``: first device event's start to the last one's end, the
      same interval on every chip (the recording's ramp before the first
      operation is not steady state and is left out);
    * ``busy_s``: union of the ``XLA Ops`` intervals inside it;
    * ``ops``: ``{name: [calls, seconds]}`` per chip;
    * ``async_ops``: the same for the ``Async XLA Ops`` line, where an
      asynchronous operation (a collective, a copy) spans from its start to
      its done;
    * ``modules``: the same for whole programs;
    * ``idle_gaps``: the ten longest gaps of the first chip, each labelled
      with the benchmark span that covers most of it (or ``host``)."""
    raw = load(path)
    used = {n: d for n, d in raw["devices"].items() if d["ops"]}
    if not used:
        return {"planes": sorted(raw["devices"]), "busy_s": 0.0,
                "window_s": 0.0, "ops": {}, "async_ops": {}, "modules": {},
                "idle_gaps": [],
                "host_spans": raw["host_spans"], "n_devices": 0}
    lo = min(e[1] for d in used.values() for e in d["ops"])
    hi = max(e[2] for d in used.values() for e in d["ops"])
    n = len(used)
    busy = sum(union_seconds([(s, e) for _n, s, e in d["ops"]])
               for d in used.values()) / n

    def table(kind):
        acc = {}
        for d in used.values():
            for name, s, e in d[kind]:
                row = acc.setdefault(name, [0, 0.0])
                row[0] += 1 / n
                row[1] += (e - s) / n
        return acc

    first = used[sorted(used)[0]]
    idle = sorted(gaps([(s, e) for _n, s, e in first["ops"]], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    labelled = []
    for a, b in idle:
        best, cover = "host", 0.5 * (b - a)   # a span must cover most of it
        for name, s, e in raw["host_spans"]:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        labelled.append([best, b - a])
    return {"planes": sorted(raw["devices"]), "n_devices": n,
            "busy_s": busy, "window_s": hi - lo, "t_lo": lo, "t_hi": hi,
            "ops": table("ops"), "async_ops": table("async_ops"),
            "modules": table("modules"),
            "idle_gaps": labelled, "host_spans": raw["host_spans"],
            "devices": used}


def breakdown(summary: dict) -> dict:
    """The ``breakdown`` key of a ``--trace 1`` line: the ten device
    operations that took most time, and the ten longest idle gaps."""
    top = sorted(summary["ops"].items(), key=lambda kv: -kv[1][1])[:10]
    return {"device_ops": [[name, sec] for name, (_c, sec) in top],
            "idle_gaps": summary["idle_gaps"][:10]}


def seconds_matching(table: dict, needles) -> tuple:
    """``(calls, seconds)`` summed over the names that contain any needle."""
    calls = secs = 0.0
    for name, (c, s) in table.items():
        if any(n in name for n in needles):
            calls += c
            secs += s
    return calls, secs
