"""What the host was doing while the chip waited: the collector's pauses
and the CPU time of the program's spans, read from the traced run.

``paddle_tpu`` measures each pass of Python's cyclic collector where it
happens (``paddle_tpu/observability/trace.py``, ``HOST_SPANS``): a
``host.gc`` ``TraceAnnotation`` with stats ``generation`` / ``collected`` /
``uncollectable`` on whichever thread the pass ran on (every other Python
thread stands still for its length: the pass holds the GIL), and, on
``time.perf_counter``'s clock, a bounded pause record
(``trace.host_pauses()``: every full pass and any pass of a millisecond or
more). Its boundary spans carry ``cpu_s``, the thread's CPU seconds inside
the span: a span whose wall time is far above its ``cpu_s`` was off the CPU
(blocked in a call, waiting for the GIL, descheduled), one whose ``cpu_s``
matches was computing.

The three readers built here say how much of the window the collector took
(``gc_pause_share_pct``), how much of the chip's idle time lies under a pass
(``idle_in_gc_pct``) and how long a tick that launched a program leaves the
chip idle (``tick_idle_ms``), and log, for the longest idle gaps, the span
that was open, its wall and CPU seconds and the passes under the gap. A
program without the hook (the parent of the PR that added it) has no
``host.gc`` event, no ``cpu_s`` and no pause record: the first two read None
there, the third reads its number and logs spans without CPU time.
"""
from __future__ import annotations

import functools
import os

from benchmark.lib import harness, startup_record, trace_reduce
from benchmark.lib.program_spans import (LEAVES, SPANS, contained, idle_gaps,
                                         merged, newest_xplane,
                                         overlap_seconds)

GC_SPAN = "host.gc"
#: the spans of a serving tick (the fit path's and the producer thread's
#: are not under ``router.step``)
TICK_SPANS = [n for n in SPANS if not n.startswith(("fit.", "io."))]
OUTSIDE = "outside router.step"


# -------------------------------------------------------------- the recording
@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime: float) -> dict:
    """``{"spans": {name: [(start, end, cpu_s | None)]}, "gc": [(start,
    end, generation, collected, thread)]}`` of the host plane, seconds on
    the trace's clock, sorted. ``host.gc`` is taken from ANY line: a pass
    on another thread stops the one that drives the device as well."""
    from jax.profiler import ProfileData

    spans, passes = {name: [] for name in SPANS}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != GC_SPAN and ev.name not in spans:
                    continue
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                stats = dict(ev.stats)
                if ev.name == GC_SPAN:
                    passes.append((start, end, stats.get("generation"),
                                   stats.get("collected"), line.name))
                else:
                    spans[ev.name].append((start, end, stats.get("cpu_s")))
    return {"spans": {n: sorted(v, key=lambda s: s[:2])
                      for n, v in spans.items()},
            "gc": sorted(passes, key=lambda p: p[:2])}


def recording(ctx):
    """The newest recording's spans and passes, or None where the run has
    no trace or the program entered no span."""
    trace = ctx.get("trace")
    if not trace or not trace.get("devices"):
        return None
    path = newest_xplane()
    if path is None:
        return None
    rec = _load(path, os.path.getmtime(path))
    return rec if any(rec["spans"].values()) else None


def pause_record():
    """The program's pause record, or None where it keeps none."""
    try:
        from paddle_tpu.observability import trace
    except ImportError:
        return None
    read = getattr(trace, "host_pauses", None)
    return read() if read is not None else None


# ----------------------------------------------------------------- arithmetic
def intersect(a, b):
    """The pieces of the sorted, disjoint intervals ``a`` that lie inside
    the sorted, disjoint intervals ``b``."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def by_generation(passes):
    """``{generation: [count, seconds]}`` of ``(t0, t1, generation, ...)``."""
    out = {}
    for t0, t1, generation, *_ in passes:
        row = out.setdefault(generation, [0, 0.0])
        row[0] += 1
        row[1] = round(row[1] + (t1 - t0), 6)
    return dict(sorted(out.items(), key=lambda kv: str(kv[0])))


def owner_of(gap, rec) -> dict:
    """The line that names a gap's owner: its seconds, the innermost span
    of the program open at its start with that span's wall and CPU seconds
    (``OUTSIDE`` where none is: the caller's own work between two ticks),
    and the seconds and generation of every collector pass under the gap."""
    g0, g1 = gap
    best = None
    for name in TICK_SPANS:
        for s, e, cpu in rec["spans"][name]:
            if s <= g0 < e and (best is None or s >= best[1]):
                best = (name, s, e, cpu)
    out = {"gap_s": round(g1 - g0, 6),
           "span": best[0] if best else OUTSIDE}
    if best:
        out["span_wall_s"] = round(best[2] - best[1], 6)
        out["span_cpu_s"] = None if best[3] is None else round(best[3], 6)
    out["gc"] = [[round(min(e, g1) - max(s, g0), 6), generation]
                 for s, e, generation, *_ in rec["gc"]
                 if min(e, g1) > max(s, g0)]
    return out


def longest(gaps, rec, n: int = 5) -> list:
    return [owner_of(g, rec)
            for g in sorted(gaps, key=lambda g: g[0] - g[1])[:n]]


# ----------------------------------------------------------- the three readers
def gc_pause_share_pct(ctx):
    """100 x the seconds of the pause record's entries that began in
    [READY, READY + ``window_s``] over ``window_s``."""
    if ctx["kind"] != "serve":
        return None
    pauses, rec = pause_record(), startup_record.record()
    ready = startup_record.cut_of(ctx, rec) if rec else None
    if pauses is None or ready is None:
        return None
    window_s = ctx["window"]["window_s"]
    inside = [e for e in pauses["entries"] if ready <= e[0] <= ready + window_s]
    seconds = sum(e[1] - e[0] for e in inside)
    top = sorted(inside, key=lambda e: e[0] - e[1])[:5]
    harness.log(
        f"gc_pause_share_pct: pause record {len(pauses['entries'])} entries "
        f"({pauses['dropped']} dropped), {len(inside)} began in the "
        f"{window_s:.2f} s after READY, {seconds:.4f} s; by generation "
        f"[count, seconds] {by_generation(inside)}; the five longest "
        "(seconds, generation, collected, seconds after READY) "
        f"{[(round(e[1] - e[0], 4), e[2], e[3], round(e[0] - ready, 3)) for e in top]}"
        f"; the counters, whole process: {_counters()}")
    return 100.0 * seconds / window_s


def _counters() -> dict:
    """``{generation: [passes, seconds]}`` of the program's two counters
    (every pass, the short young ones the record leaves out too)."""
    from paddle_tpu.observability import metrics
    snap = metrics.REGISTRY.snapshot()
    out = {}
    for col, name in enumerate(("paddle_tpu_host_gc_collections_total",
                                "paddle_tpu_host_gc_pause_seconds_total")):
        for s in snap.get(name, {}).get("series", []):
            out.setdefault(s["labels"][0], [0, 0.0])[col] = round(
                s["value"], 4)
    return out


def idle_in_gc_pct(ctx):
    """The share of the first chip's idle time in the traced window that
    overlaps a ``host.gc`` event of any thread."""
    rec = recording(ctx) if ctx["kind"] == "serve" else None
    if rec is None or not rec["gc"]:
        return None
    gaps = idle_gaps(ctx)
    idle = sum(e - s for s, e in gaps)
    if not idle:
        return None
    under = overlap_seconds(gaps, merged([p[:2] for p in rec["gc"]]))
    harness.log(
        f"idle_in_gc_pct: {len(rec['gc'])} host.gc events in the recording, "
        f"by generation [count, seconds] {by_generation(rec['gc'])}; idle "
        f"{idle:.4f} s of the window, {under:.4f} s of it under a pass; the "
        f"five longest idle gaps of the window: {longest(gaps, rec)}")
    return 100.0 * under / idle


def tick_idle_ms(ctx):
    """The first chip's idle milliseconds inside ``router.step``, per
    traced tick that launched at least one program and lies wholly inside
    the traced window."""
    rec = recording(ctx) if ctx["kind"] == "serve" else None
    if rec is None:
        return None
    spans = {n: [s[:2] for s in rec["spans"][n]] for n in TICK_SPANS}
    lo, hi = ctx["trace"]["t_lo"], ctx["trace"]["t_hi"]
    ticks = [t for t in spans["router.step"]
             if lo <= t[0] and t[1] <= hi
             and (contained(spans["serving.prefill"], t)
                  or contained(spans["serving.decode"], t))]
    if not ticks:
        return None
    gaps = idle_gaps(ctx)
    pieces = intersect(gaps, merged(ticks))
    idle = {}
    for name in TICK_SPANS:
        inside = [iv for t in ticks for iv in contained(spans[name], t)]
        idle[name] = 1e3 * overlap_seconds(gaps, merged(inside)) / len(ticks)
    # a parent's own work: its idle time less its children's
    split = {name: round(ms - sum(idle[c] for c in idle if SPANS[c] == name),
                         4)
             for name, ms in idle.items()}
    leaves = {n: v for n, v in split.items() if n in LEAVES}
    harness.log(
        f"tick_idle_ms over {len(ticks)} ticks that launched a program "
        f"({sum(1 for t in ticks if contained(spans['serving.prefill'], t))} "
        "with a chunk), idle ms a tick by span (a parent's: its own work): "
        f"{split}; the leaf with most: "
        f"{max(leaves, key=leaves.get) if leaves else None}")
    harness.log("tick_idle_ms, the five longest idle gaps inside those "
                f"ticks: {longest(pieces, rec)}")
    return 1e3 * sum(e - s for s, e in pieces) / len(ticks)
