"""Device profiler — timed sections, compiled-program analyses, and
step-time attribution.

Three instruments over the round-8 host telemetry:

* ``measure``/``timed_section`` — wall-clock device measurement with
  ``block_until_ready`` bracketing (jax dispatch is async: un-bracketed
  host timing measures enqueue cost, not execution). ``timed_section``
  additionally emits a ``device``-category span onto the trace timeline so
  the attribution pass can see where device execution actually sat.
* ``record_compiled`` — captures XLA ``cost_analysis()`` +
  ``memory_analysis()`` of every compiled program at ``to_static`` /
  SOT-flush compile time (gated by ``FLAGS_perf_capture``), keyed by
  site/label. This is the per-program modeled-cost table the roofline
  report joins against measured step time.
* ``attribute``/``step_attribution`` — decompose each step of a span
  timeline into compute / collective / host / idle. Categories are
  resolved by priority on a single host timeline (collective > device >
  host), idle is the uncovered remainder, so the four components sum to
  the measured step time *exactly*; the acceptance tolerance exists for
  timelines stitched from multiple clocks.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ...core import flags
from .. import metrics as _metrics
from .. import trace as _trace

__all__ = ["capture_enabled", "record_compiled", "compiled_programs",
           "clear_compiled", "measure", "timed_section", "attribute",
           "step_attribution", "memory_breakdown", "STEP_CAT",
           "DEVICE_CAT"]

# Hot mirror (same contract as metrics.enabled()).
_capture = {"on": bool(flags.get_flag("perf_capture"))}
flags.on_change("perf_capture",
                lambda v: _capture.__setitem__("on", bool(v)))


def capture_enabled() -> bool:
    return _capture["on"]


#: span categories the attribution pass keys on
DEVICE_CAT = "device"
STEP_CAT = "step"
#: host-side span categories (everything instrumented that is not device
#: execution or a collective). "io" is the prefetch/transfer lane — when
#: a DevicePrefetcher hides a transfer under a device span, the overlap
#: subtraction removes it from the host share (that's the win showing).
_HOST_CATS = ("dispatch", "compile", "user", "framework", "serving",
              "autotune", "io")

_m_perf_captures = _metrics.counter(
    "paddle_tpu_perf_captures_total",
    "Compiled-program cost/memory analyses captured, by site.",
    labelnames=("site",))

# --------------------------------------------------------------------------
# Compiled-program capture
# --------------------------------------------------------------------------
_MAX_PROGRAMS = 512
_programs: Dict[tuple, dict] = {}
_prog_lock = threading.Lock()


def record_compiled(site: str, label: str, compiled) -> Optional[dict]:
    """Capture cost/memory analysis of one compiled program (a
    ``jax.stages.Compiled``). Keyed by (site, label); repeated compiles of
    the same key bump ``n_captures`` and keep the latest analysis. Any
    backend that exposes no analysis records an empty entry (the capture
    event still counts). Never raises."""
    try:
        from .costmodel import xla_cost

        rec = {"site": site, "label": str(label), "n_captures": 1,
               "flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0,
               "argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
               "alias_bytes": 0, "generated_code_bytes": 0,
               "peak_bytes": 0}
        cost = xla_cost(compiled)
        if cost:
            rec.update(cost)
        mb = memory_breakdown(compiled)
        if mb is not None:
            rec.update(mb)
        key = (site, str(label))
        with _prog_lock:
            prev = _programs.get(key)
            if prev is not None:
                rec["n_captures"] = prev["n_captures"] + 1
            elif len(_programs) >= _MAX_PROGRAMS:
                _programs.pop(next(iter(_programs)))
            _programs[key] = rec
        _m_perf_captures.inc(site=site)
        return rec
    except Exception:
        return None


def memory_breakdown(compiled) -> Optional[dict]:
    """Alias-aware memory accounting of one compiled program — the ONE
    place the peak formula lives (``record_compiled`` reads it).
    Donated inputs alias outputs, so XLA reuses the argument HBM:
    ``peak = arg + out + temp − alias``.
    None when the backend exposes no analysis."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    out = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0) or 0),
        "generated_code_bytes": int(
            getattr(mem, "generated_code_size_in_bytes", 0)),
    }
    out["peak_bytes"] = max(
        0, out["argument_bytes"] + out["output_bytes"]
        + out["temp_bytes"] - out["alias_bytes"])
    return out


def compiled_programs(site: Optional[str] = None) -> List[dict]:
    """Captured program analyses (insertion order), optionally filtered
    by site ("to_static" / "sot" / explicit callers)."""
    with _prog_lock:
        out = [dict(r) for r in _programs.values()]
    if site is not None:
        out = [r for r in out if r["site"] == site]
    return out


def clear_compiled():
    with _prog_lock:
        _programs.clear()


def analyze(fn: Callable, *args) -> Optional[dict]:
    """Lower+compile ``fn`` over example arrays and capture its analysis
    under site "analyze" — the explicit cross-check entry the tests use
    (``costmodel`` vs ``xla_cost`` on the same program)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    label = getattr(fn, "__name__", repr(fn))
    return record_compiled("analyze", label, compiled)


# --------------------------------------------------------------------------
# block_until_ready-bracketed measurement
# --------------------------------------------------------------------------
def _block(x):
    import jax

    leaves = jax.tree_util.tree_leaves(x)
    for leaf in leaves:
        data = getattr(leaf, "_data", leaf)
        if hasattr(data, "block_until_ready"):
            data.block_until_ready()
    return x


def measure(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Seconds per call of ``fn(*args)`` with ``block_until_ready``
    bracketing: outstanding work is drained before the clock starts and
    the outputs are fully materialized before it stops."""
    out = None
    for _ in range(max(warmup, 0)):
        out = fn(*args)
    _block(out)
    t0 = time.perf_counter()
    for _ in range(max(iters, 1)):
        out = fn(*args)
    _block(out)
    return (time.perf_counter() - t0) / max(iters, 1)


class timed_section:
    """Scoped device-bracketed timing::

        with perf.device.timed_section("train_step") as ts:
            out = step(batch)
            ts.track(out)
    # ts.seconds = enter→(block_until_ready on tracked outputs) wall time

    Emits a ``device``-category span covering the block wait (the device
    execution window the attribution pass counts as compute) and a
    ``step``-category span covering the whole section when ``step=True``.
    """

    def __init__(self, name: str, step: bool = True):
        self.name = name
        self._step = step
        self._tracked: List = []
        self.seconds = 0.0
        self.device_seconds = 0.0

    def track(self, out):
        self._tracked.append(out)
        return out

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            tb0 = time.perf_counter()
            _block(self._tracked)
            t1 = time.perf_counter()
            self.seconds = t1 - self._t0
            self.device_seconds = t1 - tb0
            if _trace._active["on"]:
                _trace.add_complete(f"{self.name}.device", DEVICE_CAT,
                                    tb0, t1)
                if self._step:
                    _trace.add_complete(self.name, STEP_CAT, self._t0, t1)
        return False


# --------------------------------------------------------------------------
# Step-time attribution
# --------------------------------------------------------------------------
def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for a, b in intervals[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _covered(intervals, lo, hi) -> float:
    s = 0.0
    for a, b in intervals:
        s += max(0.0, min(b, hi) - max(a, lo))
    return s


def _subtract_cover(base: List[Tuple[float, float]],
                    cover: List[Tuple[float, float]]):
    """Portions of ``base`` not covered by ``cover`` (both merged)."""
    out = []
    for a, b in base:
        cur = a
        for c, d in cover:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, min(c, b)))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def attribute(spans: Sequence[tuple],
              steps: Optional[Sequence[Tuple[float, float]]] = None) -> dict:
    """Decompose step windows of a span timeline into compute /
    collective / host / idle seconds.

    ``spans`` are trace-buffer tuples ``(name, cat, t0, t1, tid, args)``.
    ``steps`` are (t0, t1) windows; when None they are taken from
    ``step``-category spans in the timeline. Overlaps resolve by priority
    collective > compute(device) > host; idle is the uncovered remainder,
    so per step: compute+collective+host+idle == t1−t0 exactly.

    Returns ``{"steps": [per-step dicts], "total": aggregate dict}``.
    """
    coll, dev, host = [], [], []
    step_windows = list(steps) if steps is not None else []
    for name, cat, t0, t1, _tid, _args in spans:
        if t1 <= t0:
            continue
        if cat == STEP_CAT and steps is None:
            step_windows.append((t0, t1))
        elif cat == "collective":
            coll.append((t0, t1))
        elif cat == DEVICE_CAT:
            dev.append((t0, t1))
        elif cat in _HOST_CATS:
            host.append((t0, t1))
    coll, dev, host = _merge(coll), _merge(dev), _merge(host)
    # priority: a device wait that contains a collective counts as
    # collective for the contained part; host spans yield to both
    dev_x = _subtract_cover(dev, coll)
    host_x = _subtract_cover(_subtract_cover(host, coll), dev)
    per_step = []
    for t0, t1 in sorted(step_windows):
        total = t1 - t0
        c = _covered(coll, t0, t1)
        d = _covered(dev_x, t0, t1)
        h = _covered(host_x, t0, t1)
        idle = max(0.0, total - c - d - h)
        per_step.append({
            "step_s": total, "compute_s": d, "collective_s": c,
            "host_s": h, "idle_s": idle,
            "compute_frac": d / total if total else 0.0,
            "collective_frac": c / total if total else 0.0,
            "host_frac": h / total if total else 0.0,
            "idle_frac": idle / total if total else 0.0,
        })
    total = {k: sum(s[k] for s in per_step)
             for k in ("step_s", "compute_s", "collective_s", "host_s",
                       "idle_s")}
    st = total["step_s"]
    for k in ("compute", "collective", "host", "idle"):
        total[f"{k}_frac"] = (total[f"{k}_s"] / st) if st else 0.0
    total["n_steps"] = len(per_step)
    return {"steps": per_step, "total": total}


def step_attribution(step_fn: Callable, iters: int = 2, warmup: int = 1,
                     name: str = "step") -> dict:
    """Run ``step_fn()`` ``iters`` times under an exclusive trace window
    with device bracketing and return ``attribute()``'s aggregate. The
    helper owns the span buffer for its duration — do not call inside an
    active profiler recording (the drained spans would vanish from the
    profiler's export)."""
    was_active = _trace.active()
    for _ in range(max(warmup, 0)):
        _block(step_fn())
    if not was_active:
        _trace.clear()
        _trace.activate()
    t_begin = time.perf_counter()
    try:
        for _ in range(max(iters, 1)):
            with timed_section(name) as ts:
                ts.track(step_fn())
    finally:
        if not was_active:
            _trace.deactivate()
    # inside someone else's recording window, read without draining so
    # the profiler's export still sees every span — but attribute ONLY
    # the spans of THIS call's window (earlier step spans in the buffer
    # would inflate n_steps and skew every fraction)
    spans = (_trace.tail(_trace.MAX_EVENTS) if was_active
             else _trace.drain())
    spans = [s for s in spans if s[2] >= t_begin]
    out = attribute(spans)
    out["total"]["name"] = name
    return out
