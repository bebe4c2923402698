"""Fleet-wide telemetry plane — cross-rank aggregation, clock alignment,
straggler detection.

Rounds 8/12 made every *process* observable (metrics registry, span
tracer, step attribution); round 13 made 4-process SPMD training a tier-1
reality. This module closes the gap between the two: telemetry that spans
the fleet, shaped after the reference's multi-rank failure-diagnosis
subsystem (paddle/phi/core/distributed/comm_task_manager.cc +
fleet-executor, PAPER.md §fleet-executor):

* :func:`snapshot` / :func:`dump` — gather every rank's metrics snapshot,
  span tail, flight-recorder tail and replica health to every rank (rank
  0 persists) over the cross-process object collectives
  (``all_gather_object`` riding the gloo/ICI tensor transport);
* :func:`clock_sync` — barrier-based monotonic-clock offset handshake:
  after a barrier all ranks sample ``perf_counter`` at (approximately)
  the same true instant; the median offset over several rounds aligns
  per-rank trace timelines to rank 0 (``tools/fleet_trace.py`` consumes
  it; accuracy is bounded by barrier exit skew — µs on ICI, ~ms on the
  CPU gloo transport);
* :class:`FleetBeacon` — a cheap per-step beacon (wall time + the
  round-12 compute/collective/host/idle split from one traced probe step
  per window) all-gathered every ``window`` steps as ONE fixed-shape
  tensor collective, reduced into skew statistics:
  ``paddle_tpu_fleet_straggler_score{rank=}``, slowest-rank /
  step-skew gauges, and a once-per-window stderr warning naming the
  straggler and its dominant attribution bucket. The ``fleet.slow_step``
  fault point makes the detector drillable deterministically. The same
  windowed gather also folds each rank's live goodput fraction into the
  row, so ``paddle_tpu_goodput_job_fraction`` (min over ranks) is a live
  job-level number, not a post-mortem merge.

Un-instrumented host time (a sleeping or swapping rank) shows up in the
``idle`` bucket — attribution covers what the spans cover.

Also here: :func:`merge_snapshots` — fold the per-process
``PADDLE_TPU_METRICS_DUMP`` files (``.rankN`` / ``.pidN`` suffixes) into
one rank-labeled aggregate (``python -m paddle_tpu.observability
--merge``), and the replica registry serving snapshots include.
"""
from __future__ import annotations

import os
import re
import sys
import time
import weakref
from typing import Dict, List, Optional

import numpy as np

from ..core import flags
from ..fault import inject as _inject
from . import flight as _flight
from . import metrics as _metrics
from . import trace as _trace
from .metrics import REGISTRY

__all__ = ["snapshot", "local_snapshot", "dump", "clock_sync",
           "clock_state", "FleetBeacon", "beacon", "reset_beacon",
           "skew_stats", "BUCKETS", "merge_snapshots",
           "merge_snapshot_files", "register_replica", "replica_health"]

flags.define_flag(
    "fleet_beacon", True,
    "Per-step fleet beacon: step wall time + attribution split, "
    "all-gathered every PADDLE_TPU_BEACON_WINDOW steps into straggler "
    "statistics. Near-free per step; one fixed-shape collective per "
    "window when running multi-process.")

_enabled = {"on": bool(flags.get_flag("fleet_beacon"))}
flags.on_change("fleet_beacon",
                lambda v: _enabled.__setitem__("on", bool(v)))


def _rank_world():
    import jax
    try:
        return jax.process_index(), jax.process_count()
    except Exception:
        return 0, 1


# --------------------------------------------------------------------------
# Instruments (stable names — README "Fleet observability")
# --------------------------------------------------------------------------
_m_straggler = _metrics.gauge(
    "paddle_tpu_fleet_straggler_score",
    "Per-rank relative step-time excess over the fleet median, from the "
    "last beacon window ((mean_rank - median) / median).",
    labelnames=("rank",))
_m_slowest = _metrics.gauge(
    "paddle_tpu_fleet_slowest_rank",
    "Rank with the highest mean step time in the last beacon window.")
_m_skew = _metrics.gauge(
    "paddle_tpu_fleet_step_skew",
    "Relative step-time spread across ranks in the last beacon window "
    "((max - min) / median).")
_m_windows = _metrics.counter(
    "paddle_tpu_fleet_beacon_windows_total",
    "Beacon windows flushed (each = one cross-rank gather when "
    "multi-process).")
_m_warnings = _metrics.counter(
    "paddle_tpu_fleet_straggler_warnings_total",
    "Beacon windows whose slowest rank exceeded the straggler "
    "threshold.")
_m_gather_s = _metrics.histogram(
    "paddle_tpu_fleet_beacon_gather_seconds",
    "Wall time of the per-window beacon all-gather (the beacon's only "
    "collective cost).")
_m_clock_off = _metrics.gauge(
    "paddle_tpu_fleet_clock_offset_seconds",
    "Per-rank perf_counter offset vs rank 0 from the last clock_sync "
    "handshake.", labelnames=("rank",))
_m_goodput_job = _metrics.gauge(
    "paddle_tpu_goodput_job_fraction",
    "Job-level goodput: MINIMUM live goodput fraction over all ranks in "
    "the last beacon window (the job is only as productive as its worst "
    "rank).")


# --------------------------------------------------------------------------
# Clock alignment
# --------------------------------------------------------------------------
_CLOCK: Dict[str, Optional[dict]] = {"state": None}


def clock_state() -> Optional[dict]:
    """Result of the last :func:`clock_sync` in this process (None if it
    never ran)."""
    return _CLOCK["state"]


def clock_sync(rounds: int = 5, group=None) -> dict:
    """Barrier-based clock-offset handshake.

    Each round: a barrier, then every rank samples ``perf_counter``
    (the monotonic clock the span tracer stamps with) immediately on
    exit — all ranks sample at approximately
    the same true instant, so ``t_r - t_0`` estimates rank r's clock
    offset vs rank 0; the median over ``rounds`` suppresses exit-skew
    noise, and the residual spread is reported as the alignment error
    bound. Every rank receives the full offset table (the handshake ends
    in an object all-gather).
    """
    from ..distributed.communication import collective as C

    rank, world = _rank_world()
    samples = []
    for _ in range(max(int(rounds), 1)):
        C.barrier(group)
        samples.append(time.perf_counter())
    # the fleet plane is per-PROCESS: virtual in-process "ranks" share
    # one clock, so a single-process run has exactly one offset row
    if world > 1:
        rows: List = []
        C.all_gather_object(rows, samples, group)
    else:
        rows = [samples]
    n = len(samples)
    offsets, residual = {}, 0.0
    for r in range(len(rows)):
        diffs = sorted(rows[r][k] - rows[0][k] for k in range(n))
        off = diffs[n // 2]
        offsets[r] = off
        residual = max(residual,
                       max(abs(d - off) for d in diffs))
    state = {"world": len(rows), "rank": rank, "rounds": n,
             "offsets": offsets, "skew_bound_s": residual,
             "synced_at_perf_counter": time.perf_counter(),
             "synced_at_unix": time.time()}
    _CLOCK["state"] = state
    if _metrics.enabled():
        for r, off in offsets.items():
            _m_clock_off.set(off, rank=r)
    return state


# --------------------------------------------------------------------------
# Replica registry (serving tier)
# --------------------------------------------------------------------------
_replicas: "weakref.WeakSet" = weakref.WeakSet()


def register_replica(replica) -> None:
    """Register a serving replica (anything with ``health() -> dict``)
    for inclusion in fleet snapshots — a multi-replica router polls ONE
    endpoint instead of one per engine. Weakly held: a dropped engine
    unregisters itself."""
    _replicas.add(replica)


def replica_health() -> List[dict]:
    out = []
    for r in list(_replicas):
        try:
            out.append(r.health())
        except Exception as e:          # a dying replica must not take
            out.append({"error": repr(e)})  # the telemetry plane with it
    return out


# --------------------------------------------------------------------------
# Cross-rank snapshot
# --------------------------------------------------------------------------
def _truncate_timelines(timelines, max_timelines: int,
                        max_events: int):
    """Newest ``max_timelines`` live timelines, each keeping its FIRST
    event (submitted — the anchor segment math needs) plus the newest
    ``max_events - 1``; truncation is marked so consumers don't mistake
    a clipped timeline for a complete one."""
    out = []
    for tl in timelines[-max_timelines:]:
        evs = tl.get("events", [])
        if len(evs) > max_events:
            tl = dict(tl)
            tl["events"] = [evs[0]] + evs[-(max_events - 1):]
            tl["truncated"] = len(evs) - max_events
        out.append(tl)
    return out


def local_snapshot(trace_tail: int = 200, reqtrace_tail: int = 20) -> dict:
    """This rank's contribution: metrics snapshot, span tail, flight
    tail, request-timeline tail, beacon report, replica health, clock
    state."""
    import socket

    from . import goodput as _goodput
    from . import reqtrace as _reqtrace
    from . import sentinel as _sentinel

    rank, world = _rank_world()
    b = _beacon["b"]
    return {
        "rank": rank, "world": world, "pid": os.getpid(),
        "host": socket.gethostname(),
        "perf_counter": time.perf_counter(), "unix_time": time.time(),
        "metrics": REGISTRY.snapshot(),
        "spans": [[name, cat, t0, t1, tid, args]
                  for name, cat, t0, t1, tid, args
                  in _trace.tail(trace_tail)],
        "flight": _flight.RECORDER.tail(50),
        # newest terminal request timelines + whatever is mid-flight:
        # the per-rank evidence the planned one-engine-per-host serving
        # deployment needs to debug a request after the fact. Live
        # timelines are capped like the tail AND event-truncated — a
        # host mid-way through long generations must not ship MBs of
        # decode_tick events through the cross-rank gather
        "reqtrace": (_reqtrace.RECORDER.tail(reqtrace_tail)
                     + _truncate_timelines(
                         _reqtrace.RECORDER.live_timelines(),
                         max_timelines=reqtrace_tail,
                         max_events=100)),
        "beacon": (b.last_report if b is not None else None),
        "replicas": replica_health(),
        "clock": clock_state(),
        # job health plane: the rank's goodput account + incident tail,
        # so fleet.snapshot() carries the job-level (min-over-ranks)
        # goodput evidence in one gather
        "goodput": _goodput.ledger().snapshot(),
        "sentinel": _sentinel.get().snapshot(),
    }


def snapshot(trace_tail: int = 200, group=None) -> dict:
    """Gather every rank's :func:`local_snapshot` (all ranks receive the
    aggregate; in-process 'ranks' share one process, so world is 1).
    This is a COLLECTIVE — every rank must call it at the same point."""
    local = local_snapshot(trace_tail)
    if local["world"] > 1:
        from ..distributed.communication import collective as C
        ranks: List[dict] = []
        C.all_gather_object(ranks, local, group)
    else:
        # per-PROCESS aggregation: in-process virtual ranks share this
        # snapshot, so one row covers them all
        ranks = [local]
    return {"format": "paddle_tpu.fleet_snapshot/1",
            "world": len(ranks), "rank": local["rank"],
            "clock": clock_state(), "ranks": ranks}


def dump(path: str, trace_tail: int = 200, group=None) -> Optional[str]:
    """Collective snapshot; rank 0 persists it as JSON and returns the
    path (other ranks return None)."""
    import json

    snap = snapshot(trace_tail=trace_tail, group=group)
    if snap["rank"] != 0:
        return None
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(snap, f, default=str)
    os.replace(tmp, path)
    return path


# --------------------------------------------------------------------------
# Straggler detection
# --------------------------------------------------------------------------
#: attribution buckets, in beacon-row order (columns 4..7)
BUCKETS = ("compute", "collective", "host", "idle")

#: relative step-time excess past which the slowest rank is named
STRAGGLER_THRESHOLD = float(
    os.environ.get("PADDLE_TPU_STRAGGLER_THRESHOLD", "0.2"))


def skew_stats(matrix, threshold: float = None) -> dict:
    """Reduce a gathered beacon matrix into skew statistics.

    ``matrix`` rows are ``[rank, steps, mean_step_s, max_step_s,
    compute_frac, collective_frac, host_frac, idle_frac]`` (one per
    rank; ndarray or nested lists), optionally extended with a 9th
    column: the rank's live goodput fraction (−1 when its ledger is
    cold) — the job-level goodput is the MINIMUM over ranks that
    reported one. Pure function — unit-testable
    without processes. Plain-Python math on purpose: rows are
    fleet-sized (≤ dozens) and this runs cache-cold inside training
    loops, where numpy's dispatch machinery alone would dominate."""
    threshold = STRAGGLER_THRESHOLD if threshold is None else threshold
    rows = [[float(v) for v in r] for r in matrix]
    means = [r[2] for r in rows]
    srt = sorted(means)
    n = len(srt)
    med = (srt[n // 2] if n % 2 else
           0.5 * (srt[n // 2 - 1] + srt[n // 2]))
    scores = ([(m - med) / med for m in means] if med > 0
              else [0.0] * n)
    i = max(range(n), key=lambda k: means[k])
    buckets = rows[i][4:8]
    dominant = BUCKETS[max(range(4), key=lambda k: buckets[k])]
    fracs = [r[8] for r in rows if len(r) > 8 and r[8] >= 0.0]
    return {
        "job_goodput_fraction": (min(fracs) if fracs else None),
        "median_step_s": med,
        "scores": {int(rows[r][0]): scores[r] for r in range(n)},
        "slowest_rank": int(rows[i][0]),
        "slowest_score": scores[i],
        "slowest_mean_step_s": means[i],
        "dominant_bucket": dominant,
        "skew": (srt[-1] - srt[0]) / med if med > 0 else 0.0,
        "is_straggler": scores[i] > threshold,
    }


class FleetBeacon:
    """Per-step beacon + per-window cross-rank skew reduction.

    Two integration styles:

    * bracketed — ``step_begin()`` / ``step_end()`` around each training
      step (``Engine.fit``);
    * boundary — ``tick()`` once per step at a fixed point in the loop
      (the fleet trainers' ``optimizer.step()``); the inter-tick wall
      time is the step time, profiler-timer style.

    The last step of every window is the **probe**: the span tracer is
    activated for just that step (unless a profiler already owns it, in
    which case spans are read without draining) and the round-12
    ``perf.attribute`` decomposition yields this rank's
    compute/collective/host/idle split. At the window boundary every rank
    contributes one fixed-shape float32 row to a cached compiled
    all-gather; :func:`skew_stats` turns the matrix into the straggler
    verdict on every rank. All ranks must run the same window size —
    the gather is a collective.
    """

    def __init__(self, window: Optional[int] = None, group=None):
        self.window = max(int(window if window is not None else
                              os.environ.get("PADDLE_TPU_BEACON_WINDOW",
                                             "16")), 2)
        self._wm1 = self.window - 1       # probe-step index, hot path
        self.group = group
        self.windows = 0
        self.last_report: Optional[dict] = None
        self.first_flagged_window: Optional[int] = None
        self._t0 = None
        self._t_last = None
        self._own_trace = False
        self._reset_window()

    def _reset_window(self):
        self._n = 0
        self._sum = 0.0
        self._max = 0.0
        self._attr = (0.0, 0.0, 0.0, 1.0)    # un-probed: all idle

    # ------------------------------------------------------------ feeding
    # The hot path is deliberately flat: on a non-probe step,
    # step_begin/step_end execute a handful of bytecodes each — in a real
    # training loop these run cache-cold, so every avoided function call
    # is measurable.
    def _probe_next(self) -> bool:
        return self._n == self._wm1

    def _arm_probe(self):
        if self._n == self._wm1 and not _trace._active["on"]:
            _trace.clear()
            _trace.activate()
            self._own_trace = True

    def _slow_step_drill(self):
        p = _inject.fire("fleet.slow_step")
        if p is not None:
            time.sleep(float(p.get("seconds", 0.05)))

    def step_begin(self):
        if not _enabled["on"]:
            return
        if self._n == self._wm1:
            self._arm_probe()
        if _inject._armed:
            self._t0 = time.perf_counter()
            self._slow_step_drill()
            return
        self._t0 = time.perf_counter()

    def step_end(self):
        # _observe's fast path, inlined: this runs cache-cold once per
        # training step and an extra Python call is ~half its budget
        t0 = self._t0
        if t0 is None or not _enabled["on"]:
            return
        t1 = time.perf_counter()
        self._t0 = None
        dt = t1 - t0
        if dt < 0.0:
            dt = 0.0
        self._sum += dt
        if dt > self._max:
            self._max = dt
        n = self._n
        if n == self._wm1:
            self._probe_attribution(t0, t1)
            self._n = self.window
            self._flush()
            self._reset_window()
        else:
            self._n = n + 1

    def tick(self):
        """Step-boundary marker for loops that can't bracket: wall time
        between consecutive ticks is one step."""
        if not _enabled["on"]:
            return
        now = time.perf_counter()
        if self._t_last is not None:
            self._observe(self._t_last, now)
        if self._n == self._wm1:
            self._arm_probe()
        self._t_last = time.perf_counter()
        if _inject._armed:
            self._slow_step_drill()

    # ----------------------------------------------------------- internals
    def _observe(self, t0: float, t1: float):
        dt = t1 - t0
        if dt < 0.0:
            dt = 0.0
        self._sum += dt
        if dt > self._max:
            self._max = dt
        n = self._n
        if n == self._wm1:
            self._probe_attribution(t0, t1)
            self._n = self.window     # this step completed the window
            self._flush()
            self._reset_window()
        else:
            self._n = n + 1

    def _probe_attribution(self, t0: float, t1: float):
        from .perf import device as _perf_device

        if self._own_trace:
            _trace.deactivate()
            spans = _trace.drain()
            self._own_trace = False
        elif _trace.active():
            # a profiler owns the buffer: read without draining so its
            # export still sees every span
            spans = _trace.tail(_trace.MAX_EVENTS)
        else:
            return
        try:
            tot = _perf_device.attribute(spans, steps=[(t0, t1)])["total"]
            self._attr = (tot["compute_frac"], tot["collective_frac"],
                          tot["host_frac"], tot["idle_frac"])
            from . import goodput as _goodput
            _goodput.ledger().note_attribution(*self._attr)
        except Exception:
            pass                      # a beacon must never fail the step

    def _flush(self):
        rank, world = _rank_world()
        mean = self._sum / max(self._n, 1)
        # col 8: this rank's live goodput fraction (−1 = ledger cold);
        # one snapshot per window, amortised against the gather it rides
        gp = -1.0
        try:
            from . import goodput as _goodput
            led = _goodput.ledger()
            if led.running():
                gp = float(led.snapshot()["goodput_fraction"])
        except Exception:
            pass
        row = [float(rank), float(self._n), mean, self._max,
               *self._attr, gp]
        if world > 1:
            from ..distributed.communication import collective as C
            tg0 = time.perf_counter()
            try:
                matrix = C.gather_rows(
                    np.asarray(row, np.float32)).tolist()
            except Exception as e:
                # telemetry must not kill training — fall back to a
                # local-only row, but LOUDLY: peers that completed this
                # window's transport saw our row; peers blocked in it
                # will hang and the (flight-recorded) gather names this
                # rank in the watchdog's cross-rank diff
                matrix = [row]
                sys.stderr.write(
                    f"[fleet] rank {rank}: beacon gather failed "
                    f"(window {self.windows + 1}): {e!r} — reporting "
                    f"local-only stats for this window\n")
            if _metrics.enabled():
                _m_gather_s.observe(time.perf_counter() - tg0)
        else:
            matrix = [row]            # no collective in a 1-process run
        self.windows += 1
        stats = skew_stats(matrix)
        stats["window"] = self.windows
        stats["per_rank"] = matrix
        self.last_report = stats
        try:
            from . import goodput as _goodput
            from . import sentinel as _sentinel
            _goodput.ledger().note_skew(
                int(self._n), mean, stats["median_step_s"])
            _sentinel.get().note_straggler(
                stats.get("slowest_rank"), bool(stats["is_straggler"]),
                skew=float(stats.get("skew", 0.0)))
        except Exception:
            pass                      # telemetry must not kill training
        if _metrics.enabled():
            _m_windows.inc()
            for r, s in stats["scores"].items():
                _m_straggler.set(s, rank=r)
            _m_slowest.set(stats["slowest_rank"])
            _m_skew.set(stats["skew"])
            if stats.get("job_goodput_fraction") is not None:
                _m_goodput_job.set(stats["job_goodput_fraction"])
        if stats["is_straggler"]:
            if self.first_flagged_window is None:
                self.first_flagged_window = self.windows
            if _metrics.enabled():
                _m_warnings.inc()
            sys.stderr.write(
                f"[fleet] straggler: rank {stats['slowest_rank']} is "
                f"{stats['slowest_score'] * 100:.0f}% over the fleet "
                f"median step time "
                f"({stats['slowest_mean_step_s'] * 1e3:.1f} ms vs "
                f"{stats['median_step_s'] * 1e3:.1f} ms median), "
                f"dominant bucket: {stats['dominant_bucket']} "
                f"(beacon window {self.windows})\n")


_beacon: Dict[str, Optional[FleetBeacon]] = {"b": None}


def beacon() -> FleetBeacon:
    """Process-wide beacon singleton (window from
    ``PADDLE_TPU_BEACON_WINDOW``, default 16)."""
    if _beacon["b"] is None:
        _beacon["b"] = FleetBeacon()
    return _beacon["b"]


def reset_beacon(window: Optional[int] = None) -> FleetBeacon:
    """Replace the singleton (tests / window changes)."""
    _beacon["b"] = FleetBeacon(window=window)
    return _beacon["b"]


# --------------------------------------------------------------------------
# Metrics-dump merging (the .rankN / .pidN fold)
# --------------------------------------------------------------------------
def merge_snapshots(snaps: Dict[str, dict]) -> dict:
    """Fold per-process metric snapshots into ONE snapshot whose series
    carry a leading ``rank`` label (``proc`` when the metric already has
    its own ``rank`` label — the fleet gauges do — so the rendered
    Prometheus never repeats a label name). Histograms keep per-process
    series (the label separates them; no cross-rank bucket summing, so
    nothing is lost). The result renders through
    ``metrics.render_prometheus`` unchanged."""
    out: dict = {}
    for label in sorted(snaps, key=lambda k: (len(str(k)), str(k))):
        snap = snaps[label]
        for name in sorted(snap):
            m = snap[name]
            inner = list(m.get("labelnames", []))
            e = out.setdefault(name, {
                "kind": m.get("kind", "untyped"),
                "help": m.get("help", ""),
                "labelnames": [("proc" if "rank" in inner else "rank")]
                + inner,
                "series": [],
            })
            if "buckets" in m and "buckets" not in e:
                e["buckets"] = list(m["buckets"])
            for s in m.get("series", []):
                e["series"].append({
                    "labels": [str(label)] + [str(v)
                                              for v in s.get("labels", [])],
                    "value": s.get("value"),
                })
    return out


def _suffix_label(base: str, path: str) -> str:
    suf = path[len(base):].lstrip(".")
    if not suf:
        return "0"                   # the primary keeps the bare path
    m = re.fullmatch(r"rank(\d+)", suf)
    if m:
        return m.group(1)
    m = re.fullmatch(r"rank(\d+)\.(pid\d+)", suf)
    if m:
        return f"{m.group(1)}.{m.group(2)}"
    return suf                       # pidN / explicit METRICS_SUFFIX


def merge_snapshot_files(base: str) -> dict:
    """Fold ``base`` + every ``base.<suffix>`` snapshot file written by
    ``PADDLE_TPU_METRICS_DUMP`` (rank>0 → ``.rankN``, workers →
    ``.pidN``) into one rank-labeled aggregate. Unreadable files are
    skipped with a stderr note (a half-written dump from a crashed rank
    must not block the merge of the healthy ones)."""
    import glob
    import json

    paths = ([base] if os.path.exists(base) else []) + \
        sorted(glob.glob(base + ".*"))
    snaps: Dict[str, dict] = {}
    for p in paths:
        if ".tmp." in os.path.basename(p):
            continue
        try:
            with open(p) as f:
                snaps[_suffix_label(base, p)] = json.load(f)
        except (OSError, ValueError) as e:
            sys.stderr.write(f"[fleet] skipping unreadable snapshot "
                             f"{p!r}: {e}\n")
    if not snaps:
        raise FileNotFoundError(
            f"no metric snapshot files found at {base!r} (or {base}.*)")
    return merge_snapshots(snaps)
