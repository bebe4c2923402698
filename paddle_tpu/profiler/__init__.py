"""paddle.profiler — host+device profiling on the observability layer.

Capability parity with the reference profiler (reference:
python/paddle/profiler/profiler.py:79 — Profiler(targets, scheduler,
on_trace_ready), RecordEvent, make_scheduler, export_chrome_tracing; device
side backed by CUPTI fluid/platform/profiler/cuda_tracer.cc). TPU-native:
the device tracer is jax.profiler (XPlane/perfetto trace with XLA op and
TPU step timeline); the host side rides ``paddle_tpu.observability`` — the
dispatcher's op hook supplies per-op call counts AND host latency, the
span tracer collects compile/collective/autotune ranges from every
instrumented layer, and ``export_chrome_tracing`` merges them into one
chrome trace. ``timer_only`` mode reports step throughput (steps/sec,
examples/sec) without starting the device tracer.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from collections import defaultdict
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

from ..observability import metrics as _metrics
from ..observability import trace as _trace


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1          # accepted alias (reference parity)
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys(Enum):
    """Summary sort orders (reference profiler.SortedKeys subset — host
    timeline only; device time lives in the jax trace)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    Calls = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """reference profiler.py make_scheduler — step-phase state machine."""
    cycle = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


#: chrome-trace tid blocks per span category, so each instrumented layer
#: renders as its own named row in the viewer
_CAT_TID_BASE = {"user": 0, "dispatch": 100, "compile": 200,
                 "collective": 300, "autotune": 400,
                 # 500 is the unknown-category fallback lane; io/device
                 # get full 100-slot lanes so a process with many traced
                 # threads cannot bleed io spans into the device lane
                 "io": 600, "device": 700, "startup": 800}


def _trace_rank() -> Optional[int]:
    """This process's trainer rank — read from the launcher env, not
    the jax backend. None when not launched distributed (rank 0 of a
    real launch still reports 0, so its trace filename stays globbable
    alongside its peers')."""
    from ..observability.flight import env_rank
    return env_rank()


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback writing ONE merged chrome trace: user
    RecordEvent ranges + every span the observability tracer collected
    while recording (dispatch ops, to_static/SOT compiles, collectives,
    autotune probes). The jax device trace (perfetto) lands in the same
    dir.

    Distributed runs: the default filename carries the trainer rank
    (``worker_r1_host_ops.json``) and, when ``fleet.clock_sync`` has run
    in this process, a ``clock_sync`` metadata event embeds the rank's
    perf_counter offset vs rank 0 — ``tools/fleet_trace.py`` reads it to
    merge every rank's file onto one aligned timeline."""
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        rank = _trace_rank()
        # distributed launches (rank 0 included) get worker_rN so ONE
        # worker_r*_host_ops.json glob collects the whole fleet
        default_name = "worker" if rank is None else f"worker_r{rank}"
        rank = rank or 0
        fname = os.path.join(
            dir_name, f"{worker_name or default_name}_host_ops.json")
        events = []
        for name, t0, t1 in prof._events:
            events.append({"name": name, "cat": "user", "ph": "X",
                           "pid": 0, "tid": 0,
                           "ts": int(t0 * 1e6),
                           "dur": max(int((t1 - t0) * 1e6), 0)})
        # the start-up record first: one trace holds set-up and steady state
        startup = [e[:6] for e in _trace.startup_record()["entries"]]
        for name, cat, t0, t1, tid, args in startup + list(prof._spans):
            ev = {"name": name, "cat": cat, "ph": "X", "pid": 0,
                  "tid": _CAT_TID_BASE.get(cat, 500) + tid,
                  "ts": int(t0 * 1e6),
                  "dur": max(int((t1 - t0) * 1e6), 0)}
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        events.sort(key=lambda e: (e["ts"], e["tid"]))
        meta = [{"name": "process_name", "ph": "M", "pid": 0,
                 "args": {"name": "paddle_tpu host"
                          + (f" (rank {rank})" if rank else "")}}]
        try:
            from ..observability import fleet as _fleet
            cs = _fleet.clock_state()
        except Exception:
            cs = None
        if cs is not None:
            # self-describing alignment: the merger needs no side file
            meta.append({"name": "clock_sync", "ph": "M", "pid": 0,
                         "args": {
                             "rank": rank, "world": cs.get("world"),
                             "offset_vs_rank0_s":
                                 cs["offsets"].get(rank, 0.0),
                             "skew_bound_s": cs.get("skew_bound_s"),
                             "synced_at_perf_counter":
                                 cs.get("synced_at_perf_counter")}})
        else:
            meta.append({"name": "clock_sync", "ph": "M", "pid": 0,
                         "args": {"rank": rank,
                                  "offset_vs_rank0_s": None}})
        if prof._spans_dropped:
            # truncation marker: the buffer overflowed, the timeline is
            # incomplete — tooling must not read it as full coverage
            meta.append({"name": "spans_dropped", "ph": "M", "pid": 0,
                         "args": {"count": prof._spans_dropped}})
        meta += [{"name": "thread_name", "ph": "M", "pid": 0,
                  "tid": base, "args": {"name": cat}}
                 for cat, base in sorted(_CAT_TID_BASE.items(),
                                         key=lambda kv: kv[1])]
        with open(fname, "w") as f:
            json.dump({"traceEvents": meta + events}, f)
        prof.trace_path = fname
    return handler


class RecordEvent:
    """User-scoped range marker (reference profiler/utils.py RecordEvent).
    Shows in the host-op summary, the merged chrome trace, and, under an
    active jax trace, as a TraceAnnotation on the device timeline."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._jax_ctx = None
        self._t0 = None

    def begin(self):
        self._t0 = time.perf_counter()
        try:
            self._jax_ctx = jax.profiler.TraceAnnotation(self.name)
            self._jax_ctx.__enter__()
        except Exception:
            self._jax_ctx = None

    def end(self):
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(None, None, None)
        if self._t0 is None:
            return
        t1 = time.perf_counter()
        if _ACTIVE is not None:
            # the active profiler exports _events itself — adding to the
            # trace buffer too would render every user range twice
            _ACTIVE._events.append((self.name, self._t0, t1))
        else:
            _trace.add_complete(self.name, "user", self._t0, t1)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


_ACTIVE: Optional["Profiler"] = None

# Step-timer metrics (collection gated by FLAGS_enable_metrics)
_m_steps = _metrics.counter(
    "paddle_tpu_train_steps_total",
    "Profiler-observed training steps.")
_m_step_time = _metrics.histogram(
    "paddle_tpu_train_step_seconds", "Wall time per training step.")
_m_steps_per_s = _metrics.gauge(
    "paddle_tpu_steps_per_second",
    "Throughput of the most recent profiler-observed step.")
_m_examples_per_s = _metrics.gauge(
    "paddle_tpu_examples_per_second",
    "Examples/sec of the most recent step (step() called with "
    "num_samples).")


class Profiler:
    """reference profiler.py:79 Profiler. Usage::

        with profiler.Profiler(targets=[...], scheduler=(2, 5)) as p:
            for step, batch in enumerate(loader):
                train_step(batch)
                p.step(num_samples=batch_size)
        p.summary()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False):
        self.targets = list(targets or [ProfilerTarget.CPU,
                                        ProfilerTarget.TPU])
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                       record=end - start, repeat=1)
        self.scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._events = []                 # RecordEvent: (name, t0, t1)
        self._spans = []                  # harvested observability spans
        self._spans_dropped = 0
        self._op_stats = defaultdict(lambda: [0, 0.0, 0.0])  # n, total, max
        self._step_times = []
        self._step_samples = []
        self._step_t0 = None
        self._hook_handle = None
        self._device_trace_dir = None
        self._host_tracing = False
        self.trace_path = None

    # ---------------------------------------------------------------- hooks
    def _op_hook(self, op_name, inputs, outputs, attrs, duration=0.0):
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            st = self._op_stats[op_name]
            st[0] += 1
            st[1] += duration
            if duration > st[2]:
                st[2] = duration

    # ---------------------------------------------------------------- state
    def start(self):
        global _ACTIVE
        _ACTIVE = self
        # per-session hygiene: a restarted profiler must not report the
        # previous session's events/op stats/step timings
        self._events = []
        self._spans = []
        self._spans_dropped = 0
        self._op_stats = defaultdict(lambda: [0, 0.0, 0.0])
        self._step_times = []
        self._step_samples = []
        self._step = 0
        self._step_t0 = time.perf_counter()
        from ..core import dispatch
        if self._hook_handle is None:
            dispatch.register_op_hook(self._op_hook)
            self._hook_handle = self._op_hook
        self._transition(self.scheduler(self._step))
        return self

    def stop(self):
        global _ACTIVE
        self._transition(ProfilerState.CLOSED)
        if self._hook_handle is not None:
            from ..core import dispatch
            dispatch.unregister_op_hook(self._hook_handle)
            self._hook_handle = None
        _ACTIVE = None
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._step_t0 is not None:
            dt = now - self._step_t0
            self._step_times.append(dt)
            if num_samples:
                self._step_samples.append(num_samples)
            if _metrics.enabled() and dt > 0:
                _m_steps.inc()
                _m_step_time.observe(dt)
                _m_steps_per_s.set(1.0 / dt)
                if num_samples:
                    _m_examples_per_s.set(num_samples / dt)
        self._step_t0 = now
        self._step += 1
        self._transition(self.scheduler(self._step))

    def _transition(self, new_state: ProfilerState):
        was_rec = self._state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN)
        now_rec = new_state in (ProfilerState.RECORD,
                                ProfilerState.RECORD_AND_RETURN)
        if now_rec and not was_rec:
            # host span collection rides the same window as the device
            # trace; RecordEvent/_op_stats collection is hook-side
            if not self.timer_only:
                _trace.clear()
                _trace.activate()
                self._host_tracing = True
                self._device_trace_dir = os.environ.get(
                    "PADDLE_PROFILER_TRACE_DIR", "/tmp/paddle_tpu_trace")
                try:
                    jax.profiler.start_trace(self._device_trace_dir)
                except Exception:
                    self._device_trace_dir = None
        if was_rec and not now_rec:
            if self._host_tracing:
                _trace.deactivate()
                self._spans_dropped += _trace.dropped()
                self._spans.extend(_trace.drain())
                self._host_tracing = False
                if self._spans_dropped:
                    import warnings
                    warnings.warn(
                        f"profiler span buffer overflowed: "
                        f"{self._spans_dropped} span(s) dropped — the "
                        f"exported timeline is truncated (shorten the "
                        f"record window)")
            if self._device_trace_dir is not None:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._device_trace_dir = None
        self._state = new_state

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -------------------------------------------------------------- report
    def step_info(self, unit: Optional[str] = None) -> str:
        """Throughput line for timer_only mode (reference
        profiler/timer.py benchmark().step_info)."""
        if not self._step_times:
            return "no steps recorded"
        n = len(self._step_times)
        total = sum(self._step_times)
        avg = total / n
        ips = (1.0 / avg) if avg > 0 else 0.0
        out = (f"steps: {n} avg_step: {avg * 1e3:.3f} ms "
               f"steps/sec: {ips:.3f}")
        if self._step_samples and total > 0:
            # examples/sec from the num_samples the caller fed to step()
            out += (f" {unit or 'examples'}/sec: "
                    f"{sum(self._step_samples) / total:.3f}")
        elif unit:
            out += f" {unit}/sec: {ips:.3f}"
        return out

    @staticmethod
    def _sort_key(sorted_by):
        if sorted_by in (None, SortedKeys.CPUTotal, "time", "cpu_total"):
            return lambda kv: -kv[1][1]
        if sorted_by in (SortedKeys.Calls, "calls"):
            return lambda kv: -kv[1][0]
        if sorted_by in (SortedKeys.CPUAvg, "avg", "cpu_avg"):
            return lambda kv: -(kv[1][1] / kv[1][0] if kv[1][0] else 0.0)
        if sorted_by in (SortedKeys.CPUMax, "max", "cpu_max"):
            return lambda kv: -kv[1][2]
        raise ValueError(f"unsupported sorted_by {sorted_by!r}")

    def summary(self, sorted_by=None, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms"):
        """Print the host-op table (calls + real host latency from the
        dispatch hook) and, in timer_only mode, step throughput. Returns
        ``{op_name: calls}`` (stable reporting surface)."""
        scale = {"s": 1.0, "ms": 1e3, "us": 1e6}.get(time_unit, 1e3)
        rows = sorted(self._op_stats.items(), key=self._sort_key(sorted_by))
        line = "-" * 78
        print(line)
        print(f"{'op':<32}{'calls':>8}{'total(' + time_unit + ')':>14}"
              f"{'avg(' + time_unit + ')':>12}{'max(' + time_unit + ')':>12}")
        print(line)
        for name, (n, tot, mx) in rows[:40]:
            avg = tot / n if n else 0.0
            print(f"{name:<32}{n:>8}{tot * scale:>14.3f}"
                  f"{avg * scale:>12.3f}{mx * scale:>12.3f}")
        print(line)
        if self._step_times:
            print(self.step_info())
        if self._events:
            print("user ranges:")
            for name, t0, t1 in self._events[:20]:
                print(f"  {name}: {(t1 - t0) * 1e3:.3f} ms")
        return {name: n for name, (n, _tot, _mx) in rows}

    def op_stats(self) -> dict:
        """Raw per-op host stats: {op: {"calls", "total_s", "max_s"}}."""
        return {name: {"calls": n, "total_s": tot, "max_s": mx}
                for name, (n, tot, mx) in self._op_stats.items()}


@contextlib.contextmanager
def profile(**kwargs):
    p = Profiler(**kwargs)
    p.start()
    try:
        yield p
    finally:
        p.stop()


__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "SortedKeys", "make_scheduler", "export_chrome_tracing",
           "profile"]
