"""What every driver shares: finding a cell's files by name, the compile
clock, the device report, the profiler window and the result line."""
from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")

#: seconds of the steady window a ``--trace 1`` run records
TRACE_SECONDS = 4.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """Everything a cell is made of, found by the names in BENCHMARK.json."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    reports = lambda m: workload in m.get(  # noqa: E731
        "workloads", [w["name"] for w in bench["workloads"]])
    return {
        "bench": bench, "cell": cell,
        "config": load_json(ROOT, config["file"]),
        "traffic": load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def load_driver(kind: str):
    return importlib.import_module(f"benchmark.drivers.{kind}")


def read_layer_metric(name: str, ctx: dict):
    """One small reader per per-layer metric, found by the metric's name. A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    module = importlib.import_module(
        "benchmark.layer_metrics." + name.replace("-", "_").replace(".", "_"))
    return module.read(ctx)


# ------------------------------------------------------------------ device
def setup_jax_cache() -> str:
    """JAX's persistent compilation cache at a FIXED path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), and every
    program in it however short its compile was."""
    import jax
    from paddle_tpu.compile.cache import enable_jax_cache

    root = enable_jax_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return root


def require_chips(chips: int) -> list:
    """The devices the cell runs on, or ``NoChip``. Never a CPU fallback."""
    import jax

    if jax.default_backend() != "tpu":
        raise NoChip(f"no TPU: jax.default_backend() is "
                     f"{jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def device_report(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache loads
    included) and how many there were."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.total, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration
            self.count += 1


class FreezeWatch:
    """A thread that sleeps 20 ms at a time and notes when it woke much
    later than that: seconds in which the whole process (or its machine)
    stood still, as against a main thread that waits for the device while
    other threads run. Logged, never part of a metric."""

    def __init__(self, threshold_s: float = 0.25):
        import threading
        self.threshold_s, self.freezes = threshold_s, []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-freeze-watch")

    def _run(self):
        last = time.perf_counter()
        while not self._stop.wait(0.02):
            now = time.perf_counter()
            if now - last > self.threshold_s:
                self.freezes.append(round(now - last, 3))
            last = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5)
        return False


# ---------------------------------------------------------------- profiler
class TraceWindow:
    """A ``jax.profiler`` recording of part of the steady window. ``start``
    and ``stop`` may be called from different threads."""

    def __init__(self, directory: str):
        self.directory = directory
        self.t_start = self.t_stop = None

    def start(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self):
        from benchmark.lib import trace_reduce
        if self.t_start is None or self.t_stop is None:
            return None
        path = trace_reduce.find_xplane(self.directory)
        return trace_reduce.reduce(path) if path else None


def trace_dir(workload: str) -> str:
    return os.path.join(ROOT, ".bench_out", "traces", workload)


# ------------------------------------------------------------- result line
def result_line(*, correct, attempted, failed, metrics, device,
                breakdown=None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    return json.dumps(line)


_T0 = time.perf_counter()


def log(*a):
    print(f"[bench {time.perf_counter() - _T0:7.2f}s]", *a, file=sys.stderr,
          flush=True)
