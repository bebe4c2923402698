"""paddle_tpu.observability — framework-wide telemetry.

Two always-compiled-out-when-disabled primitives:

- :mod:`.metrics` — a registry of labeled counters/gauges/histograms
  (``FLAGS_enable_metrics`` gates collection at dict-lookup cost) with
  Prometheus text + JSON export. Instrumented subsystems: eager dispatch
  (per-op host latency, eager-jit cache), to_static/SOT (compiles,
  retraces, graph breaks, segment cache), pallas autotune (cache hit/miss,
  winner timings), distributed collectives (calls, bytes, latency), the
  profiler step timer (steps/sec, examples/sec), and a live device-memory
  callback gauge.
- :mod:`.trace` — a span buffer active while a ``profiler.Profiler``
  session records; ``export_chrome_tracing`` merges spans from all layers
  into one chrome trace.

CLI: ``python -m paddle_tpu.observability`` (or ``tools/metrics_dump.py``)
prints the Prometheus/JSON snapshot of the current process or of a file
written via ``PADDLE_TPU_METRICS_DUMP=/path FLAGS_enable_metrics=1``.
"""
from __future__ import annotations

import os

from . import metrics, trace
from . import flight  # noqa: F401  (registers the flight-record exit dump)
from . import reqtrace  # noqa: F401  (registers the reqtrace exit dump)
from . import goodput  # noqa: F401  (registers the goodput exit dump)
from . import sentinel  # noqa: F401  (anomaly sentinel singleton)
from .metrics import (REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
                      enabled, render_prometheus)

__all__ = ["metrics", "trace", "flight", "reqtrace", "goodput", "sentinel",
           "REGISTRY", "MetricsRegistry",
           "Counter", "Gauge", "Histogram", "enabled", "render_prometheus",
           "device_live_bytes", "snapshot", "to_prometheus"]

# .fleet (cross-rank plane) stays a plain submodule — it pulls in the
# distributed collective layer, which must not load at package import.

snapshot = REGISTRY.snapshot
to_prometheus = REGISTRY.to_prometheus


def device_live_bytes() -> float:
    """Bytes held by live device arrays (jax.live_arrays) — evaluated at
    snapshot/export time only, never on the hot path."""
    try:
        import jax
        return float(sum(int(getattr(a, "nbytes", 0) or 0)
                         for a in jax.live_arrays()))
    except Exception:
        return 0.0


metrics.gauge(
    "paddle_tpu_device_live_bytes",
    "Bytes referenced by live device arrays (jax.live_arrays), read at "
    "snapshot time.").set_function(device_live_bytes)


def _startup_seconds() -> dict:
    summary = trace.startup_summary()
    return {(key[:-2],): v for key, v in summary.items()
            if key.endswith("_s") and v is not None}


metrics.gauge(
    "paddle_tpu_startup_seconds",
    "The start-up record (observability.trace.startup_summary), read at "
    "snapshot time whatever FLAGS_enable_metrics was during set-up: "
    "seconds from the OS's start of the process to its first ready mark "
    "(phase=ready) and of each phase before it (import, backend, build, "
    "warmup, fit_setup, trace_lower, compile).",
    labelnames=("phase",)).set_function(_startup_seconds)
metrics.gauge(
    "paddle_tpu_startup_cache_misses",
    "Programs compiled before the first ready mark, under a phase of the "
    "program's own, that JAX's persistent compilation cache did not "
    "hold.").set_function(
        lambda: trace.startup_summary()["cache_misses"])


# The pid that first imported this module owns the bare dump path; it is
# published through the ENVIRONMENT so both fork- and spawn-started
# children (which re-import the module and would otherwise see their own
# pid as the installer) recognize they are not the primary process.
_PRIMARY_PID_ENV = "PADDLE_TPU_METRICS_PRIMARY_PID"
os.environ.setdefault(_PRIMARY_PID_ENV, str(os.getpid()))


def _dump_path(path: str) -> str:
    """Process-unique dump path: multi-process runs (distributed workers,
    fork/spawn dataloader workers) each get their own file instead of
    last-writer-wins on one. The primary process keeps ``path`` verbatim
    (back-compat with the README workflow); an explicit
    ``PADDLE_TPU_METRICS_SUFFIX`` always wins."""
    suffix = os.environ.get("PADDLE_TPU_METRICS_SUFFIX")
    if suffix is not None:
        return f"{path}.{suffix}"
    parts = []
    for var in ("PADDLE_TRAINER_ID", "RANK"):
        v = os.environ.get(var)
        if v is not None and v.strip().isdigit() and int(v) > 0:
            parts.append(f"rank{int(v)}")
            break
    if os.environ.get(_PRIMARY_PID_ENV) != str(os.getpid()):
        # non-primary process (fork/spawn worker): pid disambiguates
        # even under an inherited rank env — rank N's dataloader workers
        # must not clobber rank N's own file
        parts.append(f"pid{os.getpid()}")
    return ".".join([path] + parts)


def _install_exit_dump():
    """PADDLE_TPU_METRICS_DUMP=/path: write the JSON snapshot at process
    exit so `python -m paddle_tpu.observability --input /path` can render
    it offline. The path gains a process-unique suffix (.rankN / .pidN)
    in non-primary processes — see _dump_path."""
    path = os.environ.get("PADDLE_TPU_METRICS_DUMP")
    if not path:
        return

    import atexit
    import json

    def _dump():
        try:
            # attributed HBM census rides into the snapshot's gauges
            from .perf import memory as _perf_memory
            _perf_memory.refresh_metrics()
        except Exception:
            pass
        try:
            # live serving replicas move their device-side counters
            # (expert load) into the registry when their health is read
            from . import fleet as _fleet
            _fleet.replica_health()
        except Exception:
            pass
        try:
            with open(_dump_path(path), "w") as f:
                json.dump(REGISTRY.snapshot(), f, indent=1, sort_keys=True)
        except OSError:
            pass

    atexit.register(_dump)


_install_exit_dump()
