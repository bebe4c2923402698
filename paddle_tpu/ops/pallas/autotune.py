"""Measured per-shape/per-chip kernel autotuning with a persistent cache.

Capability parity with the reference's runtime autotune machinery
(reference: paddle/phi/kernels/autotune/cache.h — AlgorithmsCache keyed by
shape/dtype, paddle/phi/kernels/autotune/switch_autotune.cc — the
enable/disable switch and hit-rate bookkeeping). TPU-native: instead of
picking cuDNN algos, the search picks Pallas tile sizes. First sight of a
(kernel, shape-class, chip) key benchmarks a small candidate grid with the
real compiled kernel, caches the winner in memory AND on disk
(``$PADDLE_TPU_AUTOTUNE_CACHE``, else ``paddle_tpu/autotune.json`` under
``compile.cache.cache_root()`` — the checkout's ``.jax_cache`` or the
machine's ``$JAX_COMPILATION_CACHE_DIR``, never ``$HOME``), so later
processes on the same chip inherit the measurement instead of a
hand-tuned constant from a different chip generation.

A candidate that fails to compile or run is never dropped in silence:
it is counted, logged with the compiler's message and kept in
``AutotuneCache.failures``; if the caller's own default is among the
failures, or nothing survives, :func:`autotune` raises.

Shape classes bucket the sequence length to the next power of two —
close-by lengths share tiling behavior, so the cache stays small and a
fresh length does not re-benchmark.

The switch is the ``FLAGS_use_autotune`` flag (reference
switch_autotune.cc semantics; default on). When the flag is off or the
backend is not a real TPU (CPU tests run kernels through the Pallas
interpreter, where timing means nothing), callers fall back to their
static defaults.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ...core import flags
from ...observability import metrics as _metrics
from ...observability import trace as _trace

# flags use_autotune / autotune_attn_impl are defined in core/flags.py
# (readers like nn/functional/flash_attention must not depend on this
# module having been imported first)

# Autotune telemetry (gated by FLAGS_enable_metrics)
_m_at_cache = _metrics.counter(
    "paddle_tpu_autotune_cache_total",
    "Autotune winner-cache lookups: hit = cached winner served, miss = "
    "candidate grid measured.", labelnames=("event",))
_m_at_probe_time = _metrics.histogram(
    "paddle_tpu_autotune_measure_seconds",
    "Wall time of one full candidate-grid measurement (all probes).")
_m_at_winner = _metrics.gauge(
    "paddle_tpu_autotune_winner_seconds",
    "Median per-call latency of the winning candidate, per cache key.",
    labelnames=("key",))

__all__ = ["AutotuneCache", "autotune", "cache_path", "chip_kind",
           "seq_bucket", "should_autotune"]


def cache_path() -> str:
    p = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
    if p:
        return p
    from ...compile.cache import cache_root
    return os.path.join(cache_root(), "paddle_tpu", "autotune.json")


def chip_kind() -> str:
    """Device kind string of the default backend, cache-key safe."""
    import jax
    return str(jax.devices()[0].device_kind).replace(" ", "_")


def is_tpu_backend() -> bool:
    """True only for backends whose Pallas timings are meaningful tile
    probes. Positive test, not "not cpu": a GPU (or any other) backend
    must not run TPU tile probes and cache their winners."""
    import jax
    return jax.default_backend() == "tpu"


def should_autotune() -> bool:
    """Autotune only where measuring is meaningful: flag on + real chip
    (the Pallas interpreter's timings would tune for the interpreter)."""
    return bool(flags.get_flag("use_autotune")) and is_tpu_backend()


def probe_reps(flops_per_call: float, target_s: float = 0.08,
               assumed_tflops: float = 100.0) -> int:
    """How many times to chain a kernel inside one probe program so
    device time dominates the per-call dispatch overhead."""
    per_call_s = max(flops_per_call, 1.0) / (assumed_tflops * 1e12)
    return int(min(256, max(4, round(target_s / per_call_s))))


def seq_bucket(n: int) -> int:
    """Next power of two ≥ n (min 128): nearby lengths share tiling."""
    b = 128
    while b < n:
        b *= 2
    return b


#: bump when the measurement methodology or entry layout changes — every
#: entry stamped with an older schema is treated as absent and re-measured
#: (a winner tuned under old methodology must not survive the upgrade)
SCHEMA_VERSION = 2


class AutotuneCache:
    """Process-wide winner cache, mirrored to a JSON file.

    File writes are atomic (tmp + rename) and merged with any concurrent
    writer's content at save time (last writer wins per key) — several
    processes on one host converge instead of clobbering each other.

    Entries are stamped ``{"schema": SCHEMA_VERSION, "stamp": epoch_s,
    "value": winner}``; ``get`` unwraps the stamp and returns ``None``
    for entries from another schema (including pre-stamp bare values),
    so stale winners invalidate instead of silently persisting.
    """

    def __init__(self, path: Optional[str] = None):
        self._explicit_path = path
        self._lock = threading.Lock()
        self._mem: Dict[str, Any] = {}
        self._loaded = False
        #: key -> {candidate: error message} for every candidate that
        #: failed to compile or run in this process
        self.failures: Dict[str, Dict[str, str]] = {}

    @property
    def _path(self) -> str:
        # resolved at use: the process-wide instance is built at import
        return self._explicit_path or cache_path()

    # ------------------------------------------------------------- file io
    def _load_file(self) -> Dict[str, Any]:
        try:
            with open(self._path) as f:
                data = json.load(f)
            return data if isinstance(data, dict) else {}
        except (OSError, ValueError):
            return {}

    def _ensure_loaded(self):
        if not self._loaded:
            disk = self._load_file()
            disk.update(self._mem)  # in-memory results win
            self._mem = disk
            self._loaded = True

    def _save(self):
        try:
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            merged = self._load_file()
            merged.update(self._mem)
            tmp = f"{self._path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            os.replace(tmp, self._path)
        except OSError:
            pass  # cache persistence is best-effort

    # -------------------------------------------------------------- access
    def get(self, key: str):
        with self._lock:
            self._ensure_loaded()
            ent = self._mem.get(key)
        if isinstance(ent, dict) and "schema" in ent:
            if ent.get("schema") != SCHEMA_VERSION:
                return None  # stamped under another methodology: stale
            return ent.get("value")
        # pre-stamp bare value (or absent): treat as stale either way
        return None

    def put(self, key: str, value, persist: bool = True):
        with self._lock:
            self._ensure_loaded()
            self._mem[key] = {"schema": SCHEMA_VERSION,
                              "stamp": time.time(), "value": value}
            if persist:
                self._save()

    def clear_memory(self):
        """Forget in-process state (tests); disk is untouched."""
        with self._lock:
            self._mem = {}
            self._loaded = False


_cache = AutotuneCache()


def get_cache() -> AutotuneCache:
    return _cache


def make_key(kernel: str, **attrs) -> str:
    parts = [kernel, chip_kind()]
    parts += [f"{k}={attrs[k]}" for k in sorted(attrs)]
    return "|".join(parts)


def autotune(key: str,
             candidates: Sequence[Any],
             run: Callable[[Any, int], Any],
             default: Any,
             warmup: int = 2,
             iters: int = 5,
             describe: Optional[Callable[[Any], dict]] = None) -> Any:
    """Return the cached winner for ``key``, measuring on first sight.

    ``run(candidate, i)`` executes the kernel once with that candidate on
    the ``i``-th probe input and returns a JAX value. Callers must pass
    per-candidate JITTED closures over a few DISTINCT probe inputs —
    timing re-traced calls measures Python — and, when the caller may be
    under an outer trace, build those inputs inside
    ``jax.core.eval_context()`` so they are concrete. A candidate
    that fails to
    compile or run is counted, logged with its error and recorded in
    ``get_cache().failures[key]``; the survivors are still ranked. If
    ``default`` itself is among the failures, or no candidate survives,
    this raises ``RuntimeError``: the caller's fallback is known broken
    on this chip, and caching it would hide that from every later run.
    ``describe(candidate)`` says what a candidate makes the kernel do (the
    flash kernels' plan): logged beside each time, and the winner's
    stamped with it on the ``autotune:<key>`` span.
    """
    import jax

    cached = _cache.get(key)
    if cached is not None:
        if _metrics.enabled():
            _m_at_cache.inc(event="hit")
        # JSON round-trips tuples as lists
        return tuple(cached) if isinstance(cached, list) else cached

    if _metrics.enabled():
        _m_at_cache.inc(event="miss")
    log = logging.getLogger("paddle_tpu.autotune")
    measure_t0 = time.perf_counter()
    best, best_t = None, float("inf")
    timings: Dict[str, float] = {}
    failed: Dict[str, str] = {}
    # callers reach here while an outer jit is TRACING (the flash vjp
    # rules, the fused lowerings): outside an eval context the probe's
    # ops would be staged into that trace and the loop below would time
    # tracing, not the chip. (Not ensure_compile_time_eval: that also
    # constant-folds inside the Pallas kernel being traced, where
    # program_id has no eval rule.)
    span_args = {"candidates": len(candidates)}
    with _trace.span(f"autotune:{key}", "autotune", span_args), \
            jax.core.eval_context():
        for cand in candidates:
            try:
                for i in range(max(warmup, 1)):
                    jax.block_until_ready(run(cand, i))
                ts = []
                for i in range(iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(cand, warmup + i))
                    ts.append(time.perf_counter() - t0)
                ts.sort()
                dt = ts[len(ts) // 2]
            except Exception as e:
                # the compiler's refusal IS the datum: recorded and
                # reported, never dropped
                failed[str(cand)] = msg = f"{type(e).__name__}: {e}"
                log.warning("autotune %s: candidate %s failed: %s",
                            key, cand, msg[:2000])
                continue
            timings[str(cand)] = dt
            if dt < best_t:
                best, best_t = cand, dt
        if best is not None:
            span_args.update(winner=str(best), winner_ms=best_t * 1e3,
                             **(describe(best) if describe else {}))
    if failed:
        _cache.failures[key] = failed
    if _metrics.enabled():
        _m_at_probe_time.observe(time.perf_counter() - measure_t0)
        if best is not None:
            _m_at_winner.set(best_t, key=key)
    if flags.get_flag("log_level") >= 1:
        said = {str(c): describe(c) for c in candidates} if describe else {}
        ranked = ", ".join(
            f"{c}={t * 1e3:.3f}ms" + (f" {said[c]}" if c in said else "")
            for c, t in sorted(timings.items(), key=lambda kv: kv[1]))
        log.info("autotune %s: %s", key, ranked or "no candidate survived")
    if best is None or str(default) in failed:
        what = ("no candidate survived" if best is None
                else f"the caller's default {default!r} failed")
        raise RuntimeError(
            f"autotune {key}: {what} "
            f"({len(failed)}/{len(candidates)} candidates failed): "
            + "; ".join(f"{c}: {m[:500]}" for c, m in failed.items()))
    _cache.put(key, list(best) if isinstance(best, tuple) else best)
    return best
