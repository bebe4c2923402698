"""Global flag registry.

Capability parity with the reference's gflags-style system (reference:
paddle/common/flags.cc — PHI_DEFINE_EXPORTED_* definitions; Python surface
paddle.get_flags / paddle.set_flags). Flags are defined in Python, can be
seeded from FLAGS_* environment variables, and are queried by subsystems
(allocator stats, nan/inf checks, collective timeouts, ...).
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional


@dataclass
class _Flag:
    name: str
    default: Any
    help: str
    type: type
    value: Any = None


_registry: Dict[str, _Flag] = {}
_lock = threading.Lock()
_observers: Dict[str, Callable[[Any], None]] = {}


def _coerce(ty: type, raw):
    if ty is bool:
        if isinstance(raw, str):
            return raw.lower() in ("1", "true", "yes", "on")
        return bool(raw)
    return ty(raw)


def define_flag(name: str, default, help: str = "", type: Optional[type] = None):
    """Register a flag. Env var FLAGS_<name> overrides the default."""
    ty = type if type is not None else (default.__class__ if default is not None else str)
    with _lock:
        if name in _registry:
            return _registry[name].value
        env = os.environ.get("FLAGS_" + name)
        value = _coerce(ty, env) if env is not None else default
        _registry[name] = _Flag(name, default, help, ty, value)
        return value


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    out = {}
    with _lock:
        for n in names:
            key = n[6:] if n.startswith("FLAGS_") else n
            if key not in _registry:
                raise KeyError(f"Flag {n!r} is not defined")
            out[n] = _registry[key].value
    return out


def get_flag(name: str):
    # lock-free fast path (dict reads are GIL-atomic); the eager dispatch
    # hot loop reads flags per op, so this must stay at dict-lookup cost
    f = _registry.get(name[6:] if name.startswith("FLAGS_") else name)
    if f is None:
        raise KeyError(f"Flag {name!r} is not defined")
    return f.value


def set_flags(flags: Dict[str, Any]):
    with _lock:
        for n, v in flags.items():
            key = n[6:] if n.startswith("FLAGS_") else n
            if key not in _registry:
                raise KeyError(f"Flag {n!r} is not defined")
            f = _registry[key]
            f.value = _coerce(f.type, v)
            for obs in _observers.get(key, ()):
                obs(f.value)


def on_change(name: str, fn: Callable[[Any], None]):
    # multiple subscribers per flag: dispatch's hot mirror AND any user
    # tap must both see every set_flags
    _observers.setdefault(name, []).append(fn)


def all_flags() -> Iterable[str]:
    return list(_registry)


# ---------------------------------------------------------------------------
# Core flag definitions (subset mirroring reference paddle/common/flags.cc).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf after every op.")
define_flag("check_nan_inf_level", 0, "0: error on NaN/Inf; >0: log only.")
define_flag("benchmark", False, "Synchronize after each op for benchmarking.")
define_flag("paddle_num_threads", 1, "Host threads for compute.")
define_flag("allocator_strategy", "auto_growth", "Allocator strategy facade (XLA owns HBM).")
define_flag("eager_delete_tensor_gb", 0.0, "GC threshold facade.")
define_flag("distributed_timeout_ms", 30 * 60 * 1000, "Collective watchdog timeout.")
define_flag("stop_check_timeout", -1, "Seconds before a hung collective aborts the job.")
define_flag("tpu_matmul_precision", "default", "default|high|highest matmul precision.")
define_flag("use_pallas_kernels", True, "Use Pallas TPU kernels for hot ops when available.")
define_flag("flash_min_seq_len", 1024,
            "Shortest sequence routed to the Pallas flash-attention kernel; "
            "below it XLA's fused dense attention is faster (measured on "
            "v5e, BERT-base S=512: 117.2k tok/s XLA vs 114.2k Pallas — the "
            "blocked online-softmax only pays once the attention matrix "
            "stops fitting comfortably).")
define_flag("use_autotune", True,
            "Measure-and-cache kernel tile sizes per shape/chip "
            "(reference FLAGS_use_autotune).")
define_flag("autotune_attn_impl", False,
            "Also autotune the attention ALGORITHM (XLA dense vs Pallas "
            "flash) per shape class. Opt-in: one noisy probe can "
            "flip a model to the slow path wholesale; tile "
            "tuning has bounded downside, algorithm selection does not.")
define_flag("eager_jit_cache", True, "Run steady-state eager ops through cached compiled lowerings.")
define_flag("log_level", 0, "VLOG-style verbosity for framework logging.")
define_flag("cudnn_deterministic", False, "Determinism facade (XLA is deterministic by default).")
define_flag("max_inplace_grad_add", 0, "Grad accumulation chunking facade.")
# Persistent compilation cache (paddle_tpu/compile/) — registered here so
# set_flags works before the compile package is first imported.
define_flag("compile_cache", False,
            "Enable the persistent on-disk compilation cache.")
define_flag("compile_cache_dir", "",
            "Cache directory; empty = $PADDLE_TPU_COMPILE_CACHE_DIR or "
            "<cache_root>/paddle_tpu/pcc (compile.cache.cache_root).")
define_flag("compile_cache_size_mb", 512,
            "LRU size budget for the persistent compilation cache (MB).")
define_flag("compile_cache_manifest", "",
            "Shape-signature manifest (JSONL) recording path for AOT "
            "warmup; empty = off.")
# Graph fusion pass (paddle_tpu/compile/fusion/) — registered here so
# set_flags works before the fusion package is first imported. Default
# OFF: with the flag clear, every compile path is bit-exact with the
# unfused seed behavior (tests/test_fusion.py pins this).
define_flag("enable_fusion", False,
            "Rewrite matched subgraphs (norm->linear->act, residual+norm, "
            "bias+act, rope+projection) onto fused ops in the compile "
            "paths (to_static/SOT/Engine/static.Program).")
# Program verifier (paddle_tpu/static/verifier.py) — static contract /
# collective-desync / sharding / donation-hazard checks over the op-list
# IR, run once per new compile signature in every compile path.
define_flag("verify_programs", "warn",
            "Pre-compile program verification mode: 'warn' (default) "
            "reports findings as ProgramVerifierWarning, 'strict' "
            "raises ProgramVerifierError naming the op + source line "
            "before XLA sees the program, 'off' disables.",
            type=str)
# Performance attribution (paddle_tpu/observability/perf/) — registered
# here so the dispatch hot-path mirror can read them at import time.
define_flag("perf_capture", False,
            "Capture XLA cost_analysis()/memory_analysis() of compiled "
            "programs (to_static signatures, SOT segments) into the perf "
            "registry for roofline reporting.")
define_flag("perf_op_cost", False,
            "Accumulate the analytical cost model's per-op FLOPs/bytes "
            "into paddle_tpu_perf_op_* metrics at eager dispatch "
            "(requires FLAGS_enable_metrics).")
# Async runtime (io/prefetch.py + decomposed sharded-optimizer gathers) —
# registered here so set_flags works before the io/compile packages
# first import.
define_flag("prefetch", True,
            "Double-buffered device prefetch in Engine.fit / "
            "hapi.Model.fit: the next batch's host fetch + device_put "
            "runs on a background thread while the current step "
            "computes (io.DevicePrefetcher).")
define_flag("prefetch_depth", 2,
            "Batches the DevicePrefetcher keeps in flight ahead of the "
            "consumer (>=1; 2 = classic double buffering).")
define_flag("sharding_gather_group_mb", 16,
            "Byte budget (MB) of one decomposed all-gather group in the "
            "ZeRO stage-2/3 parameter re-gather: params are gathered in "
            "layer-order groups issued back-to-back so gather(k+1) "
            "overlaps compute/installation of group k.")
