"""paddle_tpu.compile — persistent compilation cache + AOT warmup.

Every process that traces, lowers, and XLA-compiles the same program is
wasting the fleet's time: compile wall time dominates cold start (the
round-8 profiler numbers), and the work is identical across replicas.
This package amortizes it (cf. JAX/XLA AOT export and Pathways' fleet-
wide compilation reuse):

- :mod:`.cache` — content-addressed on-disk entries, CRC-verified,
  atomically published, LRU-bounded by ``FLAGS_compile_cache_size_mb``;
  corrupt entries are quarantined and silently recompiled.
- :mod:`.aot` — two entry tiers: serialized PjRt executables (hit skips
  trace+lower+XLA compile) with a serialized-StableHLO fallback where
  executable serialization is unavailable (hit still skips trace+lower).
- :mod:`.fingerprint` — keys over program content + jax/jaxlib versions
  + backend/topology + lowering FLAGS.
- :mod:`.warmup` — shape-signature manifest recording plus
  ``python -m paddle_tpu.compile warm <manifest>`` to precompile every
  recorded signature before traffic arrives.

Wired into the three compile paths: ``jit.to_static`` dispatch, SOT
segment flushes, and loaded inference artifacts (``jit.load`` /
``inference.Predictor``). Enable with ``FLAGS_compile_cache=1`` (cache
directory: ``FLAGS_compile_cache_dir``, ``$PADDLE_TPU_COMPILE_CACHE_DIR``
or ``<cache_root>/paddle_tpu/pcc`` — see :func:`.cache.cache_root`).
"""
from __future__ import annotations

from .cache import (CompileCache, cache_dir, cache_root, enable_jax_cache,
                    enabled, get_cache, record_time_saved)
from .fingerprint import (aval_sig, blob_digest, code_fingerprint,
                          env_fingerprint, key_of)
from .warmup import (manifest_path, read_manifest, record_artifact,
                     record_to_static, warm)
from . import aot

__all__ = [
    "CompileCache", "get_cache", "enabled", "cache_dir", "cache_root",
    "enable_jax_cache", "record_time_saved", "key_of", "env_fingerprint",
    "aval_sig",
    "blob_digest", "code_fingerprint", "warm", "record_to_static",
    "record_artifact",
    "manifest_path", "read_manifest", "aot",
]
