"""AOT serialization helpers — two tiers of reusable compiled programs.

Tier ``exec``: the PjRt executable itself, via
``jax.experimental.serialize_executable``. A hit skips trace, lower, AND
the XLA compile — the program starts running immediately (this is what
makes a warmed serving replica's first request a cache hit).

Tier ``stablehlo``: the ``jax.export`` serialization of the lowered
program. Used where the backend cannot serialize executables — a hit
still skips Python trace + StableHLO lowering and pays only the XLA
compile of the stored module.

Both deserialize paths are deliberately forgiving: version skew, platform
mismatch, or any other incompatibility returns ``None`` (a miss → the
caller recompiles). The CRC layer in :mod:`.cache` already filtered out
corruption, so failures here mean "not usable on this runtime", which is
a legitimate miss, not an error.

Donation: a serialized ``exec``-tier executable carries its input→output
buffer aliasing, so a deserialized donated program donates exactly like
the locally-compiled one — callers record ``donate`` in the entry meta
and fold it into the cache key (``to_static._pcc_key``) so donated and
undonated programs can never cross-hit; the ``stablehlo`` tier drops
aliasing on export (a hit is correct but pays the undonated memory).
"""
from __future__ import annotations

import pickle
from typing import Callable, Optional, Tuple

from ..observability import metrics as _metrics
from ..observability import trace as _trace

__all__ = ["serialize_compiled", "serialize_exported", "load_runner",
           "TIER_EXEC", "TIER_STABLEHLO"]

TIER_EXEC = "exec"
TIER_STABLEHLO = "stablehlo"

_m_deser_fail = _metrics.counter(
    "paddle_tpu_pcc_deserialize_incompatible_total",
    "Cache entries that decoded cleanly but could not be loaded on this "
    "runtime (version/platform skew) — treated as misses.",
    labelnames=("tier",))


def serialize_compiled(compiled) -> Optional[Tuple[str, bytes]]:
    """Serialize a ``jax.stages.Compiled``; None when the backend cannot
    (the caller falls back to :func:`serialize_exported`)."""
    try:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
        # the executable is loaded back over exactly the devices it was
        # compiled for (deserialize_and_load defaults to EVERY local
        # device, which mis-shards a 1-device program on a multi-device
        # host)
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
        return TIER_EXEC, pickle.dumps(
            (payload, in_tree, out_tree, device_ids), protocol=4)
    except Exception:
        return None


def serialize_exported(exported) -> Optional[Tuple[str, bytes]]:
    """Serialize a ``jax.export.Exported`` StableHLO program."""
    try:
        return TIER_STABLEHLO, bytes(exported.serialize())
    except Exception:
        return None


def load_runner(tier: str, payload: bytes) -> Optional[Callable]:
    """Rebuild a callable from a cache payload; None = unusable here.

    The returned callable takes exactly the dynamic (non-static)
    arguments the original function was compiled for.
    """
    if tier == TIER_EXEC:
        try:
            import jax
            from jax.experimental import serialize_executable as se
            with _trace.span("pcc_deserialize:exec", "compile"):
                blob, in_tree, out_tree, device_ids = pickle.loads(payload)
                by_id = {d.id: d for d in jax.devices()}
                return se.deserialize_and_load(
                    blob, in_tree, out_tree,
                    execution_devices=[by_id[i] for i in device_ids])
        except Exception:
            _m_deser_fail.inc(tier=TIER_EXEC)
            return None
    if tier == TIER_STABLEHLO:
        try:
            from jax import export as jax_export
            with _trace.span("pcc_deserialize:stablehlo", "compile"):
                exported = jax_export.deserialize(payload)
            return exported.call
        except Exception:
            _m_deser_fail.inc(tier=TIER_STABLEHLO)
            return None
    _m_deser_fail.inc(tier=tier or "unknown")
    return None
