"""LazyGuard — deferred parameter initialization.

Reference: ``python/paddle/nn/initializer/lazy_init.py`` (``LazyGuard``
context: layers constructed under it record their initializers instead
of running them; materialization happens later — the big-model workflow
where per-shard init must wait for placement decisions).

TPU-native: a lazy Parameter carries a ``jax.ShapeDtypeStruct`` payload
(shape/dtype inspection works, compute does not — identical contract to
the reference's unallocated tensor) plus its recorded initializer.
Materialization is automatic at the layer's first forward, or explicit
via ``materialize_layer`` (which a sharded-init path can call per shard
after choosing placements).
"""
from __future__ import annotations

__all__ = ["LazyGuard", "in_lazy_mode", "materialize_layer",
           "materialize_parameter"]

import weakref

from ..observability import trace as _trace

#: lazy params awaiting materialization — id-keyed weak refs (a WeakSet
#: would trip over Tensor's elementwise __eq__), so an abandoned
#: LazyGuard model stops taxing every Layer.__call__ once it's GC'd
_STATE = {"on": False}
_PENDING: dict = {}


class LazyGuard:
    """Context manager: defer parameter initialization inside."""

    def __enter__(self):
        _trace.note_backend()
        _STATE["on"] = True
        return self

    def __exit__(self, *exc):
        _STATE["on"] = False
        return False


def in_lazy_mode() -> bool:
    return _STATE["on"]


def _register(param, init, shape, dtype) -> None:
    param._lazy_init = (init, tuple(shape), dtype)
    key = id(param)
    _PENDING[key] = weakref.ref(
        param, lambda _ref, _k=key: _PENDING.pop(_k, None))


def has_outstanding() -> bool:
    return bool(_PENDING)


def materialize_parameter(param) -> bool:
    """Run the recorded initializer; True if this call materialized."""
    lazy = getattr(param, "_lazy_init", None)
    if lazy is None:
        return False
    init, shape, dtype = lazy
    param._swap_payload(init(shape, dtype))
    del param._lazy_init
    _PENDING.pop(id(param), None)
    return True


def materialize_layer(layer) -> int:
    """Materialize every lazy parameter under ``layer``; returns count."""
    n = 0
    for p in layer.parameters():
        if p is not None and materialize_parameter(p):
            n += 1
    return n
