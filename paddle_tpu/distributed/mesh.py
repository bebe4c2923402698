"""Global device mesh state.

TPU-native backbone of the distributed layer. The reference builds NCCL
communicators per topology axis (reference: python/paddle/distributed/fleet/
base/topology.py:178 HybridCommunicateGroup; paddle/fluid/distributed/
collective/process_group_nccl.cc). Here the topology IS a
``jax.sharding.Mesh``: each axis (dp/pp/sharding/sep/mp) is a mesh axis, a
"communication group" is a mesh axis name, and collectives are XLA ops over
those axes riding ICI/DCN — there are no communicator handles to manage.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import trace as _trace

_state = threading.local()
_global_mesh: Optional[Mesh] = None
_lock = threading.Lock()

# Canonical hybrid axis order (reference topology.py hybrid_group_names
# order: data, pipe, sharding, sep, model).
HYBRID_AXES = ("dp", "pp", "sharding", "sep", "mp")


def build_mesh(shape: Dict[str, int] | Sequence[int] = None,
               axis_names: Sequence[str] = None,
               devices=None) -> Mesh:
    """Create a Mesh over the available devices.

    ``shape`` maps axis name -> size (dict), or a plain size list with
    ``axis_names``. Defaults to a 1-axis 'dp' mesh over every device.
    """
    _trace.note_backend(query=True)
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if shape is None:
        shape, axis_names = [n], ["dp"]
    elif isinstance(shape, dict):
        axis_names = list(shape.keys())
        shape = list(shape.values())
    else:
        shape = list(shape)
        axis_names = list(axis_names)
    total = int(np.prod(shape))
    if total != n:
        raise ValueError(f"mesh shape {shape} needs {total} devices, "
                         f"have {n}")
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, tuple(axis_names))


def set_mesh(mesh: Mesh):
    global _global_mesh
    with _lock:
        _global_mesh = mesh
    return mesh


def get_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        with _lock:
            if _global_mesh is None:
                _global_mesh = build_mesh()
    return _global_mesh


def has_mesh() -> bool:
    return _global_mesh is not None


def axes_dividing(mesh: Mesh, n: int, names: Sequence[str]):
    """The axes among ``names`` that ``mesh`` spreads over more than one
    device, if together they divide ``n`` evenly (a dim of ``n`` can be cut
    over them); else None."""
    axes = tuple(a for a in names if mesh.shape.get(a, 1) > 1)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return axes if axes and n % size == 0 else None


def data_axes_dividing(batch: int):
    """``(mesh, its data axes)`` where a mesh is set, the caller is in no
    manual region yet and the axes' devices divide ``batch`` (an array of
    ``batch`` rows traced here is cut over them); else ``(None, None)``."""
    if has_mesh() and not jax.sharding.get_abstract_mesh().manual_axes:
        mesh = get_mesh()
        axes = axes_dividing(mesh, batch, ("dp", "sharding"))
        if axes:
            return mesh, axes
    return None, None


def axis_size(axis: str) -> int:
    mesh = get_mesh()
    return int(mesh.shape[axis]) if axis in mesh.shape else 1


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or get_mesh(), P())


def sharding_for(spec: P, mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or get_mesh(), spec)
