"""Device/place abstraction.

Reference keeps a Place hierarchy (paddle/phi/common/place.h) threaded through
kernel dispatch. On TPU the device story is JAX's: a flat list of addressable
devices plus meshes for SPMD. Place here is a light handle used by user-facing
APIs (``paddle.device.set_device`` style) that resolves to a jax.Device.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from ..observability import trace as _trace


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> jax.Device:
        """The jax device this place names. A place whose platform is
        absent is an error, never another platform's device: a TPUPlace
        silently resolved to a CPU device runs the job on the host under
        the chip's name."""
        _trace.note_backend(query=True)
        try:
            devs = jax.devices(self.device_type)
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r}: no {self.device_type!r} device in this "
                f"process (default backend "
                f"{jax.default_backend()!r})") from e
        return devs[self.device_id % len(devs)]


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"


class CustomPlace(Place):
    """Pluggable-device analog of the reference's CustomPlace (PJRT plugins)."""

    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


_current_place: Optional[Place] = None


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    _trace.note_backend(query=True)
    plat = jax.default_backend()
    if plat == "tpu":
        return TPUPlace(0)
    return CPUPlace(0)


def get_device() -> str:
    p = _current_place or _default_place()
    return f"{p.device_type}:{p.device_id}"


def set_device(device: str) -> Place:
    global _current_place
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("tpu", "gpu"):  # gpu alias maps to the accelerator
        _current_place = TPUPlace(idx)
    elif kind == "cpu":
        _current_place = CPUPlace(idx)
    else:
        _current_place = CustomPlace(kind, idx)
    return _current_place


def current_place() -> Place:
    return _current_place or _default_place()


def is_compiled_with_tpu() -> bool:
    _trace.note_backend(query=True)
    return any(d.platform.lower() == "tpu" for d in jax.devices())


def device_count() -> int:
    _trace.note_backend(query=True)
    return jax.device_count()
