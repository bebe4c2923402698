"""Stateful RNG facade over TPU counter-based PRNG.

The reference uses per-device mutable Philox generators (reference:
paddle/phi/core/generator.h). TPU-native randomness is functional
(threefry/rbg keys), so this module presents a *stateful facade*: a global
Generator holds a base key and a monotonically increasing counter; every
consumer folds the counter into the base key, giving reproducible streams
from ``paddle.seed`` while remaining pure under jit (callers inside captured
programs must thread keys explicitly — see paddle_tpu.jit).

TP/PP "seed trees" (reference python/paddle/distributed/fleet/meta_parallel/
parallel_layers/random.py) are derived by folding the axis name+index into
the base key — see paddle_tpu.distributed.random.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import numpy as np

from ..observability import trace as _trace


class Generator:
    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        with getattr(self, "_lock", threading.Lock()):
            self._seed = int(seed) & 0xFFFFFFFFFFFFFFFF
            self._key = jax.random.key(self._seed)
            self._counter = 0
        return self

    def seed(self) -> int:
        return self._seed

    def get_state(self):
        return (self._seed, self._counter)

    def set_state(self, state):
        seed, counter = state
        self.manual_seed(seed)
        self._counter = int(counter)

    def next_key(self):
        """Return a fresh PRNG key; advances the stream."""
        with self._lock:
            c = self._counter
            self._counter += 1
        return jax.random.fold_in(self._key, c)

    def split(self, n: int):
        return jax.random.split(self.next_key(), n)


_default_generator: Optional[Generator] = None


def default_generator() -> Generator:
    global _default_generator
    if _default_generator is None:
        _default_generator = Generator(np.random.randint(0, 2**31 - 1))
    return _default_generator


def seed(s: int) -> Generator:
    """paddle.seed — reset the global stream."""
    global _default_generator
    _trace.note_backend(query=True)
    _default_generator = Generator(s)
    return _default_generator


def next_key():
    return default_generator().next_key()


def get_rng_state():
    return [default_generator().get_state()]


def set_rng_state(state):
    default_generator().set_state(state[0])
