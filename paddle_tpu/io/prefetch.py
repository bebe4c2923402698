"""Double-buffered device prefetch.

The round-12 step attribution shows synchronous input pipelines as
host+idle time at the top of every step: the consumer fetches a batch,
pays the host→device transfer, and only then dispatches compute. The
:class:`DevicePrefetcher` moves that work onto a background thread — it
pulls the NEXT batch from any iterator and issues its ``device_put``
(sharding-aware via a caller-supplied placement function) while the
current step computes, keeping up to ``depth`` batches in flight. jax
dispatch being async, the transfer overlaps device execution; the
consumer's ``next()`` becomes a queue pop.

This is the input half of the async runtime (the reference runs a
multi-stream actor runtime — ``fleet_executor`` — for the same reason);
``Engine.fit`` and ``hapi.Model.fit`` wrap their loaders in one by
default (``FLAGS_prefetch``).

Telemetry: ``paddle_tpu_prefetch_depth`` (configured depth),
``paddle_tpu_prefetch_hits_total`` (batch was already transferred when
the consumer asked), ``paddle_tpu_prefetch_stall_seconds_total`` (time
the consumer waited on the producer), and ``io.prefetch`` spans on the
producer thread — on the merged timeline they visibly overlap the
``device`` spans of the step (``tools/fleet_trace.py --overlap``).

Shutdown discipline: the producer thread and the WRAPPED iterator are
torn down together — explicitly via :meth:`close`/``with``, at iterator
exhaustion, and via ``weakref.finalize`` when the consumer abandons a
prefetching iterator mid-epoch. A wrapped multiprocess DataLoader
iterator propagates that teardown to its worker processes (no orphans).
"""
from __future__ import annotations

import queue as queue_mod
import threading
import time
import weakref
from typing import Callable, Iterator, Optional

from ..core import flags
from ..observability import goodput as _goodput
from ..observability import metrics as _metrics
from ..observability import trace as _trace

__all__ = ["DevicePrefetcher", "default_place_fn"]

_m_depth = _metrics.gauge(
    "paddle_tpu_prefetch_depth",
    "Configured DevicePrefetcher depth (batches kept in flight).")
_m_hits = _metrics.counter(
    "paddle_tpu_prefetch_hits_total",
    "Batches already transferred when the consumer asked (no wait).")
_m_stall = _metrics.counter(
    "paddle_tpu_prefetch_stall_seconds_total",
    "Seconds the consumer waited because the producer was behind.")

_DONE = object()


def default_place_fn(batch):
    """Default placement: move every array/Tensor leaf to the device
    (committed ``jnp.asarray``); structure is preserved. Callers with a
    mesh pass their own placement (e.g. the Engine's ``_shard_batch``)."""
    import jax.numpy as jnp
    import numpy as np

    from ..core.tensor import Tensor

    if isinstance(batch, Tensor):
        return Tensor(jnp.asarray(batch._data),
                      stop_gradient=batch.stop_gradient)
    if isinstance(batch, np.ndarray):
        return jnp.asarray(batch)
    if isinstance(batch, (list, tuple)):
        return type(batch)(default_place_fn(b) for b in batch)
    if isinstance(batch, dict):
        return {k: default_place_fn(v) for k, v in batch.items()}
    return batch


def _teardown_inner(it):
    """Propagate shutdown to the wrapped iterator: a multiprocess
    DataLoader iterator must reap its worker processes the moment the
    prefetcher dies, not at interpreter exit."""
    for name in ("close", "_teardown"):
        fn = getattr(it, name, None)
        if callable(fn):
            try:
                fn()
            except Exception:
                pass
            return


def _producer_loop(it, q, stop, place_fn):
    """Producer thread: fetch + place the next batch, park it in the
    bounded queue. Holds NO reference to the prefetcher object, so the
    consumer-side wrapper stays collectable (its finalize is the
    mid-epoch abandonment path)."""
    try:
        while not stop.is_set():
            try:
                with _trace.boundary("io.prefetch"):
                    batch = next(it)
                    placed = place_fn(batch)
            except StopIteration:
                _offer(q, (_DONE, None), stop)
                return
            except BaseException as e:  # surface in the consumer
                _offer(q, ("error", e), stop)
                return
            if not _offer(q, ("ok", placed), stop):
                return
    finally:
        if stop.is_set():
            # abandoned mid-epoch: reap the wrapped iterator from here —
            # the finalize thread already signalled and moved on
            _teardown_inner(it)


def _offer(q, item, stop) -> bool:
    """put() that never deadlocks shutdown: re-checks the stop event
    while the queue is full."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue_mod.Full:
            continue
    return False


def _shutdown(stop, thread, it):
    """finalize/close target (module-level: must not re-reference the
    prefetcher). Signals the producer, waits briefly, and guarantees the
    wrapped iterator's teardown even if the producer is parked."""
    stop.set()
    thread.join(timeout=5.0)
    _teardown_inner(it)


class DevicePrefetcher:
    """Wrap ``it`` so batches are fetched, placed, and transferred
    ``depth`` steps ahead of the consumer.

    ``place_fn(batch)`` runs on the producer thread and should return
    the device-resident (and, under a mesh, sharded) batch; defaults to
    :func:`default_place_fn`. ``depth`` defaults to
    ``FLAGS_prefetch_depth``.
    """

    def __init__(self, it: Iterator, depth: Optional[int] = None,
                 place_fn: Optional[Callable] = None):
        if depth is None:
            depth = int(flags.get_flag("prefetch_depth"))
        self.depth = max(1, int(depth))
        self._queue: queue_mod.Queue = queue_mod.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._done = False
        self.hits = 0
        self.stall_seconds = 0.0
        if _metrics.enabled():
            _m_depth.set(self.depth)
        inner = iter(it)
        self._thread = threading.Thread(
            target=_producer_loop,
            args=(inner, self._queue, self._stop,
                  place_fn or default_place_fn),
            name="paddle_tpu-prefetch", daemon=True)
        self._finalizer = weakref.finalize(
            self, _shutdown, self._stop, self._thread, inner)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        waited = False
        try:
            kind, payload = self._queue.get_nowait()
        except queue_mod.Empty:
            waited = True
            t0 = time.perf_counter()
            while True:
                try:
                    kind, payload = self._queue.get(timeout=1.0)
                    break
                except queue_mod.Empty:
                    # a closed prefetcher (or a dead producer that never
                    # parked a sentinel) must not hang the consumer
                    if self._stop.is_set() or not self._thread.is_alive():
                        self._done = True
                        raise StopIteration
            stalled = time.perf_counter() - t0
            self.stall_seconds += stalled
            if _metrics.enabled():
                _m_stall.inc(stalled)
            # input starvation is badput the data plane owns: bill the
            # stall window to the goodput ledger's data_stall bucket
            _goodput.bill_interval("data_stall", t0, t0 + stalled)
        if kind is _DONE:
            self._done = True
            self.close()
            raise StopIteration
        if kind == "error":
            self._done = True
            self.close()
            raise payload
        if not waited:
            # a hit = a real BATCH that was ready when asked — sentinels
            # must not inflate the documented hit-rate metric
            self.hits += 1
            if _metrics.enabled():
                _m_hits.inc()
        return payload

    def close(self):
        """Stop the producer and tear down the wrapped iterator
        (idempotent; also runs at GC / interpreter exit)."""
        self._finalizer()

    @property
    def closed(self) -> bool:
        return not self._finalizer.alive

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
