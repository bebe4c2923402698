"""Collective operations.

Reference surface: python/paddle/distributed/communication/ (all_reduce.py,
all_gather.py, reduce_scatter.py, all_to_all.py, broadcast.py, scatter.py,
reduce.py) over ProcessGroupNCCL. TPU-native: every collective is a cached
one-op compiled program — ``shard_map`` over the group's mesh axes with the
matching ``jax.lax`` collective (psum/all_gather/psum_scatter/all_to_all/
ppermute) — so eager collectives and in-graph collectives are the same code
riding ICI (SURVEY.md §5 'Distributed communication backend').

Rank semantics under single-controller SPMD: "rank i's tensor" is shard i of
a distributed array. A replicated input behaves as every rank holding the
same value.
"""
from __future__ import annotations

import functools
import math
import os as _os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from ..shard_map_compat import shard_map as _shard_map_compat


def shard_map(f, mesh, in_specs, out_specs):
    """shard_map with the static replication checker off — collective
    outputs (all_gather/broadcast) are replicated in ways the checker can't
    infer."""
    return _shard_map_compat(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check=False)

import time as _time

from ...core import dispatch
from ...core.tensor import Tensor, as_tensor
from ...fault import inject as _inject
from ...fault.retry import RetryPolicy, retry as _retry
# arms the collective-timeout abort plane: importing the supervisor
# registers FLAGS_collective_timeout_s and (only when armed) a monitor
# thread over the flight ring — the per-collective hot path is untouched,
# the begin/end token below is already the evidence it reads
from ...fault import supervisor as _supervisor  # noqa: F401
from ...observability import flight as _flight
from ...observability import metrics as _metrics
from ...observability import trace as _trace
from .. import mesh as mesh_mod
from .group import Group, get_default_group

#: retry schedule for the host-side object collectives — these ride the
#: coordination channel (gRPC/pickle), where a stuck peer produces a
#: TimeoutError that a bounded backoff normally rides out
_OBJ_COLL_POLICY = RetryPolicy(max_attempts=4, base_delay=0.01,
                               max_delay=0.1, jitter=0.0,
                               retry_on=(TimeoutError, OSError))

#: most recent completed collective, for watchdog hang diagnostics
LAST_COLLECTIVE = {"op": None, "t": 0.0}

# Collective telemetry (gated by FLAGS_enable_metrics / an active
# profiler trace session; off = one dict lookup per collective)
_m_coll_calls = _metrics.counter(
    "paddle_tpu_collective_calls_total",
    "Collective invocations per primitive.", labelnames=("op",))
_m_coll_bytes = _metrics.counter(
    "paddle_tpu_collective_bytes_total",
    "Input payload bytes handed to each collective primitive.",
    labelnames=("op",))
_m_coll_latency = _metrics.histogram(
    "paddle_tpu_collective_latency_seconds",
    "Host wall time per collective call (build/cache lookup + dispatch; "
    "completion only when the caller synchronizes).", labelnames=("op",))


def _coll_begin(name: str, payload=None, group: Optional[Group] = None,
                **extra):
    """Open one collective record: a (t0, flight_entry) token.

    The flight recorder stamps a per-group monotonic sequence number and
    an in-flight ring entry HERE, before the device op — a rank that
    blocks inside the collective leaves the entry unfinished, which is
    exactly the evidence the cross-rank hang diff reads. Metric/trace
    timestamps additionally require their own gates, as before."""
    t0 = (_time.perf_counter()
          if _metrics.enabled() or _trace.active() else None)
    rec = None
    if _flight.enabled():
        gid = int(getattr(group, "id", 0) or 0) if group is not None else 0
        # bytes from shape × itemsize: reading .nbytes off a live jax
        # Array costs µs per call, which would dominate the recorder
        shape = getattr(payload, "shape", ())
        dt = getattr(payload, "dtype", None)
        nbytes = 0
        if dt is not None:
            nbytes = int(math.prod(shape)) * int(
                getattr(dt, "itemsize", 0) or 0)
        rec = _flight.RECORDER.begin(gid, name, shape, dt, nbytes,
                                     **extra)
    if _os.environ.get("PADDLE_TPU_PROGRAM_RECORD"):
        # static cross-rank seam (tpulint --cross-rank): eager
        # collectives never ride the dispatch recorder, so the program
        # dump notes them here — env-gated, zero cost otherwise
        from ...static import crossrank as _crossrank
        _crossrank.note_collective(
            name, getattr(payload, "shape", ()),
            getattr(payload, "dtype", ""),
            getattr(group, "id", 0) if group is not None else 0,
            **extra)
    return (t0, rec, name)


def _coll_end(tok, payload=None):
    t0, rec, name = tok
    LAST_COLLECTIVE["op"] = name     # one dict write; no clock read
    _flight.RECORDER.end(rec)
    if t0 is None:
        return
    # timestamp (for hang-age reporting) only when telemetry is already
    # paying for clocks — the disabled path stays at its documented cost
    LAST_COLLECTIVE["t"] = _time.monotonic()
    t1 = _time.perf_counter()
    nbytes = int(getattr(payload, "nbytes", 0) or 0)
    if _metrics.enabled():
        _m_coll_calls.inc(op=name)
        _m_coll_bytes.inc(nbytes, op=name)
        _m_coll_latency.observe(t1 - t0, op=name)
    _trace.add_complete(f"collective:{name}", "collective", t0, t1,
                        {"bytes": nbytes})


def _coll_abort(tok, exc):
    """Close the in-flight flight entry when the collective RAISES
    (shape error, device OOM, transport timeout): this rank is no
    longer inside the transport, so leaving ``t1=None`` would poison
    every later hang diff with a stale 'blocked at seq N' verdict.
    The exception type stays on the entry for the post-mortem."""
    _, rec, _name = tok
    if rec is not None and rec.get("t1") is None:
        rec["raised"] = type(exc).__name__
        _flight.RECORDER.end(rec)


def _desync_bypass(tok) -> bool:
    """``collective.desync`` fault guard: when armed (with an optional
    ``op=`` filter), this rank SKIPS the device collective — its peers
    enter it and block on the missing participant, which is precisely
    the desync failure mode the flight recorder + watchdog diff must
    name. The bypassed entry completes immediately and is marked, so a
    post-mortem reader can see the divergence locally too."""
    if _inject.fire("collective.desync", op=tok[2]) is None:
        return False
    if tok[1] is not None:
        tok[1]["bypassed"] = True
    return True


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def _group(group) -> Group:
    return group if group is not None else get_default_group()


def _ensure_on_mesh(arr, mesh):
    """Give the payload a NamedSharding on `mesh` (replicated if it has
    none), so shard_map specs line up."""
    sh = getattr(arr, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh.shape == mesh.shape:
        return arr, sh.spec
    arr = jax.device_put(arr, NamedSharding(mesh, P()))
    return arr, P()


def _reduce_fn(op, axes):
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        f = lambda x: jax.lax.psum(x, axes)
    elif op == ReduceOp.MAX:
        f = lambda x: jax.lax.pmax(x, axes)
    elif op == ReduceOp.MIN:
        f = lambda x: jax.lax.pmin(x, axes)
    elif op == ReduceOp.PROD:
        # True product: gather then multiply (log/exp would NaN on
        # negatives and zeros).
        ax = axes[0] if len(axes) == 1 else axes
        f = lambda x: jnp.prod(jax.lax.all_gather(x, ax, tiled=False), axis=0)
    else:
        raise ValueError(f"unsupported reduce op {op}")
    return f


@functools.lru_cache(maxsize=512)
def _build_all_reduce(mesh_key, axes, spec, op):
    mesh = _MESHES[mesh_key]
    red = _reduce_fn(op, axes)

    def body(x):
        y = red(x)
        if op == ReduceOp.AVG:
            n = int(np.prod([mesh.shape[a] for a in axes]))
            y = y / n
        return y
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


_MESHES = {}


def _mesh_key(mesh):
    key = (id(mesh),)
    _MESHES[key] = mesh
    return key


# ------------------------------------------------ branch-trace seam
# Inside a static.nn cond/while_loop/switch_case branch under capture,
# ops do not execute — a control-flow BranchTrace evaluates them
# abstractly. Collectives do not normally ride dispatch.call, so this
# seam records them into the active branch trace (name + group/axes
# identity + payload shape) and returns an abstract result. That trace
# is what the program verifier's static desync pass (static.verifier,
# TPU4xx) compares across arms — the compile-time complement of
# flight.diff_ranks.
def _bt_group_attrs(group, **extra) -> dict:
    if group is None:
        # normalize: an explicit default group and group=None are the
        # SAME collective — compare equal in the verifier's content
        # check (resolution may fail in a pure trace: keep None then)
        try:
            group = get_default_group()
        except Exception:
            group = None
    gid = int(getattr(group, "id", 0) or 0) if group is not None else 0
    axes = (tuple(getattr(group, "axes", ()) or ())
            if group is not None else None)
    return {"group": gid, "axes": axes, **extra}


def _branch_traced(name, tensor, group, n_out=1, out_shape=None,
                   **extra):
    """Record one collective abstractly; returns n_out abstract
    tensor(s) shaped like the input (or ``out_shape``)."""
    attrs = _bt_group_attrs(group, **extra)
    if tensor is None:
        return dispatch.call(name, lambda **_kw: jnp.zeros(()), [],
                             attrs=attrs)
    t = _t(tensor)
    if out_shape is not None:
        shape = tuple(out_shape)
        return dispatch.call(
            name, lambda x, **_kw: jnp.zeros(shape, dtype=x.dtype),
            [t], attrs=attrs)
    if n_out == 1:
        return dispatch.call(name, lambda x, **_kw: x, [t], attrs=attrs)
    return dispatch.call(
        name, lambda x, **_kw: tuple(x for _ in range(n_out)), [t],
        attrs=attrs, multi_output=True)


def _bt_nranks(group) -> int:
    try:
        return max(1, int(_group(group).nranks))
    except Exception:
        return 1                     # no process group in a pure trace


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place sum (or max/min/prod/avg) across the group's axes."""
    if dispatch.in_branch_trace():
        return _branch_traced("all_reduce", tensor, group,
                              reduce=str(op))
    g = _group(group)
    t = _t(tensor)
    tok = _coll_begin("all_reduce", t._data, g)
    if _desync_bypass(tok):  # tpulint: disable=TPU105 — taint FP: tok is a host (t0, flight_entry, name) tuple; the branch reads the fault-injection registry, never tensor data
        _coll_end(tok, t._data)
        return t
    try:
        arr, spec = _ensure_on_mesh(t._data, g.mesh)
        fn = _build_all_reduce(_mesh_key(g.mesh), g.axes, spec, op)
        out = fn(arr)
        t._swap_payload(out)
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise
    return t


def _strip_axes(spec: P, axes) -> list:
    """Remove group axes from a PartitionSpec (they become replicated)."""
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a not in axes)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(None if e in axes else e)
    return out


@functools.lru_cache(maxsize=512)
def _build_all_gather(mesh_key, axes, spec):
    mesh = _MESHES[mesh_key]
    axis = axes[0] if len(axes) == 1 else axes

    def body(x):
        return jax.lax.all_gather(x, axis, tiled=False)
    # gathered result is replicated along the group axes
    out_spec = P(None, *_strip_axes(spec, axes))
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=out_spec))


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """Gather each rank's tensor; fills ``tensor_list`` (reference
    all_gather.py)."""
    if dispatch.in_branch_trace():
        n = _bt_nranks(group)
        outs = _branch_traced("all_gather", tensor, group, n_out=n)
        outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
        if tensor_list is None:
            tensor_list = []
        del tensor_list[:]
        tensor_list.extend(outs)
        return tensor_list
    g = _group(group)
    t = _t(tensor)
    tok = _coll_begin("all_gather", t._data, g)
    if _desync_bypass(tok):  # tpulint: disable=TPU105 — taint FP: tok is a host (t0, flight_entry, name) tuple; the branch reads the fault-injection registry, never tensor data
        _coll_end(tok, t._data)
        stacked = jnp.broadcast_to(
            t._data[None], (g.nranks,) + tuple(t._data.shape))
    else:
        try:
            arr, spec = _ensure_on_mesh(t._data, g.mesh)
            fn = _build_all_gather(_mesh_key(g.mesh), g.axes, spec)
            stacked = fn(arr)              # (nranks, *global_shape_local)
            _coll_end(tok, arr)
        except BaseException as e:
            _coll_abort(tok, e)
            raise
    n = stacked.shape[0]
    if tensor_list is None:
        tensor_list = []
    del tensor_list[:]
    for i in range(n):
        tensor_list.append(Tensor(stacked[i]))
    return tensor_list


# ------------------------------------------------- cross-process exchange
# One device per PROCESS: the sharding under which
# jax.make_array_from_process_local_data lets each process contribute its
# own row, and a replicated-output jit is a true all-gather over the
# coordination transport (gloo on CPU, ICI/DCN on TPU pods). This is the
# substrate of the fleet telemetry plane (observability.fleet): per-rank
# payloads really ARE distinct across processes there, unlike the
# single-controller in-process case where every "rank" holds the same
# object.
_PROC_MESH = {"mesh": None, "world": 0}


def _process_mesh():
    world = jax.process_count()
    if _PROC_MESH["mesh"] is None or _PROC_MESH["world"] != world:
        devs = []
        for i in range(world):
            cand = [d for d in jax.devices() if d.process_index == i]
            if not cand:
                raise RuntimeError(
                    f"no addressable-or-known device for process {i}")
            devs.append(cand[0])
        from jax.sharding import Mesh
        _PROC_MESH["mesh"] = Mesh(np.array(devs), ("fleet",))
        _PROC_MESH["world"] = world
    return _PROC_MESH["mesh"]


@functools.lru_cache(maxsize=64)
def _gather_rows_fn(mesh_key, shape, dtype):
    mesh = _MESHES[mesh_key]
    return jax.jit(lambda a: a,
                   out_shardings=NamedSharding(mesh, P()))


def gather_rows(row: "np.ndarray") -> "np.ndarray":
    """All-gather one fixed-shape numeric row per PROCESS: rank r's
    ``row`` (shape ``S``) lands in result[r] (shape ``(world, *S)``) on
    every rank. Single process: the identity stack. The compiled gather
    is cached per (world, shape, dtype) — a beacon calling this every N
    steps pays one compile ever. Flight-recorded like every other
    primitive: the blocking host read happens inside the token, so a
    rank stuck here (a peer died mid-window) leaves the pending ring
    entry the watchdog's cross-rank diff needs — the telemetry plane's
    own collective must not be the one hang it cannot diagnose."""
    row = np.asarray(row)
    world = jax.process_count()
    if world == 1:
        return row[None]
    tok = _coll_begin("gather_rows", row, None)
    try:
        mesh = _process_mesh()
        sharded = NamedSharding(mesh, P("fleet"))
        x = jax.make_array_from_process_local_data(
            sharded, jnp.asarray(row)[None], (world,) + row.shape)
        fn = _gather_rows_fn(_mesh_key(mesh), (world,) + row.shape,
                             str(row.dtype))
        out = np.asarray(fn(x))  # tpulint: disable=TPU104 — object-gather boundary: the gathered payload matrix is consumed on the host by contract
    finally:
        _coll_end(tok, row)
    return out


#: pickled payloads are padded to a power-of-two bucket (floor 256) so
#: repeated object gathers reuse a handful of compiled programs
_OBJ_BUCKET_MIN = 256


def _gather_payloads(payload: bytes) -> List[bytes]:
    """Cross-process all-gather of one variable-length bytes payload per
    process. Two fixed-shape rounds: lengths first (so every process pads
    to the same bucket), then the padded payload matrix."""
    lengths = gather_rows(np.asarray([len(payload)], np.int32))
    maxlen = int(lengths.max())
    bucket = _OBJ_BUCKET_MIN
    while bucket < maxlen:
        bucket *= 2
    row = np.zeros(bucket, np.uint8)
    row[:len(payload)] = np.frombuffer(payload, np.uint8)
    rows = gather_rows(row)
    return [bytes(rows[r, :int(lengths[r, 0])])
            for r in range(rows.shape[0])]


def all_gather_object(object_list, obj, group=None):
    """Host-side object gather (reference all_gather_object is a
    pickle-over-NCCL convenience). Across real processes each rank's
    ``obj`` is DISTINCT: the payload is pickled, padded, and exchanged
    through the tensor collectives (gloo/ICI transport, see
    ``_gather_payloads``). Single-controller in-process, every 'rank'
    holds the same object, so it replicates. Guarded by the
    ``collective.timeout`` fault point and retried with backoff — the
    host object channel is the part of a collective that an unhealthy
    peer can actually stall."""
    import pickle

    g = _group(group)

    world = jax.process_count()
    if world > 1:
        # the cross-process exchange spans EVERY process; a proper
        # subgroup would hang waiting for non-members, so refuse it
        # loudly instead (full-world groups are the fleet-telemetry
        # use; per-axis subgroup object gathers have no cross-process
        # implementation here yet)
        # span check by PROCESS, not device rank: on multi-device
        # processes (a TPU host owns several chips) the full-world
        # group's nranks is the chip count, not the process count
        procs = {d.process_index
                 for d in np.asarray(g.mesh.devices).ravel()}
        if procs != set(range(world)):
            raise NotImplementedError(
                f"cross-process all_gather_object only supports groups "
                f"spanning every process ({world}); got a group whose "
                f"devices live on processes {sorted(procs)}")
        # NO retry here: re-running a real collective on one rank while
        # its peers completed (or sit inside) theirs would shift the
        # transport's collective matching — the exact desync failure
        # the flight recorder exists to name. The retry policy covers
        # the host-only replicate path, where attempts are idempotent.
        _inject.check("collective.timeout", exc=TimeoutError)
        tok = _coll_begin("all_gather_object", None, g)
        try:
            payloads = _gather_payloads(pickle.dumps(obj))
        finally:
            _coll_end(tok)
        gathered = [pickle.loads(p) for p in payloads]  # tpulint: disable=TPU104 — object collective deserialization: host unpickle is the documented contract
    else:
        def attempt():
            _inject.check("collective.timeout", exc=TimeoutError)
            return [obj] * g.nranks

        gathered = _retry(attempt, policy=_OBJ_COLL_POLICY,
                          site="all_gather_object")
    del object_list[:]
    object_list.extend(gathered)
    return object_list


@functools.lru_cache(maxsize=512)
def _build_reduce_scatter(mesh_key, axes, spec, op):
    mesh = _MESHES[mesh_key]
    axis = axes[0] if len(axes) == 1 else axes
    n = int(np.prod([mesh.shape[a] for a in axes]))

    if op in (ReduceOp.SUM, ReduceOp.AVG):
        def body(x):
            y = jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
            if op == ReduceOp.AVG:
                y = y / n
            return y
    else:
        # MAX/MIN/PROD have no fused scatter primitive: reduce the gathered
        # copies elementwise, then keep this rank's chunk.
        red = _reduce_fn(op, axes)

        def body(x):
            full = red(x)
            chunk = full.shape[0] // n
            idx = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(full, idx * chunk, chunk, 0)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """Each rank gets its reduced chunk of the concatenated input
    (reference reduce_scatter.py)."""
    if dispatch.in_branch_trace():
        src = tensor_or_tensor_list
        if isinstance(src, (list, tuple)):
            # list form: each entry is one rank's chunk — the result is
            # chunk-shaped, so the first entry is the exact shape proxy
            return _branch_traced("reduce_scatter", src[0], group,
                                  reduce=str(op))
        srct = _t(src)
        shape = tuple(srct._data.shape)
        n = _bt_nranks(group)
        if shape and shape[0] % n == 0:
            shape = (shape[0] // n,) + shape[1:]   # real op contract
        return _branch_traced("reduce_scatter", srct, group,
                              out_shape=shape, reduce=str(op))
    g = _group(group)
    src = tensor_or_tensor_list
    if isinstance(src, (list, tuple)):
        from ...ops import manipulation
        src = manipulation.concat([_t(s) for s in src], axis=0)
    src = _t(src)
    if src._data.shape[0] % g.nranks != 0:
        raise ValueError(
            f"reduce_scatter dim 0 ({src._data.shape[0]}) must divide the "
            f"group size ({g.nranks})")
    tok = _coll_begin("reduce_scatter", src._data, g)
    try:
        arr, spec = _ensure_on_mesh(src._data, g.mesh)
        fn = _build_reduce_scatter(_mesh_key(g.mesh), g.axes, spec, op)
        out = fn(arr)
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise
    if tensor is not None:
        _t(tensor)._swap_payload(out)
        return tensor
    return Tensor(out)


@functools.lru_cache(maxsize=512)
def _build_broadcast(mesh_key, axes, spec, src):
    mesh = _MESHES[mesh_key]
    axis = axes[0] if len(axes) == 1 else axes

    def body(x):
        g = jax.lax.all_gather(x, axis, tiled=False)
        return g[src]
    # every rank's local shard := src's shard, so the layout is unchanged
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def broadcast(tensor, src=0, group=None, sync_op=True):
    if dispatch.in_branch_trace():
        return _branch_traced("broadcast", tensor, group, src=int(src))
    g = _group(group)
    t = _t(tensor)
    src_local = g.get_group_rank(src)
    if src_local < 0:
        src_local = src
    tok = _coll_begin("broadcast", t._data, g)
    if _desync_bypass(tok):  # tpulint: disable=TPU105 — taint FP: tok is a host (t0, flight_entry, name) tuple; the branch reads the fault-injection registry, never tensor data
        _coll_end(tok, t._data)
        return t
    try:
        arr, spec = _ensure_on_mesh(t._data, g.mesh)
        fn = _build_broadcast(_mesh_key(g.mesh), g.axes, spec, src_local)
        t._swap_payload(fn(arr))
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise
    return t


def broadcast_object_list(object_list, src=0, group=None):
    """Broadcast picklable objects from src (reference
    communication/broadcast.py broadcast_object_list: pickle -> uint8
    tensor broadcast -> unpickle). Single-controller SPMD already has one
    Python process per host driving all devices, so the tensor round-trip
    is the multi-host path; in-process it round-trips through the same
    serialize/deserialize to keep semantics identical."""
    import pickle

    import numpy as np

    def attempt():
        # idempotent: re-running after a mid-list failure re-broadcasts
        # the same values into the same slots
        _inject.check("collective.timeout", exc=TimeoutError)
        for i, obj in enumerate(object_list):
            payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()  # tpulint: disable=TPU104 — object collective: the payload is a pickled PYTHON object, host by design
            n = Tensor(jnp.asarray([payload.size], jnp.int32))
            broadcast(n, src=src, group=group)
            t = Tensor(jnp.asarray(payload))
            broadcast(t, src=src, group=group)
            object_list[i] = pickle.loads(
                np.asarray(t._data, dtype=np.uint8).tobytes())  # tpulint: disable=TPU104 — object collective deserialization: host unpickle is the documented contract
        return object_list

    return _retry(attempt, policy=_OBJ_COLL_POLICY,
                  site="broadcast_object_list")


@functools.lru_cache(maxsize=512)
def _build_reduce(mesh_key, axes, spec, op):
    return _build_all_reduce(mesh_key, axes, spec, op)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reduce to dst. SPMD computes the reduction everywhere (a strict
    superset of the reference semantics where only dst sees the result)."""
    return all_reduce(tensor, op=op, group=group)


@functools.lru_cache(maxsize=512)
def _build_scatter(mesh_key, axes, spec, src):
    mesh = _MESHES[mesh_key]
    axis = axes[0] if len(axes) == 1 else axes
    n = int(np.prod([mesh.shape[a] for a in axes]))

    def body(x):
        g = jax.lax.all_gather(x, axis, tiled=False)
        mine = g[src]                       # src's full tensor
        chunk = mine.shape[0] // n
        idx = jax.lax.axis_index(axis)
        return jax.lax.dynamic_slice_in_dim(mine, idx * chunk, chunk, 0)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def scatter(tensor, tensor_or_tensor_list=None, src=0, group=None,
            sync_op=True):
    g = _group(group)
    source = tensor_or_tensor_list
    if isinstance(source, (list, tuple)):
        from ...ops import manipulation
        source = manipulation.concat([_t(s) for s in source], axis=0)
    source = _t(source) if source is not None else _t(tensor)
    tok = _coll_begin("scatter", source._data, g)
    try:
        arr, spec = _ensure_on_mesh(source._data, g.mesh)
        src_local = g.get_group_rank(src)
        if src_local < 0:
            src_local = src
        fn = _build_scatter(_mesh_key(g.mesh), g.axes, spec, src_local)
        out = fn(arr)
        _t(tensor)._swap_payload(out)
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise
    return tensor


@functools.lru_cache(maxsize=512)
def _build_all_to_all(mesh_key, axes, spec):
    mesh = _MESHES[mesh_key]
    axis = axes[0] if len(axes) == 1 else axes

    def body(x):
        # x local: (n, chunk, ...) — slab j goes to rank j.
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """rank i's j-th input tensor lands as rank j's i-th output
    (reference all_to_all.py)."""
    g = _group(group)
    from ...ops import manipulation
    stacked = manipulation.stack([_t(x) for x in in_tensor_list], axis=0)
    tok = _coll_begin("all_to_all", stacked._data, g)
    try:
        arr, spec = _ensure_on_mesh(stacked._data, g.mesh)
        fn = _build_all_to_all(_mesh_key(g.mesh), g.axes, spec)
        out = fn(arr)
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise
    if out_tensor_list is None:
        out_tensor_list = []
    del out_tensor_list[:]
    for i in range(out.shape[0]):
        out_tensor_list.append(Tensor(out[i]))
    return out_tensor_list


all_to_all = alltoall


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    g = _group(group)
    t = _t(in_tensor)
    n = g.nranks
    for sizes, label in ((in_split_sizes, "in_split_sizes"),
                         (out_split_sizes, "out_split_sizes")):
        if sizes is None:
            continue
        if len(set(int(s) for s in sizes)) > 1:
            raise NotImplementedError(
                f"alltoall_single with uneven {label}={list(sizes)} is not "
                "supported; pad to equal chunks")
        if len(sizes) != n or sum(int(s) for s in sizes) != t._data.shape[0]:
            raise ValueError(
                f"{label}={list(sizes)} must have one entry per rank ({n}) "
                f"and sum to dim 0 ({t._data.shape[0]})")
    tok = _coll_begin("all_to_all_single", t._data, g)
    try:
        arr, spec = _ensure_on_mesh(t._data, g.mesh)
        reshaped = arr.reshape((n, arr.shape[0] // n) + arr.shape[1:])
        fn = _build_all_to_all(_mesh_key(g.mesh), g.axes,
                               P(*([None] + list(spec))))
        out = fn(reshaped)
        out = out.reshape((-1,) + out.shape[2:])
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise
    if out_tensor is not None:
        _t(out_tensor)._swap_payload(out)
        return out_tensor
    return Tensor(out)


def barrier(group=None):
    if dispatch.in_branch_trace():
        _branch_traced("barrier", None, group)
        return
    g = _group(group)
    # token reduction built directly (not via all_reduce) so the barrier
    # records ONE metric sample instead of also inflating all_reduce's
    z = jnp.zeros(())
    tok = _coll_begin("barrier", z, g)
    if _desync_bypass(tok):  # tpulint: disable=TPU105 — taint FP: tok is a host (t0, flight_entry, name) tuple; the branch reads the fault-injection registry, never tensor data
        _coll_end(tok, z)
        return
    try:
        arr, spec = _ensure_on_mesh(z, g.mesh)
        fn = _build_all_reduce(_mesh_key(g.mesh), g.axes, spec,
                               ReduceOp.SUM)
        jax.block_until_ready(fn(arr))
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise


# --------------------------------------------------------------------- p2p
class P2POp:
    """One half of a point-to-point exchange (reference
    communication/batch_isend_irecv.py P2POp)."""

    def __init__(self, op, tensor, peer, group=None):
        self.op = op                # the send/recv function object
        self.tensor = _t(tensor)
        self.peer = peer
        self.group = group


def isend(tensor, dst, group=None, sync_op=True):
    raise RuntimeError(
        "Single-controller SPMD has no unpaired send: batch the exchange "
        "with paddle_tpu.distributed.batch_isend_irecv (ppermute), as the "
        "pipeline runtime does.")


def irecv(tensor, src, group=None, sync_op=True):
    raise RuntimeError(
        "Single-controller SPMD has no unpaired recv: batch the exchange "
        "with paddle_tpu.distributed.batch_isend_irecv (ppermute).")


send = isend
recv = irecv


@functools.lru_cache(maxsize=512)
def _build_ppermute(mesh_key, axes, spec, perm):
    mesh = _MESHES[mesh_key]
    axis = axes[0] if len(axes) == 1 else axes

    def body(x):
        return jax.lax.ppermute(x, axis, list(perm))
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def batch_isend_irecv(p2p_op_list):
    """Pair up sends/recvs into one ppermute over the group axis
    (reference batch_isend_irecv; PP p2p at
    fleet/meta_parallel/pp_utils/p2p_communication.py:637)."""
    sends = [op for op in p2p_op_list if op.op in (isend, "send", "isend")]
    recvs = [op for op in p2p_op_list if op.op in (irecv, "recv", "irecv")]
    if not sends:
        return []
    g = _group(sends[0].group)
    # In SPMD every rank executes the same exchange, so the send ops must
    # describe the whole permutation: op i = "group-rank src_rank (default i)
    # sends to group-rank peer".
    perm = tuple((int(getattr(op, "src_rank", i)), int(op.peer))
                 for i, op in enumerate(sends))
    t = sends[0].tensor
    tok = _coll_begin("batch_isend_irecv", t._data, g)
    try:
        arr, spec = _ensure_on_mesh(t._data, g.mesh)
        fn = _build_ppermute(_mesh_key(g.mesh), g.axes, spec, perm)
        out = fn(arr)
        for op in recvs:
            op.tensor._swap_payload(out)
        _coll_end(tok, arr)
    except BaseException as e:
        _coll_abort(tok, e)
        raise
    return []


# ------------------------------------------------------- in-graph wrappers
def shift_along_axis(arr, axis_name, shift, mesh=None):
    """ppermute helper used by the pipeline runtime inside compiled steps:
    shard i's value moves to shard (i+shift) mod n."""
    mesh = mesh or mesh_mod.get_mesh()
    n = int(mesh.shape[axis_name])
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(arr, axis_name, perm)
