"""Parallel environment + DataParallel.

Reference: python/paddle/distributed/parallel.py (init_parallel_env:945 —
env rendezvous, TCPStore, ProcessGroupNCCL; DataParallel:202 with the C++
EagerReducer grad-bucketing). TPU-native: rendezvous is
``jax.distributed.initialize`` (PJRT coordination service replaces
TCPStore); within a host the mesh gives SPMD parallelism, so DataParallel
needs NO reducer — sharding the batch over the 'dp' axis makes XLA emit the
gradient all-reduce automatically during backward (GSPMD), already overlapped
with remaining backward compute.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from . import mesh as mesh_mod
from .auto_parallel.api import ProcessMesh, Replicate, Shard, shard_tensor
from .communication.group import get_default_group


class ParallelEnv:
    """Reference: python/paddle/distributed/parallel.py ParallelEnv."""

    @property
    def rank(self):
        return jax.process_index()

    @property
    def world_size(self):
        return jax.process_count()

    @property
    def local_rank(self):
        return int(os.environ.get("PADDLE_RANK_IN_NODE", 0))

    @property
    def dev_id(self):
        return self.local_rank

    @property
    def nranks(self):
        return self.world_size

    @property
    def device_count(self):
        return jax.local_device_count()

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:0")

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []


_initialized = False


def init_parallel_env(mesh_shape=None):
    """Bring up the distributed runtime (reference parallel.py:945).

    Multi-host: PADDLE_MASTER/PADDLE_TRAINER_ID env (as the reference's
    launcher sets) feed ``jax.distributed.initialize`` — the PJRT
    coordination service is the TCPStore equivalent. Then a mesh over the
    global device set becomes the default topology.
    """
    global _initialized
    if _initialized:
        return ParallelEnv()
    # the launcher exports epoch-correct jax.distributed coordinates
    # (JAX_COORDINATOR_ADDRESS moves with the elastic epoch); prefer them
    # over the static PADDLE_MASTER the user may also have set
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    nnodes = int(os.environ.get("JAX_NUM_PROCESSES")
                 or os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    rank = int(os.environ.get("JAX_PROCESS_ID")
               or os.environ.get("PADDLE_TRAINER_ID", "0"))
    if not coord:
        master = (os.environ.get("PADDLE_MASTER")
                  or os.environ.get("MASTER_ADDR"))
        if master:
            port = os.environ.get("MASTER_PORT", "8471")
            coord = master if ":" in master else f"{master}:{port}"
    # must not probe jax.process_count() here: touching the backend before
    # jax.distributed.initialize permanently forecloses multi-process init
    # (jax.distributed.is_initialized reads no backend state)
    if coord and nnodes > 1 and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nnodes,
            process_id=rank)
    mesh_mod.set_mesh(mesh_mod.build_mesh(mesh_shape))
    _initialized = True
    return ParallelEnv()


def get_rank(group=None):
    return jax.process_index()


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    return jax.process_count()


def is_initialized():
    return _initialized or mesh_mod.has_mesh()


class DataParallel(Layer):
    """Data-parallel wrapper (reference parallel.py:202).

    Shards every batch input along dim 0 over the 'dp' mesh axis; params
    stay replicated. XLA's SPMD partitioner inserts the grad all-reduce
    during backward — the reference's EagerReducer bucketing/overlap
    machinery (collective/reducer.cc) is subsumed by the compiler.
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        # comm_buffer_size / last_comm_buffer_size (reference: grad-fusion
        # bucket MBs for the EagerReducer) have no effect on TPU: XLA
        # schedules and fuses the dp psums itself. find_unused_parameters
        # is likewise subsumed — jax autodiff produces zero grads for
        # unused params and every grad's psum is compiler-inserted, so
        # there is no reducer to hang; the reference semantics of
        # find_unused_parameters=True hold unconditionally here.
        self.comm_buffer_size = comm_buffer_size
        self.last_comm_buffer_size = last_comm_buffer_size
        self.find_unused_parameters = find_unused_parameters
        mesh = mesh_mod.get_mesh()
        axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
        self._pmesh = ProcessMesh(list(range(int(mesh.shape[axis]))),
                                  dim_names=[axis])
        self._axis = axis
        self._in_no_sync = False

    def _shard_input(self, x):
        if isinstance(x, Tensor):
            return shard_tensor(x, self._pmesh, [Shard(0)])
        if isinstance(x, (list, tuple)):
            return type(x)(self._shard_input(i) for i in x)
        if isinstance(x, dict):
            return {k: self._shard_input(v) for k, v in x.items()}
        return x

    def forward(self, *inputs, **kwargs):
        inputs = self._shard_input(inputs)
        kwargs = self._shard_input(kwargs)
        return self._layers(*inputs, **kwargs)

    def no_sync(self):
        """Grad-sync suppression context (reference parallel.py no_sync).

        Semantics here are exact, not skipped: with global arrays the dp
        grad all-reduce is not a separate step the wrapper issues — XLA
        fuses the psum into each backward program, so gradients inside and
        outside this context are bit-identical to the reference's
        accumulate-then-sync. What the reference saves (one allreduce per
        micro-batch) has no analog to skip; the context only records state
        for introspection parity.
        """
        import contextlib

        @contextlib.contextmanager
        def ctx():
            self._in_no_sync = True
            try:
                yield
            finally:
                self._in_no_sync = False
        return ctx()

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, *args, **kwargs):
        return self._layers.set_state_dict(*args, **kwargs)

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        pass
