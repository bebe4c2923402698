"""Pipelined execution of a partitioned program.

:class:`PipelinedProgram` runs a :class:`~.partition.StagePartition`
under any schedule table from :mod:`.schedules`:

* each stage becomes a pure jitted function replaying its op slice
  over an id->array environment (the same replay the Program runner
  and the fusion pass use);
* the backward is a rematerializing ``jax.vjp`` over that replay —
  only boundary activations are saved between F and B, never the
  stage interior — jitted with the saved activations and incoming
  gradient DONATED (``jit.donating_jit``), so steady-state 1F1B runs
  with double-buffered boundaries and stale host reads raise
  ``core.donation.DonatedBufferError``;
* steps execute host-serially in dataflow order (the same dependency
  relation :func:`.schedules.simulate` models), optionally timed per
  step so the measured bubble fraction can be compared against the
  analytical one;
* with a ``(data, pp)`` mesh, each stage is pinned to its submesh
  (``distributed.spmd.stage_submeshes``) and boundary values hop
  between adjacent submeshes via ``jax.device_put`` with the
  micro-batch dimension kept sharded over the data axis.

Gradient determinism: every (microbatch, stage) weight-gradient
contribution is stored and reduced in a FIXED order (microbatch
ascending, stage descending) regardless of the order the schedule
executed the B/W steps in — so F-then-B, 1F1B, and zero-bubble
produce bitwise-identical gradients to :meth:`run_unpipelined` (the
tests pin this). The zero-bubble W step applies the weight gradient
stashed by its B step — deferred application on the static ZBH1
clock; the per-op dX/dW kernel split lives in the fleet runtime
(``fleet.meta_parallel.pipeline_schedules``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .partition import StagePartition
from .schedules import (ScheduleStep, analytical_bubble, build_schedule,
                        peak_inflight, simulate)

__all__ = ["PipelinedProgram"]


def _is_inexact(dtype) -> bool:
    try:
        return jnp.issubdtype(jnp.dtype(dtype), jnp.inexact)
    except TypeError:
        return False


class _StageExec:
    """Jitted forward/backward executors for one pipeline stage."""

    def __init__(self, stage, program, donate: bool):
        from ...jit import donating_jit

        self.stage = stage
        self.param_ids = tuple(stage.param_ids)
        self.feed_ids = tuple(program.feed_vars[n]
                              for n in stage.feed_names)
        self.recv_ids = tuple(v.vid for v in stage.recv)
        self.send_ids = tuple(v.vid for v in stage.send)
        self.fetch_ids = tuple(v.vid for v in stage.fetch)
        self.ops = list(stage.ops)
        # only inexact-dtype boundary values carry cotangents; integer
        # routed values (token ids, lengths) are forwarded, not
        # differentiated
        self.diff_param_idx = tuple(
            i for i, pid in enumerate(self.param_ids)
            if _is_inexact(program._captured[pid]._data.dtype))
        self.diff_recv_idx = tuple(
            i for i, v in enumerate(stage.recv) if _is_inexact(v.dtype))
        self.diff_send_idx = tuple(
            i for i, v in enumerate(stage.send) if _is_inexact(v.dtype))
        self.diff_fetch_idx = tuple(
            i for i, v in enumerate(stage.fetch)
            if _is_inexact(v.dtype))

        label = f"pipeline stage {stage.index}"
        self.fwd = donating_jit(self._run, context=label)
        # donate the saved boundary activations (arg 2) and the
        # incoming output gradient (arg 3): the backward is their last
        # consumer, XLA reuses the buffers in place
        self.bwd = donating_jit(
            self._bwd, donate_argnums=(2, 3) if donate else (),
            context=f"{label} backward")

    def _run(self, params, feeds, recvs):
        env = dict(zip(self.param_ids, params))
        env.update(zip(self.feed_ids, feeds))
        env.update(zip(self.recv_ids, recvs))
        for op in self.ops:
            args = [env[i] for i in op.in_ids]
            out = op.fn(*args)
            outs = (list(out) if isinstance(out, (tuple, list))
                    else [out])
            for oid, val in zip(op.out_ids, outs):
                env[oid] = val
        return (tuple(env[v] for v in self.send_ids),
                tuple(env[v] for v in self.fetch_ids))

    def _bwd(self, params, feeds, recvs, gsends, gfetches):
        """Rematerializing vjp: re-run the stage forward, pull the
        cotangents for (differentiable sends, differentiable fetches)
        back to (differentiable params, differentiable recvs)."""

        def f(dp, dr):
            p = list(params)
            for slot, v in zip(self.diff_param_idx, dp):
                p[slot] = v
            r = list(recvs)
            for slot, v in zip(self.diff_recv_idx, dr):
                r[slot] = v
            sends, fetches = self._run(tuple(p), feeds, tuple(r))
            return (tuple(sends[i] for i in self.diff_send_idx),
                    tuple(fetches[i] for i in self.diff_fetch_idx))

        primal_p = tuple(params[i] for i in self.diff_param_idx)
        primal_r = tuple(recvs[i] for i in self.diff_recv_idx)
        _, vjp = jax.vjp(f, primal_p, primal_r)
        gp, gr = vjp((gsends, gfetches))
        return gp, gr


class PipelinedProgram:
    """Execute a stage partition under a micro-batch schedule.

    The partitioned program must be traced at MICROBATCH shape: each
    F step replays the recorded ops verbatim, so batch-dependent
    static attrs (reshape targets, split sizes) fix the per-microbatch
    batch at trace time. ``train_step`` feeds then carry ``m ×`` the
    traced leading dim (split evenly), or exactly the traced shape
    (replicated to every microbatch).

    Parameters
    ----------
    partition : StagePartition
    schedule : ``"fthenb" | "1f1b" | "zb"`` (aliases accepted)
    loss_id : value id of the scalar loss fetch (required for
        :meth:`train_step`; must be produced by the LAST stage — use
        ``split_points`` to move the boundary otherwise)
    mesh : optional ``(data, pp)`` ``jax.sharding.Mesh``; the
        ``pp_axis`` size must equal the stage count
    donate : donate backward boundary buffers (double buffering)
    check : run ``static.verifier.check_stages`` over the partition at
        construction (default: whenever the verifier mode is not off)
    """

    def __init__(self, partition: StagePartition, *,
                 schedule: str = "1f1b",
                 loss_id: Optional[int] = None,
                 mesh=None, pp_axis: str = "pp",
                 data_axis: str = "data",
                 donate: bool = True,
                 check: Optional[bool] = None):
        self.partition = partition
        self.schedule = schedule
        self.loss_id = loss_id
        self.donate = bool(donate)
        self._program = partition.program
        self._pp_axis = pp_axis
        self._data_axis = data_axis
        S = partition.num_stages

        self._submeshes = None
        if mesh is not None:
            from ..spmd import stage_submeshes
            if int(mesh.shape[pp_axis]) != S:
                raise ValueError(
                    f"mesh axis {pp_axis!r} has size "
                    f"{mesh.shape[pp_axis]}, partition has {S} stages")
            self._submeshes = stage_submeshes(mesh, pp_axis)
        self._placed: Dict[tuple, tuple] = {}

        if loss_id is not None:
            owners = [s for s in range(S)
                      if any(v.vid == loss_id
                             for v in partition.stages[s].fetch)]
            if not owners:
                raise ValueError(
                    f"loss_id {loss_id} is not among the partition's "
                    f"fetches {list(partition.fetch_ids)}")
            if owners[0] != S - 1:
                raise ValueError(
                    f"loss is produced by stage {owners[0]}, not the "
                    f"last stage {S - 1} — the backward schedule seeds "
                    f"the loss cotangent at the last stage; move the "
                    f"boundary with split_points")

        self._execs = [_StageExec(st, self._program, self.donate)
                       for st in partition.stages]

        from ...static import verifier as _verifier
        if check is None:
            check = _verifier.mode() != "off"
        if check:
            report = _verifier.check_stages(
                partition.stage_records(),
                label=f"pipeline[{partition.strategy}x{S}]")
            _verifier.enforce(report)

    # -- placement --------------------------------------------------

    def _place(self, arr, s: int):
        if self._submeshes is None:
            return arr
        from jax.sharding import NamedSharding
        from ..spmd import boundary_spec
        sub = self._submeshes[s]
        spec = boundary_spec(getattr(arr, "shape", ()), sub,
                             self._data_axis)
        return jax.device_put(arr, NamedSharding(sub, spec))

    def _transfer(self, vals, s: int):
        """Move one boundary tuple onto stage ``s``'s submesh (adjacent
        P2P hop; identity without a mesh)."""
        if self._submeshes is None:
            return tuple(vals)
        return tuple(self._place(v, s) for v in vals)

    def _stage_params(self, s: int):
        """Stage parameter arrays, device_put onto the stage submesh
        (cached per payload — re-placed only after an optimizer swaps
        the payload)."""
        ex = self._execs[s]
        out = []
        for pid in ex.param_ids:
            arr = self._program._captured[pid]._data
            if self._submeshes is not None:
                cached = self._placed.get((s, pid))
                if cached is None or cached[0] is not arr:
                    from jax.sharding import (NamedSharding,
                                              PartitionSpec as P)
                    placed = jax.device_put(
                        arr, NamedSharding(self._submeshes[s], P()))
                    self._placed[(s, pid)] = (arr, placed)
                    arr = placed
                else:
                    arr = cached[1]
            out.append(arr)
        return tuple(out)

    def _split_feeds(self, feed: Dict[str, object], m: int):
        """Full-batch feed dict -> per-stage, per-microbatch feed
        tuples. Arrays whose leading dim divides by ``m`` are split;
        everything else is replicated to every microbatch."""
        arrays = {}
        for name, val in feed.items():
            arrays[name] = jnp.asarray(getattr(val, "_data", val))
        per_stage = []
        for s, st in enumerate(self.partition.stages):
            mbs = []
            for mb in range(m):
                vals = []
                for name in st.feed_names:
                    a = arrays[name]
                    if a.ndim >= 1 and a.shape[0] % m == 0 and m > 1:
                        size = a.shape[0] // m
                        a = a[mb * size:(mb + 1) * size]
                    vals.append(self._place(a, s))
                mbs.append(tuple(vals))
            per_stage.append(mbs)
        return per_stage

    # -- execution --------------------------------------------------

    @staticmethod
    def _deps(st: ScheduleStep, S: int):
        k, s, mb = st
        need = []
        if k == "F" and s > 0:
            need.append(("F", s - 1, mb))
        if k == "B":
            need.append(("F", s, mb))
            if s < S - 1:
                need.append(("B", s + 1, mb))
        if k == "W":
            need.append(("B", s, mb))
        return need

    def _execute_table(self, table, run_step, timings=None):
        """Run the schedule table host-serially in dataflow order (the
        execution twin of :func:`.schedules.simulate`)."""
        S = len(table)
        done = set()
        cursor = [0] * S
        total = sum(len(steps) for steps in table)
        executed = 0
        while executed < total:
            progressed = False
            for s in range(S):
                while cursor[s] < len(table[s]):
                    st = table[s][cursor[s]]
                    if any(d not in done for d in self._deps(st, S)):
                        break
                    if timings is not None:
                        t0 = time.perf_counter()
                        out = run_step(st)
                        jax.block_until_ready(out)
                        timings[(st.kind, st.stage, st.mb)] = (
                            time.perf_counter() - t0)
                    else:
                        run_step(st)
                    done.add((st.kind, st.stage, st.mb))
                    cursor[s] += 1
                    executed += 1
                    progressed = True
            if not progressed:
                stuck = [(s, table[s][cursor[s]]) for s in range(S)
                         if cursor[s] < len(table[s])]
                raise RuntimeError(
                    f"pipeline schedule deadlock at {stuck}")

    def _make_steps(self, feed, m: int, want_grad: bool):
        """Build the per-step callbacks + shared state for one run."""
        S = self.partition.num_stages
        params = [self._stage_params(s) for s in range(S)]
        feeds = self._split_feeds(feed, m)
        state = {
            "recv": {},     # (s, mb) -> incoming activation tuple
            "saved": {},    # (s, mb) -> recvs retained for backward
            "gsend": {},    # (s, mb) -> cotangent of this stage's sends
            "wstash": {},   # (s, mb) -> stashed weight grads (zb)
            "contrib": {},  # (mb, s) -> weight-grad contribution
            "fetch": {},    # (vid, mb) -> fetched value
        }
        zb = [False]

        def gfetch_for(s: int):
            ex = self._execs[s]
            out = []
            for i in ex.diff_fetch_idx:
                v = ex.stage.fetch[i]
                if v.vid == self.loss_id:
                    # d(mean over microbatches)/d(loss_mb) = 1/m
                    out.append(jnp.asarray(1.0 / m, dtype=v.dtype))
                else:
                    out.append(jnp.zeros(v.shape, v.dtype))
            return tuple(out)

        def run_step(st: ScheduleStep):
            k, s, mb = st
            ex = self._execs[s]
            if k == "F":
                recvs = state["recv"].pop((s, mb), ())
                sends, fetches = ex.fwd(params[s], feeds[s][mb], recvs)
                if want_grad:
                    state["saved"][(s, mb)] = recvs
                if s < S - 1:
                    state["recv"][(s + 1, mb)] = self._transfer(
                        sends, s + 1)
                for v, val in zip(ex.stage.fetch, fetches):
                    state["fetch"][(v.vid, mb)] = val
                return (sends, fetches)
            if k == "B":
                gsends = (state["gsend"].pop((s, mb))
                          if s < S - 1 else ())
                recvs = state["saved"].pop((s, mb))
                gp, gr = ex.bwd(params[s], feeds[s][mb], recvs,
                                gsends, gfetch_for(s))
                if s > 0:
                    state["gsend"][(s - 1, mb)] = self._transfer(
                        gr, s - 1)
                if zb[0]:
                    state["wstash"][(s, mb)] = gp
                else:
                    state["contrib"][(mb, s)] = gp
                return (gp, gr)
            # W: apply the weight gradient stashed by this step's B
            gp = state["wstash"].pop((s, mb))
            state["contrib"][(mb, s)] = gp
            return gp

        return state, run_step, zb

    def _reduce(self, state, m: int):
        """Deterministic loss / gradient reduction: microbatch
        ascending, stage descending — identical regardless of the
        order the schedule executed the steps in."""
        S = self.partition.num_stages
        grads: Dict[int, object] = {}
        for mb in range(m):
            for s in range(S - 1, -1, -1):
                gp = state["contrib"].pop((mb, s), None)
                if gp is None:
                    continue
                ex = self._execs[s]
                for idx, g in zip(ex.diff_param_idx, gp):
                    pid = ex.param_ids[idx]
                    prev = grads.get(pid)
                    if prev is None:
                        grads[pid] = g
                    else:
                        # a parameter shared across stages (tied
                        # embeddings): line the contributions up on one
                        # submesh before summing
                        gs = getattr(g, "sharding", None)
                        ps = getattr(prev, "sharding", None)
                        if gs is not None and ps is not None \
                                and gs != ps:
                            g = jax.device_put(g, ps)
                        grads[pid] = prev + g
        loss = None
        if self.loss_id is not None:
            total = state["fetch"][(self.loss_id, 0)]
            for mb in range(1, m):
                total = total + state["fetch"][(self.loss_id, mb)]
            loss = total / m
        return loss, grads

    def train_step(self, feed: Dict[str, object],
                   num_microbatches: int, *,
                   collect_timing: bool = False,
                   _table=None):
        """One pipelined optimization step: forward + backward every
        microbatch under the schedule, reduce the loss (mean over
        microbatches) and the parameter gradients.

        Returns ``(loss, grads, stats)`` — ``grads`` maps captured
        parameter value id -> gradient array; ``stats`` carries the
        schedule table size, per-stage peak in-flight microbatches,
        the analytical bubble fraction, and (with
        ``collect_timing=True``) per-step durations plus the measured
        bubble from replaying them through the event simulation.
        """
        if self.loss_id is None:
            raise ValueError("train_step requires loss_id")
        m = int(num_microbatches)
        S = self.partition.num_stages
        table = _table if _table is not None else build_schedule(
            self.schedule, S, m)
        state, run_step, zb = self._make_steps(feed, m, want_grad=True)
        zb[0] = any(st.kind == "W" for steps in table for st in steps)
        timings = {} if collect_timing else None
        self._execute_table(table, run_step, timings)
        if state["wstash"]:
            raise RuntimeError(
                f"schedule finished with unapplied weight-grad "
                f"stashes: {sorted(state['wstash'])}")
        loss, grads = self._reduce(state, m)
        stats = {
            "schedule": self.schedule,
            "num_stages": S,
            "num_microbatches": m,
            "steps": sum(len(x) for x in table),
            "peak_inflight": peak_inflight(table),
            "analytical_bubble": analytical_bubble(self.schedule, S, m),
            "fetches": {vid: [state["fetch"].get((vid, mb))
                              for mb in range(m)]
                        for vid in self.partition.fetch_ids},
        }
        if timings is not None:
            stats["timings"] = timings
            stats["measured_bubble"] = simulate(
                table, durations=timings)["bubble"]
        return loss, grads, stats

    def run_unpipelined(self, feed: Dict[str, object],
                        num_microbatches: int):
        """Reference execution: per microbatch, forward through every
        stage then backward through every stage, sequentially — the
        same jitted stage functions and the same reduction order, so
        every schedule must match it bitwise."""
        if self.loss_id is None:
            raise ValueError("run_unpipelined requires loss_id")
        m = int(num_microbatches)
        S = self.partition.num_stages
        state, run_step, _zb = self._make_steps(feed, m,
                                                want_grad=True)
        for mb in range(m):
            for s in range(S):
                run_step(ScheduleStep("F", s, mb))
            for s in range(S - 1, -1, -1):
                run_step(ScheduleStep("B", s, mb))
        return self._reduce(state, m)

    def forward(self, feed: Dict[str, object],
                num_microbatches: int = 1):
        """Forward-only pipeline (inference): returns ``{fetch value
        id: [per-microbatch values]}``."""
        m = int(num_microbatches)
        S = self.partition.num_stages
        state, run_step, _zb = self._make_steps(feed, m,
                                                want_grad=False)
        for mb in range(m):
            for s in range(S):
                run_step(ScheduleStep("F", s, mb))
        return {vid: [state["fetch"].get((vid, mb))
                      for mb in range(m)]
                for vid in self.partition.fetch_ids}
