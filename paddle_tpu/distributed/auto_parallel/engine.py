"""Auto-parallel static Engine — whole-program distributed compilation.

Capability parity with the reference static planner entry (reference:
python/paddle/distributed/auto_parallel/static/engine.py — Engine(model,
loss, optimizer, strategy) with prepare/fit/evaluate/predict compiling one
distributed program via completion/partitioner/reshard). TPU-native: the
"planner" IS the GSPMD partitioner — the Engine jits ONE train step
(forward+backward+update) over the global mesh; parameter/input shardings
(from shard_tensor/fleet layers or the default data-parallel annotation)
propagate through XLA, which inserts every collective. completion =
sharding propagation, partitioner = SPMD partitioner, reshard =
device_put/with_sharding_constraint.
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.tensor import Tensor
from ...observability import trace as _trace
from .. import mesh as mesh_mod

_ENGINE_COUNTER = itertools.count()


class Engine:
    def __init__(self, model, loss=None, optimizer=None, metrics=None,
                 strategy=None, mesh=None, in_specs=None,
                 param_specs=None, placement=None, prefetch=None):
        self._model = model
        self._loss = loss
        self._optimizer = optimizer
        self._metrics = list(metrics) if metrics else []
        self._strategy = strategy
        if mesh is not None and hasattr(mesh, "jax_mesh"):
            mesh = mesh.jax_mesh()  # ProcessMesh -> jax Mesh
        self._mesh = mesh if mesh is not None else mesh_mod.get_mesh()
        # SPMD auto-sharding (distributed.spmd): with mesh= given, the
        # whole train step traces under a propagation scope — per-op
        # spmd_rules annotate every activation from the input/param
        # placements, completion/partitioner/reshard all via GSPMD.
        self._spmd_auto = mesh is not None
        self._spmd_in_specs = in_specs
        self._spmd_param_specs = param_specs
        # placement="auto": the auto-parallel planner
        # (distributed.planner) picks param_specs/in_specs itself on
        # the first batch — candidate search over the sharding rules,
        # scored by the round-12 cost model. Explicit in_specs/
        # param_specs arguments pin their half of the search.
        if placement not in (None, "auto"):
            raise ValueError(f"placement={placement!r} (only 'auto')")
        if placement == "auto" and mesh is None:
            raise ValueError("placement='auto' requires mesh=")
        self._placement = placement
        #: PlanResult of the auto placement (filled at first fit batch)
        self.placement_plan = None
        #: propagation stats of the traced step (filled at prepare-time
        #: trace; the acceptance bar is fallback == {})
        self.spmd_stats = None
        #: fusion-pass stats of the traced step (FLAGS_enable_fusion)
        self.fusion_stats = None
        self._params = [p for p in model.parameters()
                        if not p.stop_gradient]
        # prefetch (None = FLAGS_prefetch at use time) double-buffers the
        # input pipeline (io.DevicePrefetcher) so the next batch transfers
        # during the current step.
        self._prefetch_arg = prefetch
        self._train_step = None
        self._eval_step = None
        self.history: List[float] = []
        #: host totals (int64) of the device counters its steps carried
        #: (``observability.trace.STEP_COUNTERS``), over every ``fit`` call
        self.step_counters: dict = {}
        self._step_counter_decl: dict = {}   # what the running fit carries
        #: this Engine's name and ``fit`` calls in the start-up record
        self._startup_name = f"engine{next(_ENGINE_COUNTER)}"
        self._fit_calls = 0

    # ----------------------------------------------------------- compile
    @_trace.in_startup_phase("startup.prepare")
    def prepare(self, inputs_spec=None, labels_spec=None, mode="train"):
        """Build + cache the jitted SPMD step (reference engine.prepare
        compiles the distributed program).

        The update rule is the REAL optimizer package's functional core
        (``Optimizer._tree_step``), traced into the SPMD program — every
        optimizer in the suite works here, with one implementation, not a
        private re-derivation. The learning rate enters as a traced
        scalar, so LR schedulers tick without retracing.
        """
        from ...optimizer import SGD, Optimizer

        params = self._params
        model, loss_fn = self._model, self._loss
        opt = self._optimizer
        if opt is None:
            opt = SGD(learning_rate=1e-3, parameters=params)
        if not isinstance(opt, Optimizer) or \
                type(opt)._update is Optimizer._update:
            raise TypeError(
                f"Engine requires an optimizer with a functional update "
                f"rule (Optimizer._update); {type(opt).__name__} steps "
                f"imperatively (e.g. LBFGS line search) and cannot be "
                f"compiled into one SPMD program")
        self._opt = opt

        # static per-param attributes, resolved once at compile time
        lr_mults = tuple(float(getattr(p, "optimize_attr", {})
                               .get("learning_rate", 1.0)) for p in params)
        wd_flags = tuple(opt._wd_flag(p) for p in params)
        # ParamAttr-level regularizers take priority over the
        # optimizer-level decay (which _wd_flag already gates off for
        # these params) and must fold into the traced grads exactly as
        # eager Optimizer.step folds them — dropping them here silently
        # diverges Engine training from eager training
        from ...regularizer import L1Decay
        reg_terms = tuple(
            (isinstance(p.regularizer, L1Decay),
             float(getattr(p.regularizer, "_coeff", 0.0)))
            if getattr(p, "regularizer", None) is not None else None
            for p in params)

        def init_opt_state(param_arrays):
            states = [opt._init_state(p) for p in params]
            masters = [None] * len(params)  # fp32 params: no master copies
            state = (jnp.asarray(0, jnp.int32), masters, states)
            # device counters the model's layers declare ride the donated
            # state as a fourth entry, only while metrics are on: off, the
            # step is the program it was and carries none
            self._step_counter_decl = self._declared_step_counters()
            if self._step_counter_decl:
                state += ({name: jnp.zeros(c.shape, c.dtype) for name, c
                           in self._step_counter_decl.items()},)
            return state

        self._init_opt_state = init_opt_state

        def engine_train_step(param_arrays, opt_state, lr, x, y):
            t, masters, states, *counters = opt_state

            def f(pa):
                originals = [p._data for p in params]
                for p, a in zip(params, pa):
                    p._data = a
                try:
                    if not counters:
                        return self._traced_loss(model, loss_fn, params,
                                                 x, y), {}
                    with _trace.step_counters() as counted:
                        loss = self._traced_loss(model, loss_fn, params,
                                                 x, y)
                    return loss, counted.values
                finally:
                    for p, o in zip(params, originals):
                        p._data = o

            (loss, counted), grads = jax.value_and_grad(
                f, has_aux=True)(param_arrays)
            t = t + 1
            if opt._grad_clip is not None:
                pairs = opt._grad_clip(
                    [(p, Tensor(g)) for p, g in zip(params, grads)])
                grads = [g._data for _, g in pairs]
            if any(rt is not None for rt in reg_terms):
                # same fold order as eager step: clip first, then the
                # per-param regularizer term
                grads = [
                    g if rt is None else
                    g + rt[1] * (jnp.sign(w) if rt[0] else w).astype(g.dtype)
                    for g, rt, w in zip(grads, reg_terms, param_arrays)]
            with jax.named_scope("optimizer"):
                new_p, new_m, new_st = opt._tree_step(
                    lr, t, param_arrays, grads, masters, states, lr_mults,
                    wd_flags)
            new_state = (t, new_m, new_st)
            if counters:
                new_state += ({name: total + counted[name]
                               for name, total in counters[0].items()},)
            return loss, new_p, new_state

        # The step owns the state it is given: params + optimizer state
        # are donated, so XLA writes the updated values into their HBM.
        # A launch then allocates no new state buffers (it need not wait
        # for the running step to free its inputs, so it overlaps it) and
        # the high-water holds the state once, not twice. fit() re-binds
        # the returned arrays every step and writes the latest live ones
        # back into the Parameters in a finally block, so a mid-epoch
        # abort leaves the model usable; an array taken from a Parameter
        # BEFORE fit is dead after the first step, and reading it raises
        # core.donation's error naming this site.
        # The functions' names are the programs' names on the trace's
        # ``XLA Modules`` line (jit_engine_train_step, jit_engine_eval_step)
        self._train_step = jax.jit(engine_train_step,
                                   donate_argnums=(0, 1))

        def engine_eval_step(param_arrays, x, y):
            originals = [p._data for p in params]
            for p, a in zip(params, param_arrays):
                p._data = a
            try:
                out = model(Tensor(x))
                return loss_fn(out, Tensor(y))._data, out._data
            finally:
                for p, o in zip(params, originals):
                    p._data = o

        self._eval_step = jax.jit(engine_eval_step)
        return self

    def _declared_step_counters(self) -> dict:
        """``{name: StepCounter}`` over the model's layers (a Layer with a
        ``step_counters()``), empty while metrics are off."""
        from ...observability import metrics as _metrics
        out = {}
        if _metrics.enabled() and hasattr(self._model, "sublayers"):
            for layer in self._model.sublayers(include_self=True):
                declare = getattr(layer, "step_counters", None)
                if callable(declare):
                    out.update(declare())
        return out

    def _read_step_counters(self, opt_state):
        """The epoch's counts to the host (with the loss's read), their
        exporters called, the device counters zeroed where they lie (``a -
        a`` keeps the placement the step gave them, so the step is not
        traced again; like the running loss sum's add it is a tiny program
        of its own). Returns the state to go on with."""
        counters = opt_state[3]
        fresh = {name: np.asarray(a).astype(np.int64)  # tpulint: disable=TPU104 — telemetry-by-design: one host read an epoch, beside the loss's, under FLAGS_enable_metrics only
                 for name, a in counters.items()}
        for name, value in fresh.items():
            held = self.step_counters.get(name)
            self.step_counters[name] = value if held is None \
                else held + value
            export = self._step_counter_decl[name].export
            if export is not None:
                export(value)
        return opt_state[:3] + ({name: a - a
                                 for name, a in counters.items()},)

    def _traced_loss(self, model, loss_fn, params, x, y):
        """One forward+loss inside the traced step — under SPMD auto
        mode it runs in a propagation scope so every op's spmd_rule
        annotates its outputs (see distributed.spmd)."""
        from ...compile import fusion as _fusion
        if not self._spmd_auto:
            loss_t, self.fusion_stats = _fusion.rewrite_traced(
                lambda: loss_fn(model(Tensor(x)), Tensor(y)))
            return loss_t._data
        from .. import spmd as spmd_mod
        sc = spmd_mod.trace_scope(self._mesh)
        with sc:
            for p in params:
                spec = spmd_mod.param_spec_of(p, self._spmd_param_specs)
                if spec is not None:
                    sc.seed(p, spec)
            xt, yt = Tensor(x), Tensor(y)
            in_specs = self._spec_pair()
            if in_specs[0] is not None:
                sc.seed(xt, in_specs[0])
            if in_specs[1] is not None:
                sc.seed(yt, in_specs[1])
            # fusion inside the propagation scope: the fused re-emits
            # dispatch through the scope's hook, so their spmd_rules
            # annotate the fused program
            loss_t, self.fusion_stats = _fusion.rewrite_traced(
                lambda: loss_fn(model(xt), yt))
            loss = loss_t._data
        self.spmd_stats = dict(sc.stats)
        return loss

    def _ensure_auto_plan(self, x, y):
        """placement='auto': run the planner on the first batch's
        shapes — candidate search + cost-model scoring — and adopt the
        winning (param_specs, in_specs) before the step compiles."""
        if self._placement != "auto" or self.placement_plan is not None:
            return
        from .. import planner as planner_mod
        model, loss_fn = self._model, self._loss

        def step_loss(xt, yt):
            return loss_fn(model(xt), yt)

        res = planner_mod.plan(
            step_loss, self._mesh, in_specs=self._spmd_in_specs,
            example_inputs=(x, y), model=model)
        self.placement_plan = res
        res.apply(model)  # device_put + stamp the winning placement
        if self._spmd_param_specs is None:
            self._spmd_param_specs = res.param_specs
        if self._spmd_in_specs is None:
            self._spmd_in_specs = res.in_specs
        return res

    def _spec_pair(self):
        """Normalize ``in_specs`` to an (x_spec, y_spec) pair. A bare
        PartitionSpec is ATOMIC (it subclasses tuple, so a plain
        len==2 test would shred P('data', None) into garbage per-input
        entries) and broadcasts to both inputs."""
        from jax.sharding import PartitionSpec
        specs = self._spmd_in_specs
        if specs is None:
            return (None, None)
        if isinstance(specs, PartitionSpec) \
                or not isinstance(specs, (list, tuple)) \
                or len(specs) != 2:
            return (specs, specs)
        return tuple(specs)

    # ------------------------------------------------------------- data
    def _shard_batch(self, arr, which: int = 0):
        if self._spmd_auto and self._spmd_in_specs is not None:
            # auto mode: the batch lands exactly where the propagation
            # seeded it (in_specs), whatever the mesh axes are named
            spec = self._spec_pair()[which]
            if spec is None:
                return jnp.asarray(arr)
            return jax.device_put(jnp.asarray(arr),
                                  NamedSharding(self._mesh, spec))
        axes = tuple(a for a in ("dp", "sharding")
                     if a in self._mesh.axis_names
                     and int(self._mesh.shape[a]) > 1)
        if not axes:
            return jnp.asarray(arr)
        spec = P(axes if len(axes) > 1 else axes[0])
        return jax.device_put(jnp.asarray(arr),
                              NamedSharding(self._mesh, spec))

    def _replicate_over_mesh(self, tree):
        """Default data-parallel placement of params + optimizer state:
        leaves still on one device are committed REPLICATED over the
        mesh before the first step. Left where model init put them, the
        first step compiles for single-device inputs and the second for
        the replicated arrays the first returned — two compiles of the
        same step. Leaves already laid out over the mesh (shard_tensor,
        fleet layers) keep their placement."""
        replicated = NamedSharding(self._mesh, P())

        def place(a):
            if isinstance(a, jax.Array) and len(a.sharding.device_set) == 1:
                return jax.device_put(a, replicated)
            return a

        return jax.tree_util.tree_map(place, tree)

    def dataloader(self, dataset, batch_size=32, shuffle=False,
                   mode="train"):
        from ...io import DataLoader
        return DataLoader(dataset, batch_size=batch_size, shuffle=shuffle)

    # ------------------------------------------------------------ running
    def fit(self, train_data, epochs=1, batch_size=32, steps_per_epoch=None,
            log_freq=10, verbose=0):
        args = {"engine": self._startup_name, "call": self._fit_calls,
                "epochs": epochs, "steps": 0, "state_placed_s": None,
                "first_step_s": None}
        self._fit_calls += 1
        with _trace.startup_phase("startup.fit_call", args) as call, \
                contextlib.ExitStack() as setup_span:
            setup_span.enter_context(_trace.boundary("fit.setup"))
            return self._fit(setup_span, call, train_data, epochs,
                             batch_size, steps_per_epoch, log_freq, verbose)

    def startup_summary(self) -> dict:
        """Where this process's seconds before this Engine's first step
        went (``observability.trace.startup_summary``): the operator's
        question of a trainer, as ``PagedEngine.health()["startup"]`` is of
        a replica."""
        return _trace.startup_summary(self._startup_name)

    def _fit(self, setup_span, call, train_data, epochs, batch_size,
             steps_per_epoch, log_freq, verbose):
        """``fit`` under its ``fit.setup`` span, which ``_fit`` closes at the
        first wait for a batch (``ExitStack.close`` is idempotent).
        ``call`` is the call's open ``startup.fit_call`` phase."""
        if self._placement == "auto" and self.placement_plan is None:
            # plan on the first batch's shapes BEFORE the step compiles
            peek = next(iter(self.dataloader(train_data, batch_size)),
                        None)
            if peek is not None:
                xs, ys = peek[0], peek[-1]
                self._ensure_auto_plan(
                    xs.numpy() if isinstance(xs, Tensor) else np.asarray(xs),
                    ys.numpy() if isinstance(ys, Tensor) else np.asarray(ys))
        if self._train_step is None:
            self.prepare()
        from ...core import donation as _donation
        from ...core import flags as _flags
        from ...io.prefetch import DevicePrefetcher
        from ...observability import fleet as _fleet
        from ...observability import goodput as _goodput
        from ...observability import sentinel as _sentinel
        from ...observability.perf import memory as _perf_mem
        from ...optimizer.lr import LRScheduler

        loader = self.dataloader(train_data, batch_size, shuffle=True)
        pa = [p._data for p in self._params]
        _donation.ensure_live(pa, "Engine.fit entry")
        _donation.ensure_distinct(
            ((p.name, a) for p, a in zip(self._params, pa)), "Engine.fit")
        # the first step donates the arrays the Parameters hold now (on a
        # mesh their replicated copies, which may share the first device's
        # buffer): the only donated buffers a caller can still hold a
        # reference to — every later step is given arrays that never left
        # this loop
        _donation.mark_donated(pa, "the Engine's donated train step")
        opt_state = self._init_opt_state(pa)
        if self._mesh.size > 1 and not self._spmd_auto:
            pa, opt_state = self._replicate_over_mesh((pa, opt_state))
        call.mark("state_placed_s")     # step built, state made and placed
        sched = getattr(self._opt, "_learning_rate", None)
        sched = sched if isinstance(sched, LRScheduler) else None
        use_prefetch = (bool(_flags.get_flag("prefetch"))
                        if self._prefetch_arg is None
                        else bool(self._prefetch_arg))
        # goodput ledger + anomaly sentinel: the job health plane. The
        # jit-cache size tells us which steps hide a trace+compile wall.
        led = _goodput.ledger().run_begin()
        snt = _sentinel.get()
        cache_size = getattr(self._train_step, "_cache_size", None)
        # async-stretch hygiene: with no scheduler the LR is constant —
        # transfer it ONCE instead of a host read + H2D per step (the
        # sentinel's host bucket must not be polluted by our own reads)
        lr_const = (None if sched is not None
                    else jnp.asarray(self._opt.get_lr(), jnp.float32))

        def place(batch):
            """Batch → placed (x, y) device arrays; under prefetch this
            runs on the producer thread, overlapping the current step."""
            xs, ys = batch[0], batch[-1]
            x = self._shard_batch(xs.numpy() if isinstance(xs, Tensor)
                                  else xs)
            y = self._shard_batch(ys.numpy() if isinstance(ys, Tensor)
                                  else ys, which=1)
            return x, y

        census_left = 2     # attributed HBM census on the first steps
        n_steps = 0         # fit.step's step_num, over the whole call
        try:
            for epoch in range(epochs):
                # loss stays a device scalar: no per-step host sync —
                # a running device-side sum (O(1) program regardless of
                # epoch length), materialized only at log intervals and
                # epoch end
                loss_sum, loss_n = None, 0
                it = iter(loader)
                batches = (DevicePrefetcher(it, place_fn=place)
                           if use_prefetch else (place(b) for b in it))
                setup_span.close()
                try:
                    step_i = 0
                    while True:
                        # an epoch's last fit.step holds only the fetch
                        # that finds the loader exhausted
                        with _trace.boundary("fit.step", step_num=n_steps):
                            with _trace.boundary("fit.next_batch"):
                                batch = next(batches, None)
                            if batch is None or (steps_per_epoch and
                                                 step_i >= steps_per_epoch):
                                break
                            x, y = batch
                            # fleet beacon: per-step wall time + windowed
                            # cross-rank skew gather — the straggler
                            # detector's feed. Resolved per step (like the
                            # fleet trainers) so reset_beacon() takes
                            # effect mid-fit.
                            led.step_begin()
                            bcn = _fleet.beacon()
                            bcn.step_begin()
                            # lr is a traced INPUT: schedulers tick without
                            # retracing (constant LR: placed once, pre-loop)
                            lr = (lr_const if lr_const is not None
                                  else jnp.asarray(self._opt.get_lr(),
                                                   jnp.float32))
                            n_sigs = cache_size() if cache_size else None
                            with _trace.boundary("fit.dispatch"):
                                loss, pa, opt_state = self._train_step(
                                    pa, opt_state, lr, x, y)
                            if call.args["first_step_s"] is None:
                                call.mark("first_step_s")   # ready
                            with _trace.boundary("fit.post_step"):
                                if n_sigs is not None \
                                        and cache_size() > n_sigs:
                                    # jit-cache miss: the (synchronous)
                                    # trace + XLA compile wall heads this
                                    # step's window
                                    led.bill_since_step_begin("compile")
                                    snt.note_compile(
                                        "initial" if n_sigs == 0
                                        else "retrace")
                                if sched is not None:
                                    sched.step()
                                loss_sum = loss if loss_sum is None \
                                    else loss_sum + loss
                                loss_n += 1
                                if census_left:
                                    # mid-flight census: the just-donated
                                    # buffers count 0, so the recorded
                                    # high-water holds the state once
                                    _perf_mem.update_high_water(
                                        "engine_step_donated")
                                    census_left -= 1
                                bcn.step_end()
                                snt.observe_step(led.step_end())
                                if verbose and step_i % log_freq == 0:
                                    print(f"[engine] epoch {epoch} step "
                                          f"{step_i} loss {float(loss):.4f}")  # tpulint: disable=TPU103 — the log-interval materialization IS the documented host boundary (async-loss contract)
                        step_i += 1
                        n_steps += 1
                finally:
                    if isinstance(batches, DevicePrefetcher):
                        batches.close()
                if loss_n:
                    # ONE host sync per epoch for the history mean
                    with _trace.boundary("fit.epoch_sync"):
                        self.history.append(
                            float(loss_sum) / loss_n)  # tpulint: disable=TPU103 — end-of-epoch history materialization (documented contract), not a per-step sync
                        if len(opt_state) == 4:
                            opt_state = self._read_step_counters(opt_state)
        finally:
            # write the trained arrays AND accumulator states back into
            # the eager optimizer, so a later opt.step()/state_dict()
            # continues from where the Engine left off. Runs on abort
            # too: the Parameters' pre-fit payloads were donated by the
            # first step — the latest live arrays must land back.
            call.args["steps"] = n_steps
            with _trace.boundary("fit.writeback"):
                t, _masters, states = opt_state[:3]
                self._opt._step_count = int(t)  # tpulint: disable=TPU103 — one end-of-fit writeback into the eager optimizer (documented contract), not a per-step sync
                for p, a, st in zip(self._params, pa, states):
                    p._data = a
                    self._opt._accumulators[id(p)] = st
        return self.history

    def evaluate(self, eval_data, batch_size=32, verbose=0):
        if self._eval_step is None:
            self.prepare()
        loader = self.dataloader(eval_data, batch_size)
        pa = [p._data for p in self._params]
        losses = []
        for batch in loader:
            xs, ys = batch[0], batch[-1]
            loss, _ = self._eval_step(
                pa, self._shard_batch(np.asarray(
                    xs.numpy() if isinstance(xs, Tensor) else xs)),
                self._shard_batch(np.asarray(
                    ys.numpy() if isinstance(ys, Tensor) else ys),
                    which=1))
            losses.append(float(loss))  # tpulint: disable=TPU103 — evaluate() aggregates per-batch losses on the host by contract
        return {"loss": float(np.mean(losses))}

    def predict(self, test_data, batch_size=32):
        outs = []
        self._model.eval()
        from ...io import DataLoader
        for batch in DataLoader(test_data, batch_size=batch_size):
            xs = batch[0] if isinstance(batch, (list, tuple)) else batch
            outs.append(np.asarray(self._model(  # tpulint: disable=TPU101,TPU104 — predict() returns host ndarrays by contract; materialization IS the op
                xs if isinstance(xs, Tensor) else Tensor(
                    jnp.asarray(xs))).numpy()))
        return np.concatenate(outs) if outs else np.empty((0,))


__all__ = ["Engine"]
