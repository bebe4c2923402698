"""Training cells of a ``smallthinker`` configuration:
``SmallThinkerForCausalLM`` through ``distributed.auto_parallel.Engine.fit``
with ``optimizer.AdamW``, bf16 O1 autocast, the default DataLoader +
DevicePrefetcher: the shape of ``drivers/fit_lfm2_moe.py``, whose dataset,
window, checks and load counters it shares.

One Engine (the compiled step with its state) is built in set-up, driven from
the seed through its first three steps for the comparison with the plain
reference, and handed to the window. The window is ONE ``fit`` call of whole
epochs, from the first fetch of the first epoch to the return of ``fit``.
``ctx["kind"]`` stays ``"fit"``: the readers that need nothing of GPT-2's
configuration read this cell as they read the others.

A traced run switches the program's metrics on BEFORE the step is built, so
that the one step program of the process carries the expert-load counters
(`observability.trace.STEP_COUNTERS`); an untraced run's step carries none.
The load is handed on twice: ``expert_load`` over the window's steps, beside
what the window's clock measured, and ``expert_load_traced`` over the
``traced_steps`` of the last epoch, beside what the device trace measured
(the router of a one-chip share drifts towards the held experts, so the two
differ).

``run(..., break_model=fn)`` is the fault's hook, for the tests and the
builder's chip script: ``fn(lm)`` alters the built model before its step is
traced (the window argument dropped from one layer).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.drivers.fit import (TokenStream, _norms, _stall_seconds,
                                   _step_cache)
from benchmark.drivers.fit_lfm2_moe import _bytes_in_use, _expert_load
from benchmark.lib import check as check_lib
from benchmark.lib import harness, weights_smallthinker
from benchmark.lib.harness import log

#: program parameter leaf -> the weight table's leaf
_GLOBAL = {"model.embed_tokens.weight": "embed",
           "model.norm.weight": "final_norm",
           "lm_head.weight": "head"}
_BLOCK = {"input_layernorm.weight": "input_norm",
          "post_attention_layernorm.weight": "post_norm",
          "self_attn.q_proj.weight": "attn.q",
          "self_attn.k_proj.weight": "attn.k",
          "self_attn.v_proj.weight": "attn.v",
          "self_attn.o_proj.weight": "attn.o",
          "block_sparse_moe.gate_weight": "moe.router",
          "block_sparse_moe.w_gate": "moe.gate",
          "block_sparse_moe.w_up": "moe.up",
          "block_sparse_moe.w_down": "moe.down"}


def table_key(param_name: str):
    if param_name in _GLOBAL:
        return (-1, _GLOBAL[param_name])
    _model, _layers, layer, leaf = param_name.split(".", 3)
    return (int(layer), _BLOCK[leaf])


def model_config(cfg: dict):
    """The program's config from the configuration file's published keys."""
    from paddle_tpu.models import SmallThinkerConfig
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_layout", "sliding_window_layout", "sliding_window_size",
            "rope_theta", "max_position_embeddings",
            "moe_num_active_primary_experts", "moe_ffn_hidden_size",
            "moe_primary_router_apply_softmax", "norm_topk_prob",
            "rms_norm_eps", "tie_word_embeddings")
    return SmallThinkerConfig(
        moe_num_primary_experts=cfg["router_width"],
        experts_held=tuple(cfg["experts_held"]),
        initializer_range=cfg.get("initializer_range", 0.02),
        recompute=cfg["run"]["recompute"], **{k: cfg[k] for k in keys})


def build(cfg: dict, seed: int, devices, break_model=None):
    """The Engine over the configuration's model with the seed's weights."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import SmallThinkerForCausalLM
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer

    if cfg["arch"] != "smallthinker":
        raise SystemExit(f"fit_smallthinker driver has no model for arch "
                         f"{cfg['arch']!r}")
    paddle.seed(seed & 0x7FFFFFFF)
    np.random.seed(seed & 0xFFFFFFFF)      # the loader's shuffle
    mesh_mod.set_mesh(mesh_mod.build_mesh(devices=devices))

    class CausalLMLoss(nn.Layer):
        """Next-token loss of the wrapped LM under bf16 O1 autocast."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                _, loss = self.lm(ids, labels=ids)
            return loss

    run = cfg["run"]
    with LazyGuard():
        lm = SmallThinkerForCausalLM(model_config(cfg))
    made = weights_smallthinker.make(cfg, seed)
    for name, p in lm.named_parameters():
        arr = made.pop(table_key(name))
        p._lazy_init = (lambda _s, _d, a=arr: a, tuple(arr.shape), arr.dtype)
    if made:
        raise RuntimeError(f"weights without a parameter: {sorted(made)}")
    materialize_layer(lm)
    if break_model is not None:
        break_model(lm)
    net = CausalLMLoss(lm)
    opt = paddle.optimizer.AdamW(
        learning_rate=run["learning_rate"], weight_decay=run["weight_decay"],
        beta1=run["beta1"], beta2=run["beta2"], epsilon=run["epsilon"],
        parameters=net.parameters())
    engine = Engine(net, loss=lambda loss, _labels: loss, optimizer=opt)
    names = [table_key(n[len("lm."):]) for n, p in net.named_parameters()
             if not p.stop_gradient]
    return engine, opt, names


def first_steps(engine, opt, names, cfg, seed, data, calls):
    """Drive the Engine through its first steps, one step an epoch so that
    ``fit``'s history is the per-step loss, grouped into ``fit`` calls as
    ``calls`` says. Returns what the reference's ``follow`` returns."""
    import jax
    import jax.numpy as jnp

    params = engine._params
    losses, grad_norms = [], None
    for n_steps in calls:
        before = len(engine.history)
        engine.fit(data, epochs=n_steps, batch_size=data.rows_per_epoch)
        losses += [float(x) for x in engine.history[before:]]
        if grad_norms is None:
            # AdamW's first moment after ONE step from zero moments is
            # (1 - beta1) * g: the gradient as the optimizer got it
            m1 = [opt._accumulators[id(p)]["moment1"] for p in params]
            grad_norms = dict(zip(names, _norms(m1) / (1.0 - opt._beta1)))
        # a later fit call starts new moments; the ones written back here
        # would only sit in HBM beside them
        opt._accumulators.clear()
    start = weights_smallthinker.make(cfg, seed)
    delta = jax.jit(lambda ps, ss: jnp.stack(
        [jnp.linalg.norm(p - s) for p, s in zip(ps, ss)]))(
            [p._data for p in params], [start[k] for k in names])
    delta_norms = dict(zip(names, np.asarray(delta, np.float64)))
    del start
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}


def reference_numbers(cfg, seed, batches, calls, precision="float32"):
    import jax
    from benchmark.reference import smallthinker as ref
    with jax.default_matmul_precision("highest"):
        out = ref.follow(cfg, seed, batches, cfg["run"], calls, precision)
    gc.collect()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, break_step=None, break_model=None) -> dict:
    """One run of the cell. ``break_step`` and ``break_model`` are the
    faults' hooks: a function that wraps the Engine's compiled step, and
    one that alters the built model."""
    # a program without this model fails here, at once, not after the
    # reference has run
    import paddle_tpu.models.smallthinker  # noqa: F401
    cfg, traffic = cell["config"], cell["traffic"]
    batch, seq = traffic["batch"], traffic["seq_len"]
    steps_per_epoch = traffic["steps_per_epoch"]
    calls = traffic["check_calls"]
    n_check = sum(calls)
    clock = harness.CompileClock()
    verdict = check_lib.Verdict()

    # the reference first, before the program's state exists, and outside
    # setup_s: it is the check's cost, not the system's
    check_data = TokenStream(seed, cfg["vocab_size"], seq, batch, n_check)
    t0 = time.perf_counter()
    ref = reference_numbers(
        cfg, seed, [check_data.rows_of_epoch(e) for e in range(n_check)],
        calls)
    reference_s = time.perf_counter() - t0
    log(f"reference: {reference_s:.1f}s losses {ref['losses']}; device "
        f"holds {_bytes_in_use(devices) / 1e9:.3f} GB after it")

    if trace:
        # the step's counters exist only in a program traced with this on;
        # only the traced run, which reports no end-to-end metric, pays
        import paddle_tpu as paddle
        paddle.set_flags({"FLAGS_enable_metrics": True})
    engine, opt, names = build(cfg, seed, devices, break_model)
    log(f"model and weights built; device holds "
        f"{_bytes_in_use(devices) / 1e9:.3f} GB")
    if break_step is not None:
        engine.prepare()
        engine._train_step = break_step(engine._train_step)
    got = first_steps(engine, opt, names, cfg, seed, check_data, calls)
    log("first steps done")
    for name, value in check_lib.train_numbers(got, ref).items():
        verdict.compare(name, value, cfg["check"][name])
    step_est = max((check_data.epoch_starts[-1] - check_data.epoch_starts[-2])
                   if len(check_data.epoch_starts) > 1 else 1.0, 1e-3)
    # a two-step epoch: the running loss sum's add is a program of its own
    # that one-step epochs never run
    engine.fit(TokenStream(seed + 2, cfg["vocab_size"], seq, 2 * batch, 1),
               epochs=1, batch_size=batch)
    opt._accumulators.clear()

    # whole epochs that fit into --seconds (at least one)
    epochs = max(1, int(seconds / (steps_per_epoch * step_est)))
    data = TokenStream(seed + 1, cfg["vocab_size"], seq,
                       batch * steps_per_epoch,
                       min(epochs, traffic["table_epochs"]))
    tracer = harness.TraceWindow(harness.trace_dir(cell["cell"]["name"]))
    at_trace_start = {}
    if trace:
        def on_epoch(e, last=epochs - 1):
            if e == last:
                # ``fit`` has read every earlier epoch's counters by now:
                # what is added from here on is the traced epoch's
                at_trace_start["load"] = _expert_load(engine)
                tracer.start()
        data.on_epoch = on_epoch
    log(f"warm; window of {epochs} epochs starts")
    stall0 = _stall_seconds()
    load0 = _expert_load(engine)
    compiles0, steps0 = clock.count, _step_cache(engine)
    t_call = time.perf_counter()
    with harness.FreezeWatch() as watch:
        engine.fit(data, epochs=epochs, batch_size=batch)
    t_end = time.perf_counter()
    log(f"process stood still {watch.freezes} s")
    if trace:
        tracer.stop()
    t_start = data.epoch_starts[0]
    window_s = t_end - t_start
    tokens = epochs * steps_per_epoch * batch * seq
    hist = engine.history[-epochs:]
    load, load_traced = _expert_load(engine), None
    if load is not None and load0 is not None:
        if at_trace_start.get("load") is not None:
            load_traced = load - at_trace_start["load"]
        load = load - load0

    verdict.require("loss_finite", bool(np.all(np.isfinite(hist))), str(hist))
    verdict.require("loss_below_first", hist[-1] < got["losses"][0],
                    f"{hist[-1]} vs {got['losses'][0]}")
    verdict.require("no_compile_in_window",
                    clock.count == compiles0
                    and _step_cache(engine) == steps0 == 1,
                    f"{clock.count - compiles0} compiles, "
                    f"{_step_cache(engine)} step programs")
    if load is not None:
        # dropless: every pair the routers selected is counted, and what
        # landed here is what the held experts received
        steps = epochs * steps_per_epoch
        selected = (steps * batch * seq
                    * cfg["moe_num_active_primary_experts"])
        verdict.require(
            "no_pair_dropped",
            bool(np.all(load[:, -1] == selected)
                 and np.all(load[:, :-2].sum(axis=1) == load[:, -2])),
            f"selected {load[:, -1].tolist()} of {selected}, landed "
            f"{load[:, -2].tolist()}")

    metrics = {
        "train_tokens_per_s": tokens / window_s,
        "setup_s": (t_start - t_process) - reference_s,
    }
    ctx = {
        "kind": "fit", "config": cfg, "traffic": traffic,
        "chips": len(devices), "device_kind": devices[0].device_kind,
        "window_s": window_s, "tokens": tokens, "epochs": epochs,
        "steps_per_epoch": steps_per_epoch, "batch": batch, "seq_len": seq,
        "fit_call_s": t_end - t_call, "epoch_starts": data.epoch_starts,
        "t_end": t_end, "setup_compile_s": clock.total,
        "stall_s": _stall_seconds() - stall0,
        "trace": tracer.reduce() if trace else None,
        "traced_steps": steps_per_epoch,
        "expert_load": None if load is None else load.tolist(),
        "expert_load_traced": (None if load_traced is None
                               else load_traced.tolist()),
    }
    if load_traced is not None:
        log("landed pairs of the selected, by layer: window",
            (load[:, -2] / load[:, -1]).round(3).tolist(), "traced epoch",
            (load_traced[:, -2] / load_traced[:, -1]).round(3).tolist())
    edges = data.epoch_starts + [t_end]
    log("epoch seconds", [round(b - a, 3) for a, b in zip(edges, edges[1:])])
    log(f"window {window_s:.2f}s {epochs} epochs "
        f"{tokens / window_s:.1f} tokens/s; reference {reference_s:.1f}s; "
        f"compile {clock.total:.1f}s in {clock.count} programs; device "
        f"holds {_bytes_in_use(devices) / 1e9:.3f} GB between steps")
    del engine
    gc.collect()
    return {"correct": verdict.correct, "attempted": epochs * steps_per_epoch,
            "failed": 0, "metrics": metrics, "ctx": ctx,
            "numbers": verdict.numbers(),
            "device": harness.device_report(devices)}


def control(cell: dict, seed: int, devices) -> dict:
    """The control's readings: the reference in the precision below the
    configuration's, compared with the reference as a program would be."""
    cfg, traffic = cell["config"], cell["traffic"]
    calls = traffic["check_calls"]
    data = TokenStream(seed, cfg["vocab_size"], traffic["seq_len"],
                       traffic["batch"], sum(calls))
    batches = [data.rows_of_epoch(e) for e in range(sum(calls))]
    ref = reference_numbers(cfg, seed, batches, calls)
    low = reference_numbers(cfg, seed, batches, calls,
                            cfg["check"]["control_precision"])
    return check_lib.train_numbers(low, ref)
