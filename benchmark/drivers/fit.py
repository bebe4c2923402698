"""Training cells: the configuration's model through
``distributed.auto_parallel.Engine.fit`` with ``optimizer.AdamW``, bf16 O1
autocast, the default DataLoader + DevicePrefetcher.

One Engine (the compiled step with its state) is built in set-up, driven from
the seed through its first three steps for the comparison with the plain
reference, and handed to the window. The window is ONE ``fit`` call of whole
epochs; each epoch ends in ``fit``'s own host read of the loss sum, and the
benchmark's dataset stamps the first fetch of every epoch, so the window runs
from the first fetch of the first epoch to the return of ``fit``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import check as check_lib
from benchmark.lib import harness, weights
from benchmark.lib.harness import log

#: program parameter name -> the weight table's (layer, leaf) key
_GPT2_GLOBAL = {"gpt.wte.weight": "wte", "gpt.wpe.weight": "wpe",
                "gpt.ln_f.weight": "ln_f.w", "gpt.ln_f.bias": "ln_f.b"}
_GPT2_BLOCK = {"ln1.weight": "ln_1.w", "ln1.bias": "ln_1.b",
               "attn.qkv_proj.weight": "attn.c_attn.w",
               "attn.qkv_proj.bias": "attn.c_attn.b",
               "attn.out_proj.weight": "attn.c_proj.w",
               "attn.out_proj.bias": "attn.c_proj.b",
               "ln2.weight": "ln_2.w", "ln2.bias": "ln_2.b",
               "mlp.fc1.weight": "mlp.c_fc.w", "mlp.fc1.bias": "mlp.c_fc.b",
               "mlp.fc2.weight": "mlp.c_proj.w",
               "mlp.fc2.bias": "mlp.c_proj.b"}


def gpt2_key(param_name: str):
    if param_name in _GPT2_GLOBAL:
        return (-1, _GPT2_GLOBAL[param_name])
    _gpt, _blocks, layer, leaf = param_name.split(".", 3)
    return (int(layer), _GPT2_BLOCK[leaf])


class TokenStream:
    """The benchmark's dataset: seeded random token rows, a fresh set every
    epoch whatever order the sampler asks for them in. Counts its fetches,
    so it knows where each epoch starts and stamps it; ``on_epoch(e)`` runs
    at the first fetch of epoch ``e`` (on the loader's thread)."""

    def __init__(self, seed, vocab, seq, rows_per_epoch, table_epochs):
        rng = np.random.default_rng([int(seed), 0x70CE])
        self.table = rng.integers(
            0, vocab, (table_epochs * rows_per_epoch, seq), dtype=np.int64)
        self.rows_per_epoch = rows_per_epoch
        self.table_epochs = table_epochs
        self.fetches = 0
        self.epoch_starts = []
        self.on_epoch = None

    def __len__(self):
        return self.rows_per_epoch

    def __getitem__(self, i):
        epoch, first = divmod(self.fetches, self.rows_per_epoch)
        self.fetches += 1
        if first == 0:
            self.epoch_starts.append(time.perf_counter())
            if self.on_epoch is not None:
                self.on_epoch(epoch)
        row = self.table[(epoch % self.table_epochs) * self.rows_per_epoch
                         + int(i)]
        return row, row

    def rows_of_epoch(self, epoch):
        lo = (epoch % self.table_epochs) * self.rows_per_epoch
        return self.table[lo:lo + self.rows_per_epoch]


def build(cfg: dict, seed: int, devices):
    """The Engine over the configuration's model with the seed's weights."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer

    if cfg["arch"] != "gpt2":
        raise SystemExit(f"fit driver has no model for arch {cfg['arch']!r}")
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
        lm = GPTForCausalLM(GPTConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            max_seq_len=cfg["n_positions"], recompute=run["recompute"]))
    made = weights.make("gpt2", cfg, seed, "float32")
    for name, p in lm.named_parameters():
        arr = made.pop(gpt2_key(name))
        p._lazy_init = (lambda _s, _d, a=arr: a, tuple(arr.shape), arr.dtype)
    if made:
        raise RuntimeError(f"weights without a parameter: {sorted(made)}")
    materialize_layer(lm)
    net = CausalLMLoss(lm)
    opt = paddle.optimizer.AdamW(
        learning_rate=run["learning_rate"], weight_decay=run["weight_decay"],
        beta1=run["beta1"], beta2=run["beta2"], epsilon=run["epsilon"],
        parameters=net.parameters())
    engine = Engine(net, loss=lambda loss, _labels: loss, optimizer=opt)
    names = [gpt2_key(n[len("lm."):]) for n, _p in net.named_parameters()]
    return engine, opt, names


def _norms(arrays):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda xs: jnp.stack(
        [jnp.linalg.norm(x.astype(jnp.float32)) for x in xs]))
    return np.asarray(fn(list(arrays)), np.float64)


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
    start = weights.make("gpt2", cfg, seed, "float32")
    delta = jax.jit(lambda ps, ss: jnp.stack(
        [jnp.linalg.norm(p - s) for p, s in zip(ps, ss)]))(
            [p._data for p in params], [start[k] for k in names])
    delta_norms = dict(zip(names, np.asarray(delta, np.float64)))
    del start
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}


def reference_numbers(cfg, seed, batches, calls, precision="float32"):
    import jax
    from benchmark.reference import gpt2 as ref
    with jax.default_matmul_precision("highest"):
        out = ref.follow(cfg, seed, batches, cfg["run"], calls, precision)
    gc.collect()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, break_step=None) -> dict:
    """One run of a training cell. ``break_step`` is the tests' hook: a
    function that wraps the Engine's compiled step."""
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
    log(f"reference: {reference_s:.1f}s losses {ref['losses']}")

    engine, opt, names = build(cfg, seed, devices)
    log("model and weights built")
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
    if trace:
        # the program's counters are compiled out unless this flag is on;
        # only the traced run, which reports no end-to-end metric, pays
        import paddle_tpu as paddle
        paddle.set_flags({"FLAGS_enable_metrics": True})
        def on_epoch(e, last=epochs - 1):
            if e == last:
                tracer.start()
        data.on_epoch = on_epoch
    log(f"warm; window of {epochs} epochs starts")
    stall0 = _stall_seconds()
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

    verdict.require("loss_finite", bool(np.all(np.isfinite(hist))), str(hist))
    verdict.require("loss_below_first", hist[-1] < got["losses"][0],
                    f"{hist[-1]} vs {got['losses'][0]}")
    verdict.require("no_compile_in_window",
                    clock.count == compiles0
                    and _step_cache(engine) == steps0 == 1,
                    f"{clock.count - compiles0} compiles, "
                    f"{_step_cache(engine)} step programs")

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
    }
    edges = data.epoch_starts + [t_end]
    log("epoch seconds", [round(b - a, 3) for a, b in zip(edges, edges[1:])])
    log(f"window {window_s:.2f}s {epochs} epochs "
        f"{tokens / window_s:.1f} tokens/s; reference {reference_s:.1f}s; "
        f"compile {clock.total:.1f}s in {clock.count} programs")
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


def _stall_seconds() -> float:
    from paddle_tpu.observability import metrics
    m = metrics.REGISTRY.get("paddle_tpu_prefetch_stall_seconds_total")
    return float(m.total()) if m is not None else 0.0


def _step_cache(engine) -> int:
    return int(engine._train_step._cache_size())
