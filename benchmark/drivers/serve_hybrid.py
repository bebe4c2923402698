"""Serving cells of a hybrid decoder (``arch`` ``nemotron_h``: Mamba-2,
attention and latent-expert blocks) through ``serving.Router`` -> one
``inference.PagedEngine`` replica.

This kind brings only what the architecture needs: the model, its table of
weights (``lib/weights_nemotron_h.py``), its plain reference and a read of
the engine's expert counters around the window. The load generator, the
window's reduction, the sample that is checked, the record dump and every
requirement of the verdict are ``drivers/serve.py``'s own: ``run`` and
``control`` below call that module's with this kind's ``build`` /
``compare_with_reference`` in their place, so the two serving kinds cannot
drift. ``ctx["kind"]`` stays ``"serve"``.
"""
from __future__ import annotations

import contextlib
import time

from benchmark.drivers import serve
from benchmark.lib import weights_nemotron_h as weights_lib
from benchmark.lib.harness import log

_GLOBAL = {"model.embed_tokens.weight": "embed",
           "model.norm_f.weight": "norm_f", "lm_head.weight": "lm_head"}
_BLOCK = {
    "norm.weight": "norm",
    # Mamba-2
    "mixer.in_proj.weight": "in_proj", "mixer.conv_weight": "conv_w",
    "mixer.conv_bias": "conv_b", "mixer.A_log": "A_log",
    "mixer.dt_bias": "dt_bias", "mixer.D": "D",
    "mixer.norm_weight": "gated_norm", "mixer.out_proj.weight": "out_proj",
    # attention
    "mixer.q_proj.weight": "q", "mixer.k_proj.weight": "k",
    "mixer.v_proj.weight": "v", "mixer.o_proj.weight": "o",
    # latent experts
    "mixer.gate_weight": "gate",
    "mixer.e_score_correction_bias": "e_score_correction_bias",
    "mixer.latent_down.weight": "latent_down",
    "mixer.latent_up.weight": "latent_up", "mixer.w1": "w1",
    "mixer.w2": "w2", "mixer.shared_up.weight": "shared_up",
    "mixer.shared_down.weight": "shared_down"}


def param_key(param_name: str):
    if param_name in _GLOBAL:
        return (-1, _GLOBAL[param_name])
    _model, _layers, layer, leaf = param_name.split(".", 3)
    return (int(layer), _BLOCK[leaf])


def model_config(cfg: dict):
    """The program's config from the configuration file's published keys."""
    from paddle_tpu.models import NemotronHConfig

    if cfg["arch"] != "nemotron_h":
        raise SystemExit(f"serve_hybrid driver has no model for arch "
                         f"{cfg['arch']!r}")
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise SystemExit("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise SystemExit("experts_held and n_routed_experts (the experts "
                         "held here) disagree")
    same = ("vocab_size hidden_size hybrid_override_pattern "
            "num_attention_heads num_key_value_heads head_dim rope_theta "
            "mamba_num_heads mamba_head_dim n_groups ssm_state_size "
            "conv_kernel chunk_size time_step_min time_step_max "
            "time_step_floor num_experts_per_tok moe_latent_size "
            "moe_intermediate_size moe_shared_expert_intermediate_size "
            "routed_scaling_factor norm_topk_prob layer_norm_epsilon "
            "initializer_range").split()
    return NemotronHConfig(
        n_routed_experts=cfg["router_width"], experts_held=(lo, hi),
        max_seq_len=cfg["engine"]["context"],
        **{k: cfg[k] for k in same if k in cfg})


def put_weights(model, made: dict):
    """Put ``{(layer, name): array}`` into the model's parameters (a model
    still lazy gets them as its initialiser; a live one has them swapped
    in)."""
    made = dict(made)
    for name, p in model.named_parameters():
        arr = made.pop(param_key(name))
        if tuple(arr.shape) != tuple(p.shape):
            raise RuntimeError(f"{name}: table has {tuple(arr.shape)}, "
                               f"model has {tuple(p.shape)}")
        if getattr(p, "_lazy_init", None) is not None:
            p._lazy_init = (lambda _s, _d, a=arr: a, tuple(arr.shape),
                            arr.dtype)
        else:
            p._swap_payload(arr)
    if made:
        raise RuntimeError(f"weights without a parameter: {sorted(made)}")


def build(cfg: dict, seed: int):
    """``(router, replica, model)``: the seed's weights made on the device
    a layer a call, one warmed PagedEngine behind a Router."""
    import jax
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.models import NemotronHForCausalLM
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer
    from paddle_tpu.serving import Router, SchedulerConfig

    eng = cfg["engine"]
    with LazyGuard():
        model = NemotronHForCausalLM(model_config(cfg))
    log("model described")
    put_weights(model, weights_lib.make(cfg, seed, "bfloat16"))
    materialize_layer(model)
    jax.block_until_ready([p._data for p in model.parameters()])
    log("weights made")
    budget = eng.get("prefill_token_budget")
    replica = PagedEngine(
        model, max_batch=eng["max_batch"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"],
        max_blocks_per_seq=eng["context"] // eng["block_size"],
        scheduler=(SchedulerConfig(prefill_token_budget=budget)
                   if budget else None))
    log("engine built")
    router = Router([replica]).warmup()
    log("engine warm")
    return router, replica, model


#: the generator itself, kept by name: inside ``_in_serves_place``
#: ``serve.drive`` is this module's wrapper
serve_drive = serve.drive


def drive(router, replica, plan, seconds, **kw):
    """``serve.drive`` with the engine's expert counters read before and
    after it (two host reads a run, none inside the window's ticks): the
    window's records gain ``expert_load``, per expert layer the tokens
    each held expert received in the window."""
    import numpy as np

    before = replica.expert_load()
    win = serve_drive(router, replica, plan, seconds, **kw)
    after = replica.expert_load()
    if before is not None and after is not None:
        win["expert_load"] = (np.asarray(after["tokens"])
                              - np.asarray(before["tokens"])).tolist()
    return win


def compare_with_reference(cfg, seed, sample, verdict, control=False):
    from benchmark.reference import nemotron_h as ref
    t0 = time.perf_counter()
    got = ref.served_token_gaps(
        cfg, seed, [r.req["prompt"] for r in sample],
        [r.req["served"] for r in sample], cfg["engine"]["context"],
        control=control)
    log(f"{'control' if control else 'reference'} over {len(sample)} "
        f"requests, {got['positions']} served tokens: "
        f"{time.perf_counter() - t0:.1f}s, top1 share "
        f"{got['top1_share']:.4f}, mean gap {got['logit_gap_mean']:.5f}, "
        f"widest {got['logit_gap_max']:.5f}")
    if verdict is not None:
        for name in ("logit_gap_mean", "logit_gap_max"):
            verdict.compare(name, got[name], cfg["check"][name])
    return got


@contextlib.contextmanager
def _in_serves_place():
    """``drivers/serve.py``'s ``run`` and ``control`` with this kind's
    model, reference and counter reads where they call their own."""
    mine = {"build": build, "drive": drive,
            "compare_with_reference": compare_with_reference}
    theirs = {name: getattr(serve, name) for name in mine}
    for name, fn in mine.items():
        setattr(serve, name, fn)
    try:
        yield
    finally:
        for name, fn in theirs.items():
            setattr(serve, name, fn)


def run(cell, seed, seconds, trace, devices, t_process, alter_token=None):
    with _in_serves_place():
        return serve.run(cell, seed, seconds, trace, devices, t_process,
                         alter_token=alter_token)


def control(cell, seed, devices, seconds: float = 24.0):
    """A longer window than the dense kind's 8 s: the mix's shortest
    answers are 64 tokens, and eight requests have to finish."""
    with _in_serves_place():
        return serve.control(cell, seed, devices, seconds)
