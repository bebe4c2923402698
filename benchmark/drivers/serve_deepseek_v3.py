"""Serving cells of a DeepSeek-V3 decoder (``arch`` ``deepseek_v3``: latent
(MLA) attention whose cache is one row ``[c | k_r]`` a token a layer, a
dense layer, SwiGLU expert layers with a shared expert) through
``serving.Router`` -> one ``inference.PagedEngine`` replica.

As ``drivers/serve_exaone_moe.py``, this kind brings only what the
architecture needs: the model, its table of weights
(``lib/weights_deepseek_v3.py``), its plain reference and a read of the
engine's expert counters around the window (``serve_hybrid.drive``, which
reads no ``arch``). The load generator, the window's reduction, the sample
that is checked, the record dump and every requirement of the verdict are
``drivers/serve.py``'s own: ``run`` and ``control`` below call that module's
with this kind's ``build`` / ``compare_with_reference`` in their place.
``ctx["kind"]`` stays ``"serve"``.
"""
from __future__ import annotations

import contextlib
import time

from benchmark.drivers import serve, serve_hybrid
from benchmark.lib import weights_deepseek_v3 as weights_lib
from benchmark.lib.harness import log

_GLOBAL = {"model.embed_tokens.weight": "embed",
           "model.norm.weight": "norm", "lm_head.weight": "lm_head"}
_BLOCK = {
    "input_layernorm.weight": "input_norm",
    "self_attn.q_proj.weight": "q",
    "self_attn.kv_a_proj_with_mqa.weight": "kv_a",
    "self_attn.kv_a_layernorm.weight": "kv_a_norm",
    "self_attn.kv_b_proj.weight": "kv_b", "self_attn.o_proj.weight": "o",
    "post_attention_layernorm.weight": "post_norm",
    # dense layer
    "mlp.gate_proj.weight": "gate", "mlp.up_proj.weight": "up",
    "mlp.down_proj.weight": "down",
    # expert layer
    "mlp.gate_weight": "router",
    "mlp.e_score_correction_bias": "e_score_correction_bias",
    "mlp.w_gate": "w_gate", "mlp.w_up": "w_up", "mlp.w_down": "w_down",
    "mlp.shared_gate.weight": "shared_gate",
    "mlp.shared_up.weight": "shared_up",
    "mlp.shared_down.weight": "shared_down"}


def param_key(param_name: str):
    if param_name in _GLOBAL:
        return (-1, _GLOBAL[param_name])
    _model, _layers, layer, leaf = param_name.split(".", 3)
    return (int(layer), _BLOCK[leaf])


def model_config(cfg: dict):
    """The program's config from the configuration file's published keys."""
    from paddle_tpu.models import DeepseekV3Config

    if cfg["arch"] != "deepseek_v3":
        raise SystemExit(f"serve_deepseek_v3 driver has no model for arch "
                         f"{cfg['arch']!r}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise SystemExit("experts_held and n_routed_experts (the experts "
                         "held here) disagree")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise SystemExit("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    same = ("vocab_size hidden_size num_hidden_layers num_attention_heads "
            "q_lora_rank kv_lora_rank qk_nope_head_dim qk_rope_head_dim "
            "v_head_dim rope_theta rope_interleave rope_scaling "
            "intermediate_size first_k_dense_replace moe_layer_freq "
            "num_experts_per_tok n_shared_experts moe_intermediate_size "
            "routed_scaling_factor norm_topk_prob n_group topk_group "
            "rms_norm_eps initializer_range").split()
    return DeepseekV3Config(
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
    from paddle_tpu.models import DeepseekV3ForCausalLM
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer
    from paddle_tpu.serving import Router, SchedulerConfig

    eng = cfg["engine"]
    with LazyGuard():
        model = DeepseekV3ForCausalLM(model_config(cfg))
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


def compare_with_reference(cfg, seed, sample, verdict, control=False):
    from benchmark.reference import deepseek_v3 as ref
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
    model and reference, and the hybrid kind's counter reads, where they
    call their own."""
    mine = {"build": build, "drive": serve_hybrid.drive,
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


def control(cell, seed, devices, seconds: float = 50.0):
    """The cell's own window: the lanes fill over its first half, the
    shortest answers are 64 tokens behind prompts of thousands, and eight
    requests have to finish."""
    with _in_serves_place():
        return serve.control(cell, seed, devices, seconds)
