"""Serving cells of an Olmo-Hybrid decoder (``arch`` ``olmo_hybrid``: gated
delta-rule linear-attention layers whose cache is one ``d_k x d_v`` float32
matrix a head a lane, beside full-attention layers that page their K/V)
through ``serving.Router`` -> one ``inference.PagedEngine`` replica.

As ``drivers/serve_hybrid.py``, this kind brings only what the architecture
needs: the model, its table of weights (``lib/weights_olmo_hybrid.py``) and
its plain reference. The load generator, the window's reduction, the sample
that is checked, the record dump and every requirement of the verdict are
``drivers/serve.py``'s own: ``run`` and ``control`` below call that module's
with this kind's ``build`` / ``compare_with_reference`` in their place.
``ctx["kind"]`` stays ``"serve"``.
"""
from __future__ import annotations

import contextlib
import time

from benchmark.drivers import serve
from benchmark.lib import weights_olmo_hybrid as weights_lib
from benchmark.lib.harness import log

_GLOBAL = {"model.embed_tokens.weight": "embed",
           "model.norm.weight": "norm", "lm_head.weight": "lm_head"}
_BLOCK = {
    # both mixers
    "mixer.q_proj.weight": "q", "mixer.k_proj.weight": "k",
    "mixer.v_proj.weight": "v", "mixer.o_proj.weight": "o",
    # gated delta rule
    "mixer.a_proj.weight": "a", "mixer.b_proj.weight": "b",
    "mixer.g_proj.weight": "g", "mixer.conv_weight": "conv_w",
    "mixer.A_log": "A_log", "mixer.dt_bias": "dt_bias",
    "mixer.o_norm_weight": "o_norm",
    # full attention
    "mixer.q_norm.weight": "q_norm", "mixer.k_norm.weight": "k_norm",
    # the block
    "post_attention_layernorm.weight": "post_attn_norm",
    "mlp.gate_proj.weight": "gate", "mlp.up_proj.weight": "up",
    "mlp.down_proj.weight": "down",
    "post_feedforward_layernorm.weight": "post_mlp_norm"}


def param_key(param_name: str):
    if param_name in _GLOBAL:
        return (-1, _GLOBAL[param_name])
    _model, _layers, layer, leaf = param_name.split(".", 3)
    return (int(layer), _BLOCK[leaf])


def model_config(cfg: dict):
    """The program's config from the configuration file's published keys."""
    from paddle_tpu.models import OlmoHybridConfig

    if cfg["arch"] != "olmo_hybrid":
        raise SystemExit(f"serve_olmo_hybrid driver has no model for arch "
                         f"{cfg['arch']!r}")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise SystemExit("layer_types and num_hidden_layers disagree")
    same = ("vocab_size hidden_size intermediate_size num_hidden_layers "
            "num_attention_heads num_key_value_heads layer_types "
            "linear_num_key_heads linear_num_value_heads linear_key_head_dim "
            "linear_value_head_dim linear_conv_kernel_dim "
            "linear_allow_neg_eigval rope_parameters attention_bias "
            "tie_word_embeddings rms_norm_eps initializer_range "
            "chunk_size").split()
    return OlmoHybridConfig(max_seq_len=cfg["engine"]["context"],
                            **{k: cfg[k] for k in same if k in cfg})


def build(cfg: dict, seed: int):
    """``(router, replica, model)``: the seed's weights made on the device
    a layer a call, one warmed PagedEngine behind a Router."""
    import jax
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.models import OlmoHybridForCausalLM
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer
    from paddle_tpu.serving import Router, SchedulerConfig

    eng = cfg["engine"]
    with LazyGuard():
        model = OlmoHybridForCausalLM(model_config(cfg))
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


def put_weights(model, made: dict):
    """``serve_hybrid.put_weights`` under this kind's parameter names."""
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


def compare_with_reference(cfg, seed, sample, verdict, control=False):
    from benchmark.reference import olmo_hybrid as ref
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
    model and reference where they call their own."""
    mine = {"build": build, "compare_with_reference": compare_with_reference}
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
