"""Serving cells of a Solar Open 2 decoder (``arch`` ``solar_open2``: Kimi
delta attention layers whose cache is three convolution windows and one ``d
x d`` float32 matrix a head a lane, beside gated grouped-query attention
layers that page their K/V, a SwiGLU expert layer with a shared expert in
EVERY layer) through ``serving.Router`` -> one ``inference.PagedEngine``
replica.

As ``drivers/serve_exaone_moe.py``, this kind brings only what the
architecture needs: the model, its table of weights
(``lib/weights_solar_open2.py``), its plain reference and a read of the
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
from benchmark.lib import weights_solar_open2 as weights_lib
from benchmark.lib.harness import log

_GLOBAL = {"model.embed_tokens.weight": "embed",
           "model.norm.weight": "norm", "lm_head.weight": "lm_head"}
_BLOCK = {
    "input_layernorm.weight": "input_norm",
    # both mixers
    "mixer.q_proj.weight": "q", "mixer.k_proj.weight": "k",
    "mixer.v_proj.weight": "v", "mixer.o_proj.weight": "o",
    # gated grouped-query attention
    "mixer.g_proj.weight": "g",
    # Kimi delta attention
    "mixer.f_a_proj.weight": "f_a", "mixer.f_b_proj.weight": "f_b",
    "mixer.g_a_proj.weight": "g_a", "mixer.g_b_proj.weight": "g_b",
    "mixer.b_proj.weight": "b", "mixer.q_conv_weight": "q_conv",
    "mixer.k_conv_weight": "k_conv", "mixer.v_conv_weight": "v_conv",
    "mixer.A_log": "A_log", "mixer.dt_bias": "dt_bias",
    "mixer.o_norm_weight": "o_norm",
    # the expert layer
    "post_attention_layernorm.weight": "post_norm",
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
    try:
        from paddle_tpu.models import SolarOpen2Config
    except ImportError:
        raise SystemExit("serve_solar_open2 driver: this program has no "
                         "solar_open2 model") from None

    if cfg["arch"] != "solar_open2":
        raise SystemExit(f"serve_solar_open2 driver has no model for arch "
                         f"{cfg['arch']!r}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise SystemExit("experts_held and n_routed_experts (the experts "
                         "held here) disagree")
    same = ("vocab_size hidden_size num_hidden_layers num_attention_heads "
            "num_key_value_heads head_dim gqa_layers use_gqa_gate use_rope "
            "linear_attn_config kda_use_full_proj kda_allow_neg_eigval "
            "intermediate_size first_k_dense_replace n_shared_experts "
            "num_experts_per_tok moe_intermediate_size "
            "routed_scaling_factor norm_topk_prob tie_word_embeddings "
            "rms_norm_eps initializer_range chunk_size").split()
    return SolarOpen2Config(
        n_routed_experts=cfg["router_width"], experts_held=(lo, hi),
        max_seq_len=cfg["engine"]["context"],
        **{k: cfg[k] for k in same if k in cfg})


def put_weights(model, made: dict):
    """``serve_exaone_moe.put_weights`` under this kind's parameter names."""
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
    from paddle_tpu.inference import PagedEngine, ResilienceConfig
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer
    from paddle_tpu.serving import Router, SchedulerConfig

    eng = cfg["engine"]
    config = model_config(cfg)
    from paddle_tpu.models import SolarOpen2ForCausalLM
    with LazyGuard():
        model = SolarOpen2ForCausalLM(config)
    log("model described")
    put_weights(model, weights_lib.make(cfg, seed, "bfloat16"))
    materialize_layer(model)
    jax.block_until_ready([p._data for p in model.parameters()])
    log("weights made")
    budget = eng.get("prefill_token_budget")
    # the admission queue holds every client that has no lane: the engine's
    # default bound (256) is below this cell's 512 clients, and a request
    # refused at the door is a failed operation
    queue = eng.get("max_queue")
    replica = PagedEngine(
        model, max_batch=eng["max_batch"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"],
        max_blocks_per_seq=eng["context"] // eng["block_size"],
        scheduler=(SchedulerConfig(prefill_token_budget=budget)
                   if budget else None),
        resilience=ResilienceConfig(max_queue=queue) if queue else None)
    log("engine built")
    router = Router([replica]).warmup()
    log("engine warm")
    return router, replica, model


def compare_with_reference(cfg, seed, sample, verdict, control=False):
    from benchmark.reference import solar_open2 as ref
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


def control(cell, seed, devices, seconds: float = 30.0):
    """A window long enough for eight requests to finish: the mix's
    shortest answers are 64 tokens behind prompts of hundreds to thousands
    of tokens, at 256 lanes."""
    with _in_serves_place():
        return serve.control(cell, seed, devices, seconds)
