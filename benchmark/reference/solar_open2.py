"""Plain forward pass of a Solar Open 2 decoder (``model_type``
``solar_open2``: Solar-Open2-250B's block): float32 ``jax.numpy`` at
``highest`` matmul precision, written from the published config and the
layer equations of Kimi delta attention (Kimi Linear, arXiv:2510.26692;
negative eigenvalues after Grazzi et al., arXiv:2411.12537). No kernels, no
cache, no chunked form, no batching, nothing imported from the program:

* block ``l``: ``h = x + Mixer_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``
  (pre-norm: ``assumed`` in the configuration file); EVERY layer's
  feed-forward is the expert layer; a final RMSNorm and an untied head;
* KDA (a layer not in ``gqa_layers``): the recurrence ``S_t = (I - beta_t
  k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``
  as a ``lax.scan`` over TOKENS, each step written as the rule reads (decay
  every row of ``S`` by its own channel's factor, read ``S^T k``, correct,
  write, read ``S^T q``: the program's decode step folds the decay into two
  columns and reads the state once, its prefill builds pair terms by
  sub-blocks and inverts a triangular system a chunk; neither form is
  here); three causal depthwise convolutions, each an explicit sum over its
  taps (no bias: ``assumed``); ``q`` and ``k`` of unit length a head with
  ``q`` scaled by ``d ** -0.5``; ``g = -exp(A_log[h]) softplus((x W_fa) W_fb
  + dt_bias)`` a channel; ``beta = 2 sigmoid(x W_b)``; the per-head RMSNorm
  of ``o`` FIRST and the SIGMOID gate ``(x W_ga) W_gb`` after it;
* gated GQA (a layer in ``gqa_layers``): causal softmax attention over the
  WHOLE prefix, a block of queries at a time against every key, scale
  ``head_dim ** -0.5``, NO positional encoding and no q/k norm, the output
  times ``sigmoid(x W_g)`` before ``W_o``;
* the expert layer: a float32 sigmoid router over ``router_width`` scores,
  top-k renormalised and scaled, the expert part as a LOOP over the held
  experts, each applied to every token and weighted by that token's routing
  weight for it (0 where the token did not choose it), plus the shared
  expert; what the absent experts would add is left out, here as in the
  program; the head over the configuration's slice of the vocabulary.

Departures from the published description, each stated where it is made:
the unit-length normalisation adds 1e-6 under the square root (the open
implementation's ``l2norm`` epsilon); the renormalisation of the chosen
scores adds 1e-20 to their sum.

One request at a time through each layer. Everything that is a product with
a weight runs over ``BLOCK`` rows at a time, so the compiled shapes do not
depend on the request's length: only the convolutions, the scan and
attention see the whole sequence. The weights are the seed's
(``benchmark.lib.weights_solar_open2``), made and upcast to float32 ONE LAYER
AT A TIME, so the reference never holds the model.

``precision="int8"`` is the CONTROL, not a reference: the same pass with
every block matrix, every expert matrix and the head rounded to int8 per
output channel (weight-only int8, the precision just below the bf16 the
configuration serves in). The router, the decay's path (``f_a``, ``f_b``,
``A_log``, ``dt_bias``), the convolutions and the norm scales stay as they
are, as weight-only int8 deployments keep them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_solar_open2 as weights_lib

MATRICES = ("q", "k", "v", "g", "o", "b", "g_a", "g_b", "w_gate", "w_up",
            "w_down", "shared_gate", "shared_up", "shared_down", "lm_head")

#: rows a weight product takes at once
BLOCK = 2048
#: queries an attention step scores against every key
QUERY_BLOCK = 256


def _fake_int8(w):
    """Round ``[..., in, out]`` to int8 with one scale per output."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12)
    scale = scale / 127.0
    return jnp.round(w / scale) * scale


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _unit(x):
    # departure: 1e-6 under the root, the open implementation's l2norm
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


# ---------------------------------------------------------------------- KDA
@functools.partial(jax.jit, static_argnames=("eps",))
def kda_rows(x, lw, *, eps):
    """Row-wise, a block of rows: normed input -> ``q~``, ``k~``, ``v``
    before their convolutions, the log decay a channel, ``beta`` a head and
    the output gate."""
    u = _rms_norm(x, lw["input_norm"], eps)
    heads = lw["A_log"].shape[0]
    dt = jax.nn.softplus((u @ lw["f_a"]) @ lw["f_b"] + lw["dt_bias"])
    g = -jnp.exp(lw["A_log"])[:, None] * dt.reshape(x.shape[0], heads, -1)
    return (u @ lw["q"], u @ lw["k"], u @ lw["v"], g,
            2.0 * jax.nn.sigmoid(u @ lw["b"]),
            jax.nn.sigmoid((u @ lw["g_a"]) @ lw["g_b"]))


def _conv(x, w):
    """``silu`` of the causal depthwise convolution: ``x`` (T, C), ``w`` (C,
    taps); ``out_t = sum_j w_j in_{t - (taps - 1) + j}``."""
    taps, t = w.shape[1], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    out = 0.0
    for j in range(taps):
        out = out + padded[j:j + t] * w[:, j]
    return jax.nn.silu(out)


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def kda_scan(q, k, v, g, beta, gate, lw, *, heads, eps):
    """The whole sequence, from a zero state: ``q`` / ``k`` / ``v`` (T, H *
    d) before their convolutions, ``g`` (T, H, d), ``beta`` (T, H), ``gate``
    (T, H * d) -> the gated, normed read-out (T, H * d)."""
    t = q.shape[0]
    d = q.shape[1] // heads
    q = _unit(_conv(q, lw["q_conv"]).reshape(t, heads, d)) / np.sqrt(d)
    k = _unit(_conv(k, lw["k_conv"]).reshape(t, heads, d))
    v = _conv(v, lw["v_conv"]).reshape(t, heads, d)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp       # (H, d) x 4, (H,)
        s = jnp.exp(g_t)[:, :, None] * s                    # Diag(alpha) S
        seen = jnp.einsum("hkv,hk->hv", s, k_t)             # S^T k
        u = b_t[:, None] * (v_t - seen)                     # correction
        s = s + k_t[:, :, None] * u[:, None, :]             # rank-one write
        return s, jnp.einsum("hkv,hk->hv", s, q_t)          # S^T q

    _s, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                         (q, k, v, g, beta))
    o = _rms_norm(o, lw["o_norm"], eps)                     # norm first
    return (o * gate.reshape(t, heads, d)).reshape(t, heads * d)  # then gate


# ---------------------------------------------------------------- gated GQA
@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def gqa_rows(x, lw, *, heads, kv_heads, eps):
    rows = x.shape[0]
    u = _rms_norm(x, lw["input_norm"], eps)
    return ((u @ lw["q"]).reshape(rows, heads, -1),
            (u @ lw["k"]).reshape(rows, kv_heads, -1),
            (u @ lw["v"]).reshape(rows, kv_heads, -1),
            jax.nn.sigmoid(u @ lw["g"]))


@jax.jit
def attention(q, k, v):
    """``q`` (T, H, D), ``k`` / ``v`` (T, KVH, D) -> (T, H * D): causal
    softmax(q k^T / sqrt(D)) v, ``QUERY_BLOCK`` queries at a time against
    every key; a K/V head serves ``H / KVH`` query heads."""
    t, h, d = q.shape
    kvh = k.shape[1]
    qb = math.gcd(t, QUERY_BLOCK)
    qg = q.reshape(t // qb, qb, kvh, h // kvh, d)
    j = jnp.arange(t)[None, :]

    def one_block(args):
        q_blk, first = args
        seen = (first + jnp.arange(qb)[:, None]) >= j
        s = jnp.einsum("qkgd,skd->kgqs", q_blk, k) / np.sqrt(d)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one_block, (qg, jnp.arange(0, t, qb)))
    return out.reshape(t, h * d)


# ------------------------------------------------------------------ experts
def route(u, lw, top_k, scale, normalize):
    """``(n, E)`` float32: each token's weight for each expert of the
    ROUTER's width, 0 where the expert is not among its ``top_k``."""
    s = jax.nn.sigmoid(u @ lw["router"])
    order = jnp.argsort(-(s + lw["e_score_correction_bias"]), axis=-1)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order[:, :top_k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "normalize",
                                             "lo", "eps"))
def close_block(x, mixed, lw, *, top_k, scale, normalize, lo, eps):
    """A block of rows: ``h = x + mixed W_o``, then ``h + MoE(RMSNorm(h))``:
    the held experts' part (``lo`` is the first of them) plus the shared
    expert."""
    h = x + mixed @ lw["o"]
    u = _rms_norm(h, lw["post_norm"], eps)
    w = route(u, lw, top_k, scale, normalize)
    held = lw["w_gate"].shape[0]

    def one_expert(acc, inp):
        gate, up, down, w_e = inp                       # w_e (n,)
        return acc + w_e[:, None] * _swiglu(u, gate, up, down), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (lw["w_gate"], lw["w_up"], lw["w_down"], w[:, lo:lo + held].T))
    return h + routed + _swiglu(u, lw["shared_gate"], lw["shared_up"],
                                lw["shared_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return _rms_norm(x, norm, eps) @ lm_head


# -------------------------------------------------------------------- model
@functools.partial(jax.jit, static_argnames=("int8",))
def _upcast(a, *, int8):
    """One leaf to float32 (and, for the control, through int8) in one
    fused pass: an expert layer's stacked matrices are 0.8 GB each."""
    a = a.astype(jnp.float32)
    return _fake_int8(a) if int8 else a


def layer_weights(cfg, seed, layer, precision="float32"):
    made = weights_lib.make(cfg, seed, jnp.bfloat16, layers=[layer])
    out = {}
    for key in list(made):
        name = key[1]
        out[name] = _upcast(made.pop(key), int8=(precision == "int8"
                                                 and name in MATRICES))
    return out


def _blocks(t: int, block: int):
    return [(lo, min(lo + block, t)) for lo in range(0, t, block)]


def mixer(cfg: dict, index: int, x, lw, block: int = BLOCK):
    """Block ``index``'s mixer on the block's input ``x`` (T, hidden),
    before ``W_o``: (T, heads * head_dim)."""
    eps = float(cfg["rms_norm_eps"])
    spans = _blocks(x.shape[0], block)
    if index in cfg["gqa_layers"]:
        parts = [gqa_rows(x[lo:hi], lw, heads=cfg["num_attention_heads"],
                          kv_heads=cfg["num_key_value_heads"], eps=eps)
                 for lo, hi in spans]
        q, k, v, gate = (jnp.concatenate(p) for p in zip(*parts))
        return attention(q, k, v) * gate
    parts = [kda_rows(x[lo:hi], lw, eps=eps) for lo, hi in spans]
    rows = [jnp.concatenate(p) for p in zip(*parts)]
    return kda_scan(*rows, lw, heads=cfg["linear_attn_config"]["num_heads"],
                    eps=eps)


def layer(cfg: dict, index: int, x, lw, block: int = BLOCK):
    """One block of the decoder on a whole sequence ``x`` (T, hidden)."""
    a = mixer(cfg, index, x, lw, block)
    return jnp.concatenate([
        close_block(x[lo:hi], a[lo:hi], lw,
                    top_k=cfg["num_experts_per_tok"],
                    scale=float(cfg["routed_scaling_factor"]),
                    normalize=bool(cfg["norm_topk_prob"]),
                    lo=int(cfg.get("experts_held", (0, 0))[0]),
                    eps=float(cfg["rms_norm_eps"]))
        for lo, hi in _blocks(x.shape[0], block)])


def hidden(cfg: dict, seed: int, rows, precision: str = "float32",
           block: int = BLOCK):
    """``([x (T padded, hidden) a sequence], top)``: the last block's output
    for every sequence of ``rows`` (each ``ids`` (T,)), and the leaves
    outside the blocks. A sequence runs padded at the end to whole blocks,
    which a causal model's earlier positions never see, so the shapes
    compiled stay few; the caller cuts the padding off."""
    top = layer_weights(cfg, seed, -1, precision)
    embed = top.pop("embed")
    xs = []
    for ids in rows:
        padded = np.zeros((-(-len(ids) // block) * block,), np.int32)
        padded[:len(ids)] = np.asarray(ids)
        xs.append(embed[jnp.asarray(padded)])
    del embed
    for index in range(cfg["num_hidden_layers"]):
        lw = layer_weights(cfg, seed, index, precision)
        for r, x in enumerate(xs):      # one request's rows at a time
            xs[r] = layer(cfg, index, x, lw, block)
        del lw, x
    return xs, top


def logits(cfg: dict, seed: int, ids, precision: str = "float32",
           block: int = BLOCK):
    """``[T, vocab]`` float32 logits of the full forward over one sequence
    ``ids`` (T,). (The served-token comparison below never holds them all:
    it reads a block's and lets them go.)"""
    with jax.default_matmul_precision("highest"):
        (x,), top = hidden(cfg, seed, [ids], precision, block)
        eps = float(cfg["rms_norm_eps"])
        return jnp.concatenate([
            _head(x[lo:hi], top["norm"], top["lm_head"], eps=eps)
            for lo, hi in _blocks(x.shape[0], block)])[:len(ids)]


def served_token_gaps(cfg, seed, prompts, served, width: int,
                      control: bool = False, block: int = BLOCK) -> dict:
    """As ``reference.llama_like.served_token_gaps``: for each request run
    the reference once over prompt + served tokens and read, at every
    served token's position, the gap by which that token's logit lies
    below the reference's best; with ``control`` the token judged is the
    one the int8 pass puts first. (``width``, the engine's context, bounds
    every request and is not needed here.)"""
    eps = float(cfg["rms_norm_eps"])
    rows = [np.asarray(list(p) + list(s[:-1]), np.int32)
            for p, s in zip(prompts, served)]

    def head_blocks(xs, top):
        """``(request, lo, hi, logits)`` for every block of every request
        that holds a served position."""
        for r, (x, p, s) in enumerate(zip(xs, prompts, served)):
            for lo, hi in _blocks(x.shape[0], block):
                if hi > len(p) - 1 and lo < len(p) - 1 + len(s):
                    yield r, lo, hi, _head(x[lo:hi], top["norm"],
                                           top["lm_head"], eps=eps)

    judged = []
    for x_len, p, s in zip((-(-len(r) // block) * block for r in rows),
                           prompts, served):
        row = np.zeros((x_len,), np.int32)
        row[len(p) - 1:len(p) - 1 + len(s)] = s
        judged.append(row)
    widest, total, agree = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        if control:     # first, so that only one pass's rows are held
            xs, top = hidden(cfg, seed, rows, "int8", block)
            for r, lo, hi, got in head_blocks(xs, top):
                judged[r][lo:hi] = np.asarray(jnp.argmax(got, -1))
            del xs, top
        xs, top = hidden(cfg, seed, rows, "float32", block)
        for r, lo, hi, ref in head_blocks(xs, top):
            first = len(prompts[r]) - 1
            at = np.arange(lo, hi)
            mask = jnp.asarray((at >= first) & (at < first + len(served[r])))
            chosen = jnp.take_along_axis(
                ref, jnp.asarray(judged[r][lo:hi])[:, None], axis=-1)[:, 0]
            gap = jnp.where(mask, jnp.max(ref, axis=-1) - chosen, 0.0)
            widest = max(widest, float(jnp.max(gap)))
            total += float(jnp.sum(gap))
            agree += int(jnp.sum((gap == 0) & mask))
    n = sum(len(s) for s in served)
    return {"logit_gap_max": widest, "logit_gap_mean": total / n,
            "top1_share": agree / n, "positions": n}
