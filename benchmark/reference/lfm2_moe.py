"""Plain LFM2-MoE training reference (``model_type`` ``lfm2_moe``; LiquidAI's
LFM2-8B-A1B is the published instance): float32 ``jax.numpy`` at ``highest``
matmul precision, written from the published config and the layer equations
of the published ``modeling_lfm2_moe.py``, with the published AdamW
(Loshchilov & Hutter 2019). No kernels, no mixed precision, nothing imported
from the program; weights come from ``benchmark.lib.weights_lfm2_moe`` and
the seed.

    h = E[ids]                                    (no scaling)
    layer l:  x = RMSNorm(h);  h = h + (ShortConv(x) | Attention(x))
              u = RMSNorm(h);  h = h + (DenseMLP(u) if l < num_dense_layers
                                        else MoE(u))
    logits = RMSNorm(h) E^T                       (tied head)

* ``ShortConv``: ``[B | C | z] = x W_in``; ``g = B * z``; a depthwise causal
  convolution of ``conv_L_cache`` taps over ``g`` (zeros left of the first
  token, no bias); ``(C * conv) W_out``.
* ``Attention``: grouped-query, q and k RMS-normed over each head's width
  (one weight of that width each), rotate-half rotary embedding over the
  whole head, causal softmax at ``head_dim ** -0.5``; a block of queries at
  a time against every key.
* ``MoE``: float32 sigmoid scores over ALL ``router_width`` experts, the
  ``num_experts_per_tok`` largest of ``score + bias`` chosen, their scores
  (without the bias) renormalised with the published ``+ 1e-6`` and scaled;
  the expert part as a LOOP over the held experts, each applied to every
  token and weighted by that token's routing weight for it (0 where the
  token did not choose it). No shared expert.

Departures from the published code, each the configuration's (its file lists
them under ``assumed`` / ``reduced``): the chip's share of the experts
(``experts_held``) and of the vocabulary: what the absent experts would add
is left out, here as in the program, and ids and loss are over the slice;
the expert bias is a buffer that nothing updates; no auxiliary loss.
Departures for memory only, which change no number: the batch is walked a
row at a time inside one gradient (the loss is a sum over rows), every layer
is rematerialised in the backward pass (``jax.checkpoint``), attention and
the head run a block of rows at a time, and the optimizer's step donates its
state.

``precision="fp8"`` is the CONTROL, not a reference: the same mathematics
with every weight matmul in float8 (e4m3 operands forward, e5m2 gradients
backward, per-tensor scales), the precision just below the bf16 the
configuration computes in. The router stays float32, as the configuration
keeps it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_lfm2_moe as weights_lib
from benchmark.lib.weights_lfm2_moe import CONV
from benchmark.reference.gpt2 import _Frozen, _fp8_matmul

#: queries an attention step scores against every key; rows the head takes
QUERY_BLOCK = 512
HEAD_BLOCK = 2048


def init_params(cfg: dict, seed: int):
    """``(trained, buffers)``: two ``{(layer, name): float32 array}``."""
    flat = weights_lib.make(cfg, seed)
    buffers = {k: v for k, v in flat.items() if k[1] in weights_lib.BUFFERS}
    return {k: v for k, v in flat.items() if k not in buffers}, buffers


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """Rotary embedding, rotate-half, positions 0..T-1: ``x`` (T, heads, D)."""
    t, _heads, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def _blocks(n, block):
    """The block that divides ``n`` (one block where none does)."""
    return block if n % block == 0 else n


def short_conv(x, w_in, taps, w_out, mm):
    """``x`` (T, hidden), ``taps`` (hidden, L)."""
    b, c, z = jnp.split(mm(x, w_in), 3, axis=-1)
    g = b * z
    t, width = taps.shape[1], g.shape[0]
    padded = jnp.concatenate([jnp.zeros((t - 1, g.shape[1]), g.dtype), g])
    conv = sum(taps[:, j] * padded[j:j + width] for j in range(t))
    return mm(c * conv, w_out)


def attention(x, lp, cfg, mm):
    """Causal grouped-query attention over the whole row ``x`` (T, hidden)."""
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = weights_lib.head_dim(cfg), cfg["norm_eps"]
    q = _rms_norm(mm(x, lp["attn.q"]).reshape(t, nh, hd), lp["attn.q_norm"],
                  eps)
    k = _rms_norm(mm(x, lp["attn.k"]).reshape(t, nkv, hd), lp["attn.k_norm"],
                  eps)
    v = mm(x, lp["attn.v"]).reshape(t, nkv, hd)
    q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    block = _blocks(t, QUERY_BLOCK)

    @jax.checkpoint
    def some_queries(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(some_queries, jnp.arange(0, t, block))
    return mm(out.reshape(t, nh * hd), lp["attn.o"])


def swiglu(u, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


def route(u, router, bias, cfg):
    """``(idx (T, k), weights (T, k))`` over all ``router_width`` experts."""
    s = jax.nn.sigmoid(u @ router)
    steer = s + bias if cfg.get("use_expert_bias", True) else s
    _top, idx = jax.lax.top_k(steer, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-6)
    return idx, w * cfg.get("routed_scaling_factor", 1.0)


def moe(u, lp, bias, cfg, mm):
    """The held experts' part of the routed sum for ``u`` (T, hidden)."""
    lo, hi = cfg["experts_held"]
    idx, w = route(u, lp["moe.router"], bias, cfg)
    # (T, held): each held expert's weight in each token's sum
    combine = jnp.sum(
        jnp.where(idx[:, :, None] == jnp.arange(lo, hi)[None, None, :],
                  w[:, :, None], 0.0), axis=1)

    def one_expert(acc, ew):
        w1, w3, w2, c = ew
        return acc + c[:, None] * swiglu(u, w1, w3, w2, mm), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(u),
        (lp["moe.w1"], lp["moe.w3"], lp["moe.w2"], combine.T))
    return out


def layer(h, lp, bias, cfg, index, mm):
    eps = cfg["norm_eps"]
    x = _rms_norm(h, lp["operator_norm"], eps)
    if cfg["layer_types"][index] == CONV:
        h = h + short_conv(x, lp["conv.in_proj"], lp["conv.taps"],
                           lp["conv.out_proj"], mm)
    else:
        h = h + attention(x, lp, cfg, mm)
    u = _rms_norm(h, lp["ffn_norm"], eps)
    if index < cfg["num_dense_layers"]:
        return h + swiglu(u, lp["mlp.w1"], lp["mlp.w3"], lp["mlp.w2"], mm)
    return h + moe(u, lp, bias, cfg, mm)


def _of_layer(flat, index):
    return {name: a for (i, name), a in flat.items() if i == index}


def row_loss_sum(params, buffers, ids, cfg, precision="float32"):
    """Sum over the positions of ONE row of the next-token cross entropy."""
    mm = _fp8_matmul if precision == "fp8" else jnp.matmul
    h = params[(-1, "embed")][ids]
    for i in range(cfg["num_hidden_layers"]):
        bias = buffers.get((i, "moe.bias"))
        h = jax.checkpoint(
            functools.partial(layer, cfg=cfg, index=i, mm=mm))(
                h, _of_layer(params, i), bias)
    h = _rms_norm(h, params[(-1, "final_norm")], cfg["norm_eps"])[:-1]
    labels, table = ids[1:], params[(-1, "embed")]
    block = _blocks(h.shape[0], HEAD_BLOCK)      # S - 1 is odd: one block

    @jax.checkpoint
    def some_rows(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, block, axis=0)
        lb = jax.lax.dynamic_slice_in_dim(labels, start, block, axis=0)
        logp = jax.nn.log_softmax(mm(hb, table.T), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    return jnp.sum(jax.lax.map(some_rows, jnp.arange(0, h.shape[0], block)))


def loss_sum(params, buffers, ids, cfg, precision="float32"):
    """Sum over rows and positions: the rows one after another."""
    one = jax.checkpoint(functools.partial(
        row_loss_sum, cfg=cfg, precision=precision))

    def body(total, row):
        return total + one(params, buffers, row), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), ids)
    return total


@functools.partial(jax.jit, static_argnums=(3, 4))
def _loss_and_grads(params, buffers, batch, cfg, precision):
    total, grads = jax.value_and_grad(loss_sum)(params, buffers, batch, cfg,
                                                precision)
    n = batch.shape[0] * (batch.shape[1] - 1)
    return total / n, jax.tree_util.tree_map(lambda a: a / n, grads)


def loss_and_grads(params, buffers, batch, cfg, precision="float32"):
    """Mean loss over the batch's predicted tokens and its gradients."""
    return _loss_and_grads(params, buffers, jnp.asarray(np.asarray(batch)),
                           _Frozen(cfg), precision)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, t, lr, wd, b1, b2, eps):
    def leaf(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        step = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
        return p * (1 - lr * wd) - lr * step, m2, v2

    out = {k: leaf(params[k], grads[k], m[k], v[k]) for k in params}
    return tuple({k: o[i] for k, o in out.items()} for i in range(3))


@jax.jit
def _norms(tree):
    return {k: jnp.linalg.norm(a) for k, a in tree.items()}


def leaf_norms(tree: dict) -> dict:
    """``{(layer, name): l2 norm}`` with the table's own keys (an expert
    leaf is the stack of the held experts)."""
    return {k: float(x) for k, x in _norms(tree).items()}


def follow(cfg: dict, seed: int, batches, opt: dict, calls,
           precision: str = "float32") -> dict:
    """Train from the seed's weights over ``batches`` (one per step), the
    steps grouped into ``calls`` (``[1, 2]``: one step, then two); AdamW's
    moments and step count start afresh at every call, as each ``fit`` call of
    the program starts them. Returns float lists and ``{leaf: norm}`` dicts:
    ``losses`` per step, ``grad_norms`` of the first step's gradient, and
    ``delta_norms`` of the parameters' change after the last step. The
    seed's weights are made again for the last (holding them through the
    steps would be a fifth copy of the model beside the four AdamW needs)."""
    params, buffers = init_params(cfg, seed)
    zeros = lambda: {k: jnp.zeros_like(a) for k, a in params.items()}  # noqa: E731
    losses, grad_norms, step = [], None, 0
    for n_steps in calls:
        m, v = zeros(), zeros()
        for t in range(1, n_steps + 1):
            loss, grads = loss_and_grads(params, buffers, batches[step], cfg,
                                         precision)
            if grad_norms is None:
                grad_norms = leaf_norms(grads)
            params, m, v = _adamw(
                params, grads, m, v, jnp.float32(t), opt["learning_rate"],
                opt["weight_decay"], opt["beta1"], opt["beta2"],
                opt["epsilon"])
            del grads
            losses.append(float(loss))
            step += 1
        del m, v
    start, _ = init_params(cfg, seed)
    delta = leaf_norms({k: params[k] - start[k] for k in params})
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}
