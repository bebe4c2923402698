"""Plain SmallThinker training reference (``model_name`` ``smallthinker_*``;
PowerInfer's SmallThinker-21BA3B-Instruct is the published instance):
float32 ``jax.numpy`` at ``highest`` matmul precision, written from the
published config and the family's description (window layers with rotary
embedding beside full layers without positions, a router placed before the
attention, sparse ReGLU experts), with the published AdamW (Loshchilov &
Hutter 2019). No kernels, no mixed precision, nothing imported from the
program; weights come from ``benchmark.lib.weights_smallthinker`` and the
seed; RMSNorm, the rotate-half rotary embedding and AdamW are the LFM2-MoE
reference's own (``reference/lfm2_moe.py``), the same plain functions.

    h = E[ids]                                    (no scaling)
    layer l:  x = RMSNorm_in(h)
              S = the k largest of r = x W_r;  p = softmax(r_S)
              a = h + Attention_l(x)
              u = RMSNorm_post(a)
              h = a + sum_{e in S} p_e D_e (relu(G_e u) * U_e u)
    logits = RMSNorm(h) W_head                    (untied head)

* the router reads ``x``, the attention's input, not ``u``: float32 logits
  over ALL ``router_width`` experts, the ``moe_num_active_primary_experts``
  largest chosen, a softmax over those (``moe_primary_router_apply_softmax``;
  ``norm_topk_prob`` then changes nothing: the weights sum to 1).
* ``Attention_l``: grouped-query, no bias, no q/k norm, softmax at
  ``head_dim ** -0.5``. ``rope_layout[l] = 1``: rotate-half rotary embedding
  over the whole head on q and k; ``0``: no positional encoding.
  ``sliding_window_layout[l] = 1``: query ``i`` sees key ``j`` iff ``0 <= i -
  j < sliding_window_size``; ``0``: iff ``j <= i``. A block of queries at a
  time against every key, the mask applied to the whole row of scores.
* the expert part as a LOOP over the held experts, each applied to every
  token and weighted by that token's routing weight for it (0 where the
  token did not choose it). No shared expert, no dense layer.

Departures from the published description, each the configuration's (its
file lists them under ``assumed`` / ``reduced``): the chip's share of the
experts (``experts_held``) and of the vocabulary: what the absent experts
would add is left out, here as in the program, and ids and loss are over the
slice; no auxiliary loss. Departures for memory only, which change no
number: the batch is walked a row at a time inside one gradient (the loss is
a sum over rows), every layer is rematerialised in the backward pass
(``jax.checkpoint``), attention runs a block of queries at a time under
``jax.checkpoint`` (16 384 x 16 384 x 28 float32 scores never exist), the
head a block of rows at a time over all S positions with the last one's
label masked (S - 1 is odd), and the optimizer's step donates its state.

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

from benchmark.lib import weights_smallthinker as weights_lib
from benchmark.reference.gpt2 import _Frozen, _fp8_matmul
from benchmark.reference.lfm2_moe import (_adamw, _blocks, _rms_norm,
                                          _rotate, leaf_norms)

#: queries an attention step scores against every key; rows the head takes
QUERY_BLOCK = 256
HEAD_BLOCK = 2048


def init_params(cfg: dict, seed: int):
    """``{(layer, name): float32 array}``: every leaf is trained."""
    return weights_lib.make(cfg, seed)


def attention(x, lp, cfg, index, mm):
    """Grouped-query attention over the whole row ``x`` (T, hidden), with
    the layer's own positions (rotary or none) and mask (window or full)."""
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = mm(x, lp["attn.q"]).reshape(t, nh, hd)
    k = mm(x, lp["attn.k"]).reshape(t, nkv, hd)
    v = mm(x, lp["attn.v"]).reshape(t, nkv, hd)
    if cfg["rope_layout"][index]:
        q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    window = (cfg["sliding_window_size"]
              if cfg["sliding_window_layout"][index] else None)
    block = _blocks(t, QUERY_BLOCK)

    @jax.checkpoint
    def some_queries(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        back = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]
        seen = back >= 0
        if window is not None:
            seen = seen & (back < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(some_queries, jnp.arange(0, t, block))
    return mm(out.reshape(t, nh * hd), lp["attn.o"])


def route(x, router, cfg):
    """``(idx (T, k), weights (T, k))`` over all ``router_width`` experts:
    the k largest logits, a softmax over them."""
    top, idx = jax.lax.top_k(x @ router, cfg["moe_num_active_primary_experts"])
    return idx, jax.nn.softmax(top, axis=-1)


def reglu(u, gate, up, down, mm):
    return mm(jax.nn.relu(mm(u, gate)) * mm(u, up), down)


def experts(u, idx, w, lp, cfg, mm):
    """The held experts' part of the routed sum for ``u`` (T, hidden)."""
    lo, hi = cfg["experts_held"]
    # (T, held): each held expert's weight in each token's sum
    combine = jnp.sum(
        jnp.where(idx[:, :, None] == jnp.arange(lo, hi)[None, None, :],
                  w[:, :, None], 0.0), axis=1)

    def one_expert(acc, ew):
        gate, up, down, c = ew
        return acc + c[:, None] * reglu(u, gate, up, down, mm), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(u),
        (lp["moe.gate"], lp["moe.up"], lp["moe.down"], combine.T))
    return out


def layer(h, lp, cfg, index, mm):
    eps = cfg["rms_norm_eps"]
    x = _rms_norm(h, lp["input_norm"], eps)
    idx, w = route(x, lp["moe.router"], cfg)         # BEFORE the attention
    a = h + attention(x, lp, cfg, index, mm)
    u = _rms_norm(a, lp["post_norm"], eps)
    return a + experts(u, idx, w, lp, cfg, mm)


def _of_layer(flat, index):
    return {name: a for (i, name), a in flat.items() if i == index}


def row_loss_sum(params, ids, cfg, precision="float32"):
    """Sum over the positions of ONE row of the next-token cross entropy."""
    mm = _fp8_matmul if precision == "fp8" else jnp.matmul
    h = params[(-1, "embed")][ids]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(
            functools.partial(layer, cfg=cfg, index=i, mm=mm))(
                h, _of_layer(params, i))
    h = _rms_norm(h, params[(-1, "final_norm")], cfg["rms_norm_eps"])
    # every position takes part, the last one's label masked: whole blocks
    labels = jnp.concatenate([ids[1:], jnp.full((1,), -1, ids.dtype)])
    head = params[(-1, "head")]
    block = _blocks(h.shape[0], HEAD_BLOCK)

    @jax.checkpoint
    def some_rows(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, block, axis=0)
        lb = jax.lax.dynamic_slice_in_dim(labels, start, block, axis=0)
        logp = jax.nn.log_softmax(mm(hb, head), axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(lb, 0)[:, None],
                                     axis=-1)[:, 0]
        return -jnp.sum(jnp.where(lb >= 0, picked, 0.0))

    return jnp.sum(jax.lax.map(some_rows, jnp.arange(0, h.shape[0], block)))


def loss_sum(params, ids, cfg, precision="float32"):
    """Sum over rows and positions: the rows one after another."""
    one = jax.checkpoint(functools.partial(
        row_loss_sum, cfg=cfg, precision=precision))

    def body(total, row):
        return total + one(params, row), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), ids)
    return total


@functools.partial(jax.jit, static_argnums=(2, 3))
def _loss_and_grads(params, batch, cfg, precision):
    total, grads = jax.value_and_grad(loss_sum)(params, batch, cfg, precision)
    n = batch.shape[0] * (batch.shape[1] - 1)
    return total / n, jax.tree_util.tree_map(lambda a: a / n, grads)


def loss_and_grads(params, batch, cfg, precision="float32"):
    """Mean loss over the batch's predicted tokens and its gradients."""
    return _loss_and_grads(params, jnp.asarray(np.asarray(batch)),
                           _Frozen(cfg), precision)


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
    params = init_params(cfg, seed)
    zeros = lambda: {k: jnp.zeros_like(a) for k, a in params.items()}  # noqa: E731
    losses, grad_norms, step = [], None, 0
    for n_steps in calls:
        m, v = zeros(), zeros()
        for t in range(1, n_steps + 1):
            loss, grads = loss_and_grads(params, batches[step], cfg,
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
    start = init_params(cfg, seed)
    delta = leaf_norms({k: params[k] - start[k] for k in params})
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}
