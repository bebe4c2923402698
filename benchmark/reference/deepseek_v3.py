"""Plain forward pass of a DeepSeek-V3 decoder (``model_type``
``deepseek_v3``: Kanana-2-30B-A3B's block): float32 ``jax.numpy`` at
``highest`` matmul precision, written from the published config and the
family's layer equations, in the PUBLISHED (materialised) form of latent
attention. No kernels, no cache, no absorbed products, no batching, nothing
imported from the program:

* block ``l``: ``a = x + Attn_l(RMSNorm(x))``, ``y = a + FFN_l(RMSNorm(a))``;
* ``Attn_l``: ``q = W_q u`` in heads of ``[q_nope | q_rope]``; ``[c' | k_r']
  = W_kva u``, ``c = RMSNorm(c')``; rotary embedding on ``q_rope`` and on the
  ONE row ``k_r`` all heads share (interleaved pairs ``(2i, 2i + 1)`` brought
  to the half-split layout, then rotate-half, as the HF ``deepseek_v3`` code
  does); ``[k_nope_h | v_h] = W_kvb,h c`` EXPANDED for every head of every
  token; ``k_h = [k_nope_h | k_r]``; causal softmax of ``q_h . k_h /
  sqrt(qk_head_dim)`` over the WHOLE sequence, a block of queries at a time
  against every key; ``W_o``;
* dense ``FFN``: ``down(silu(gate u) * up u)``; sparse ``FFN``: the expert
  part as a LOOP over the held experts plus the shared expert
  (``reference/exaone_moe.py`` has the loop: the two families share the
  DeepSeek-V3 expert layer, and this file takes it from there), the chip's
  share of experts and of the vocabulary as the configuration states them.

One request at a time through each layer. Everything that is a product with
a weight runs over ``BLOCK`` rows at a time, so the compiled shapes do not
depend on the request's length and a 32 768-token request fits: only
attention sees the whole sequence. The weights are the seed's
(``benchmark.lib.weights_deepseek_v3``), made and upcast to float32 ONE
LAYER AT A TIME, so the reference never holds the model.

``precision="int8"`` is the CONTROL, not a reference: the same pass with
every block matrix, every expert matrix and the head rounded to int8 per
output channel (weight-only int8, the precision just below the bf16 the
configuration serves in). The router stays as it is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_deepseek_v3 as weights_lib
from benchmark.reference.exaone_moe import (BLOCK, _after_attention, _blocks,
                                            _head, _rms_norm, _upcast,
                                            attention, feed_forward)

MATRICES = ("q", "kv_a", "kv_b", "o", "gate", "up", "down", "w_gate", "w_up",
            "w_down", "shared_gate", "shared_up", "shared_down", "lm_head")


def _rotate_interleaved(x, positions, theta):
    """Rotary embedding of ``x`` (T, heads, D) whose pairs are ``(2i, 2i +
    1)``: to the half-split layout ``[x_0, x_2, .. | x_1, x_3, ..]``, then
    rotate-half. The result stays in that layout (q and k alike)."""
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(t, h, d)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope", "rank",
                                             "eps", "theta"))
def qkv(x, first, lw, *, heads, nope, rope, rank, eps, theta):
    """A block of rows from position ``first``: normed input -> q, k (rows,
    heads, nope + rope) and v (rows, heads, v_head_dim), K and V expanded
    from the compressed row for every head."""
    rows = x.shape[0]
    positions = first + jnp.arange(rows)
    u = _rms_norm(x, lw["input_norm"], eps)
    q = (u @ lw["q"]).reshape(rows, heads, nope + rope)
    q_rope = _rotate_interleaved(q[..., nope:], positions, theta)
    ckr = u @ lw["kv_a"]
    c = _rms_norm(ckr[:, :rank], lw["kv_a_norm"], eps)
    k_r = _rotate_interleaved(ckr[:, None, rank:], positions, theta)
    kv = (c @ lw["kv_b"]).reshape(rows, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (rows, heads, rope))], -1)
    return jnp.concatenate([q[..., :nope], q_rope], -1), k, kv[..., nope:]


def layer_weights(cfg, seed, layer, precision="float32"):
    made = weights_lib.make(cfg, seed, jnp.bfloat16, layers=[layer])
    out = {}
    for key in list(made):
        name = key[1]
        out[name] = _upcast(made.pop(key), int8=(precision == "int8"
                                                 and name in MATRICES))
    return out


def layer(cfg: dict, index: int, x, lw, block: int = BLOCK):
    """One block of the decoder on a whole sequence ``x`` (T, hidden)."""
    t = x.shape[0]
    eps = float(cfg["rms_norm_eps"])
    parts = [qkv(x[lo:hi], lo, lw, heads=cfg["num_attention_heads"],
                 nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                 rank=cfg["kv_lora_rank"], eps=eps,
                 theta=float(cfg["rope_theta"]))
             for lo, hi in _blocks(t, block)]
    q, k, v = (jnp.concatenate(p) for p in zip(*parts))
    del parts
    a = _attend(q, k, v)
    del q, k, v
    out = []
    for lo, hi in _blocks(t, block):
        x_blk, u = _after_attention(x[lo:hi], a[lo:hi], lw, eps=eps)
        out.append(x_blk + feed_forward(cfg, u, lw))
    return jnp.concatenate(out)


def _attend(q, k, v):
    """Causal softmax(q k^T / sqrt(qk_head_dim)) v, one K/V head a query
    head, keys wider than values: the values ride at the keys' width (zeros
    past ``v_head_dim``) through the family's plain attention and are cut
    back."""
    t, h, d = q.shape
    dv = v.shape[-1]
    v = jnp.concatenate([v, jnp.zeros((t, h, d - dv), v.dtype)], -1)
    return attention(q, k, v, window=None).reshape(t, h, d)[..., :dv].reshape(
        t, h * dv)


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
    ``ids`` (T,)."""
    with jax.default_matmul_precision("highest"):
        (x,), top = hidden(cfg, seed, [ids], precision, block)
        eps = float(cfg["rms_norm_eps"])
        return jnp.concatenate([
            _head(x[lo:hi], top["norm"], top["lm_head"], eps=eps)
            for lo, hi in _blocks(x.shape[0], block)])[:len(ids)]


def served_token_gaps(cfg, seed, prompts, served, width: int,
                      control: bool = False, block: int = BLOCK) -> dict:
    """As ``reference.exaone_moe.served_token_gaps``: for each request run
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
