"""Plain forward pass of a K-EXAONE decoder (``model_type`` ``exaone_moe``:
K-EXAONE-236B-A23B's block): float32 ``jax.numpy`` at ``highest`` matmul
precision, written from the published config and the family's layer
equations (EXAONE 4.0's hybrid attention: per-head RMSNorm on q and k,
rotary embedding on the sliding-window layers only; the DeepSeek-V3 expert
layer the family takes: sigmoid scores, a correction bias that only steers
the choice, top-k renormalised and scaled, a shared expert). No kernels, no
cache, no batching, nothing imported from the program:

* block ``l``: ``a = x + Attn_l(RMSNorm(x))``, ``y = a + FFN_l(RMSNorm(a))``;
* ``Attn_l``: grouped-query attention over the WHOLE sequence, a block of
  queries at a time against every key, under the layer's mask: causal for
  ``full_attention`` (no positional encoding), ``0 <= i - j < window`` for
  ``sliding_attention`` (rotate-half rotary embedding on q and k);
* dense ``FFN``: ``down(silu(gate u) * up u)``; sparse ``FFN``: the expert
  part as a LOOP over the held experts, each applied to every token and
  weighted by that token's routing weight for it (0 where the token did not
  choose it), plus the shared expert; the chip's share of experts and of
  the vocabulary as the configuration states them: what the absent experts
  would add is left out, here as in the program.

One request at a time through each layer. Everything that is a product with
a weight runs over ``BLOCK`` rows at a time, so the compiled shapes do not
depend on the request's length and an 18 432-token request fits: only
attention sees the whole sequence. The weights are the seed's
(``benchmark.lib.weights_exaone_moe``), made and upcast to float32 ONE LAYER
AT A TIME (every request of a comparison passes through a layer before the
next layer's weights are made), so the reference never holds the model.

``precision="int8"`` is the CONTROL, not a reference: the same pass with
every block matrix, every expert matrix and the head rounded to int8 per
output channel (weight-only int8, the precision just below the bf16 the
configuration serves in). The router stays as it is, as weight-only int8
deployments keep it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_exaone_moe as weights_lib

MATRICES = ("q", "k", "v", "o", "gate", "up", "down", "w_gate", "w_up",
            "w_down", "shared_gate", "shared_up", "shared_down", "lm_head")
SLIDING = "sliding_attention"

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


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _rotate(x, positions, theta):
    """Rotary embedding, rotate-half: ``x`` (T, heads, D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


# ------------------------------------------------------------------- layers
@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta"))
def qkv(x, first, lw, *, heads, kv_heads, eps, theta):
    """A block of rows from position ``first``: normed input -> q, k, v
    (rows, heads, D), q and k RMS-normed per head and, for ``theta`` not
    None, rotated."""
    rows = x.shape[0]
    u = _rms_norm(x, lw["input_norm"], eps)
    q = _rms_norm((u @ lw["q"]).reshape(rows, heads, -1), lw["q_norm"], eps)
    k = _rms_norm((u @ lw["k"]).reshape(rows, kv_heads, -1), lw["k_norm"],
                  eps)
    v = (u @ lw["v"]).reshape(rows, kv_heads, -1)
    if theta is not None:
        positions = first + jnp.arange(rows)
        q, k = _rotate(q, positions, theta), _rotate(k, positions, theta)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("window",))
def attention(q, k, v, *, window):
    """``q`` (T, H, D), ``k`` / ``v`` (T, KVH, D) -> (T, H * D): softmax(q
    k^T / sqrt(D) + mask) v; ``window`` None is causal, else key j is seen
    from query i iff ``0 <= i - j < window``. ``QUERY_BLOCK`` queries at a
    time against every key."""
    t, h, d = q.shape
    kvh = k.shape[1]
    qb = math.gcd(t, QUERY_BLOCK)
    qg = q.reshape(t // qb, qb, kvh, h // kvh, d)
    j = jnp.arange(t)[None, :]

    def one_block(args):
        q_blk, first = args
        i = first + jnp.arange(qb)[:, None]
        seen = (i - j >= 0) if window is None \
            else ((i - j >= 0) & (i - j < window))
        s = jnp.einsum("qkgd,skd->kgqs", q_blk, k) / np.sqrt(d)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one_block, (qg, jnp.arange(0, t, qb)))
    return out.reshape(t, h * d)


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
                                             "lo"))
def moe(u, lw, *, top_k, scale, normalize, lo):
    """The held experts' part (``lo`` is the first of them) plus the
    shared expert, on normed rows ``u`` (n, hidden)."""
    w = route(u, lw, top_k, scale, normalize)
    held = lw["w_gate"].shape[0]

    def one_expert(acc, inp):
        gate, up, down, w_e = inp                       # w_e (n,)
        return acc + w_e[:, None] * _swiglu(u, gate, up, down), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (lw["w_gate"], lw["w_up"], lw["w_down"], w[:, lo:lo + held].T))
    return routed + _swiglu(u, lw["shared_gate"], lw["shared_up"],
                            lw["shared_down"])


def feed_forward(cfg: dict, u, lw):
    """A layer's FFN on normed rows: dense where the layer holds ``gate``,
    else the expert layer."""
    if "gate" in lw:
        return _swiglu(u, lw["gate"], lw["up"], lw["down"])
    return moe(u, lw, top_k=cfg["num_experts_per_tok"],
               scale=float(cfg["routed_scaling_factor"]),
               normalize=bool(cfg["norm_topk_prob"]),
               lo=int(cfg.get("experts_held", (0, 0))[0]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _after_attention(x, a, lw, *, eps):
    """``x + a W_o`` and its normed copy, for a block of rows."""
    x = x + a @ lw["o"]
    return x, _rms_norm(x, lw["post_norm"], eps)


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


def layer(cfg: dict, index: int, x, lw, block: int = BLOCK):
    """One block of the decoder on a whole sequence ``x`` (T, hidden)."""
    t = x.shape[0]
    eps = float(cfg["rms_norm_eps"])
    window = (int(cfg["sliding_window"])
              if cfg["layer_types"][index] == SLIDING else None)
    theta = (float(cfg["rope_parameters"]["rope_theta"])
             if window is not None else None)
    parts = [qkv(x[lo:hi], lo, lw, heads=cfg["num_attention_heads"],
                 kv_heads=cfg["num_key_value_heads"], eps=eps, theta=theta)
             for lo, hi in _blocks(t, block)]
    q, k, v = (jnp.concatenate(p) for p in zip(*parts))
    del parts
    a = attention(q, k, v, window=window)
    del q, k, v
    out = []
    for lo, hi in _blocks(t, block):
        x_blk, u = _after_attention(x[lo:hi], a[lo:hi], lw, eps=eps)
        out.append(x_blk + feed_forward(cfg, u, lw))
    return jnp.concatenate(out)


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
    widest, total, agree, n = 0.0, 0.0, 0, 0
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
