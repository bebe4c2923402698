"""Plain forward pass of an Olmo-Hybrid decoder (``model_type``
``olmo_hybrid``: Ai2's Olmo-Hybrid-7B's block): float32 ``jax.numpy`` at
``highest`` matmul precision, written from the published config and the
layer equations of the gated delta rule (Yang, Kautz and Hatamizadeh,
arXiv:2412.06464; negative eigenvalues after Grazzi et al.,
arXiv:2411.12537). No kernels, no cache, no chunked form, no batching
tricks, nothing imported from the program:

* block ``l``: ``h = x + RMSNorm(Mixer_l(x))``, ``y = h + RMSNorm(MLP(h))``,
  the norm on each sublayer's OUTPUT (Olmo 2 / Olmo 3's placement; the
  config does not state it: ``assumed`` in the configuration file), ``MLP``
  a SwiGLU; a final RMSNorm and an untied head;
* ``linear_attention``: the recurrence ``S_t = alpha_t S_{t-1} + k_t (beta_t
  (v_t - alpha_t S_{t-1}^T k_t))^T``, ``o_t = S_t^T q_t`` as a ``lax.scan``
  over TOKENS, each step written as the rule reads (decay, read ``S^T k``,
  correct, write, read ``S^T q``: the program's decode step reads the state
  once and its prefill solves a triangular system a chunk; neither form is
  here), the causal depthwise convolution as an explicit sum over its taps
  (no bias: ``assumed``), ``q`` and ``k`` of unit length with ``q`` scaled
  by ``d_k ** -0.5``, ``beta = 2 sigmoid(.)`` (``linear_allow_neg_eigval``),
  the per-head RMSNorm of ``o`` FIRST and the SiLU gate after it;
* ``full_attention``: q and k RMS-normed over the WHOLE projection (Olmo 2 /
  3's q/k norm: ``assumed``), causal softmax attention as a masked softmax
  over the whole sequence, scale ``head_dim ** -0.5``, NO positional
  encoding (``rope_parameters.rope_theta`` null: ``assumed``).

Departures from the published description, each stated where it is made:
the unit-length normalisation adds 1e-6 under the square root (the
gated-delta-net reference code's ``l2norm`` epsilon); ``dt`` carries no
floor beyond its initialisation's.

The weights are the seed's (``benchmark.lib.weights_olmo_hybrid``), made and
upcast to float32 ONE LAYER AT A TIME, so the reference never holds the model
and fits beside nothing else than its own activations.

``precision="int8"`` is the CONTROL, not a reference: the same pass with
every projection, every MLP matrix and the head rounded to int8 per output
channel (weight-only int8, the precision just below the bf16 the
configuration serves in). The convolution, the time constants and the norm
scales stay as they are, as weight-only int8 deployments keep them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_olmo_hybrid as weights_lib

LINEAR, FULL = "linear_attention", "full_attention"
MATRICES = ("q", "k", "v", "a", "b", "g", "o", "gate", "up", "down",
            "lm_head")


def _fake_int8(w):
    """Round ``[..., in, out]`` to int8 with one scale per output."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12)
    scale = scale / 127.0
    return jnp.round(w / scale) * scale


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _unit(x):
    # departure: 1e-6 under the root, the gated-delta-net code's l2norm
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


# ------------------------------------------------------------------- mixers
@functools.partial(jax.jit, static_argnames=("heads", "dk", "dv", "eps",
                                             "neg_eigval"))
def linear_mixer(x, lw, *, heads, dk, dv, eps, neg_eigval):
    """``x`` (n, T, hidden) -> (n, T, hidden), from a zero state."""
    n, t, _h = x.shape
    key = heads * dk
    qkv = jnp.concatenate([x @ lw["q"], x @ lw["k"], x @ lw["v"]], axis=-1)
    taps = lw["conv_w"].shape[1]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = 0.0
    for j in range(taps):       # out_t = sum_j w_j in_{t - (taps-1) + j}
        conv = conv + padded[:, j:j + t, :] * lw["conv_w"][:, j]
    qkv = jax.nn.silu(conv)
    q = _unit(qkv[..., :key].reshape(n, t, heads, dk)) / np.sqrt(dk)
    k = _unit(qkv[..., key:2 * key].reshape(n, t, heads, dk))
    v = qkv[..., 2 * key:].reshape(n, t, heads, dv)
    beta = jax.nn.sigmoid(x @ lw["b"]) * (2.0 if neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(lw["A_log"])
                    * jax.nn.softplus(x @ lw["a"] + lw["dt_bias"]))

    def step(s, inp):
        q_t, k_t, v_t, a_t, b_t = inp       # (n, H, dk|dv), (n, H)
        s = a_t[..., None, None] * s                        # decay
        seen = jnp.einsum("nhkv,nhk->nhv", s, k_t)          # S^T k
        u = b_t[..., None] * (v_t - seen)                   # correction
        s = s + k_t[..., :, None] * u[..., None, :]         # rank-one write
        return s, jnp.einsum("nhkv,nhk->nhv", s, q_t)       # S^T q

    s0 = jnp.zeros((n, heads, dk, dv), jnp.float32)
    _s, o = jax.lax.scan(step, s0, (
        q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
        v.transpose(1, 0, 2, 3), alpha.transpose(1, 0, 2),
        beta.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3)                             # (n, T, H, dv)
    o = _rms_norm(o, lw["o_norm"], eps)                     # norm first
    o = o * jax.nn.silu((x @ lw["g"]).reshape(n, t, heads, dv))  # then gate
    return o.reshape(n, t, heads * dv) @ lw["o"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def attention_mixer(x, lw, *, heads, kv_heads, eps):
    n, t, _h = x.shape
    hd = lw["q"].shape[1] // heads
    q = _rms_norm(x @ lw["q"], lw["q_norm"], eps).reshape(n, t, heads, hd)
    k = _rms_norm(x @ lw["k"], lw["k_norm"], eps).reshape(n, t, kv_heads, hd)
    v = (x @ lw["v"]).reshape(n, t, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_row(qkv):           # a row at a time: (heads, T, T) scores
        q_r, k_r, v_r = qkv
        s = jnp.einsum("qhd,khd->hqk", q_r, k_r) / np.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v_r)

    a = jax.lax.map(one_row, (q, k, v))
    return a.reshape(n, t, heads * hd) @ lw["o"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _close_block(x, mixed, lw, *, eps):
    """The two post-norm residuals around a mixer's output and the MLP."""
    h = x + _rms_norm(mixed, lw["post_attn_norm"], eps)
    mlp = (jax.nn.silu(h @ lw["gate"]) * (h @ lw["up"])) @ lw["down"]
    return h + _rms_norm(mlp, lw["post_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return _rms_norm(x, norm, eps) @ lm_head


# -------------------------------------------------------------------- model
@functools.partial(jax.jit, static_argnames=("int8",))
def _upcast(a, *, int8):
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


def mixer(cfg: dict, kind: str, x, lw):
    """One block's mixer on the block's input ``x`` (no norm before it)."""
    eps = float(cfg["rms_norm_eps"])
    if kind == LINEAR:
        return linear_mixer(
            x, lw, heads=cfg["linear_num_value_heads"],
            dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
            eps=eps, neg_eigval=bool(cfg["linear_allow_neg_eigval"]))
    return attention_mixer(x, lw, heads=cfg["num_attention_heads"],
                           kv_heads=cfg["num_key_value_heads"], eps=eps)


def hidden(cfg: dict, seed: int, ids, precision: str = "float32"):
    """``([n, T, hidden] after the last block, the leaves outside the
    blocks)`` of the full forward over ``ids`` (``[n, T]``; rows shorter
    than T are padded at the end, which a causal model's earlier positions
    never see)."""
    ids = jnp.asarray(ids, jnp.int32)
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        top = layer_weights(cfg, seed, -1, precision)
        x = top.pop("embed")[ids]
        for layer, kind in enumerate(cfg["layer_types"]):
            lw = layer_weights(cfg, seed, layer, precision)
            x = _close_block(x, mixer(cfg, kind, x, lw), lw, eps=eps)
            del lw
        return x, top


def logits(cfg: dict, seed: int, ids, precision: str = "float32"):
    """``[n, T, vocab]`` float32 logits of the full forward over ``ids``."""
    x, top = hidden(cfg, seed, ids, precision)
    with jax.default_matmul_precision("highest"):
        return _head(x, top["norm"], top["lm_head"],
                     eps=float(cfg["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _first(x_row, norm, lm_head, *, eps):
    """The token one row's logits put first at every position."""
    return jnp.argmax(_head(x_row, norm, lm_head, eps=eps), axis=-1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _row_gaps(x_row, norm, lm_head, judged, mask, *, eps):
    """``(sum, max, positions with no gap)`` over one row's masked
    positions of ``best logit - the judged token's logit``."""
    ref = _head(x_row, norm, lm_head, eps=eps)
    chosen = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    gap = jnp.where(mask, jnp.max(ref, axis=-1) - chosen, 0.0)
    return jnp.sum(gap), jnp.max(gap), jnp.sum((gap == 0) & mask)


def served_token_gaps(cfg, seed, prompts, served, width: int,
                      control: bool = False, rows_at_once: int = 4) -> dict:
    """As ``reference.llama_like.served_token_gaps``: for each request run
    the reference once over prompt + served tokens and read, at every
    served token's position, the gap by which that token's logit lies
    below the reference's best; with ``control`` the token judged is the
    one the int8 pass puts first. Requests go through the blocks
    ``rows_at_once`` at a time, longest first, each group padded to its
    longest row rounded up to 1024 (never past ``width``), and through the
    head ONE ROW at a time: a row of 4096 positions over the whole
    vocabulary is 1.6 GB of float32 logits, four of them with the int8
    pass's beside would not fit."""
    order = sorted(range(len(prompts)),
                   key=lambda i: -(len(prompts[i]) + len(served[i])))
    eps = float(cfg["rms_norm_eps"])
    widest, total, agree, n = 0.0, 0.0, 0, 0
    for lo in range(0, len(order), rows_at_once):
        part = [(prompts[i], served[i]) for i in order[lo:lo + rows_at_once]]
        longest = max(len(p) + len(s) - 1 for p, s in part)
        w = min(width, -(-longest // 1024) * 1024)
        ids = np.zeros((rows_at_once, w), np.int32)
        mask = np.zeros((rows_at_once, w), bool)
        judged = np.zeros((rows_at_once, w), np.int32)
        for i, (p, s) in enumerate(part):
            row = list(p) + list(s[:-1])
            ids[i, :len(row)] = row
            mask[i, len(p) - 1:len(p) - 1 + len(s)] = True
            judged[i, len(p) - 1:len(p) - 1 + len(s)] = s
        with jax.default_matmul_precision("highest"):
            if control:     # first, so that only one pass's leaves are held
                x, top = hidden(cfg, seed, ids, "int8")
                for i in range(len(part)):
                    judged[i] = np.asarray(_first(
                        x[i], top["norm"], top["lm_head"], eps=eps))
                del x, top
            x, top = hidden(cfg, seed, ids)
            for i in range(len(part)):
                g_sum, g_max, same = _row_gaps(
                    x[i], top["norm"], top["lm_head"],
                    jnp.asarray(judged[i]), jnp.asarray(mask[i]), eps=eps)
                widest = max(widest, float(g_max))
                total += float(g_sum)
                agree += int(same)
            del x, top
        n += int(mask.sum())
    return {"logit_gap_max": widest, "logit_gap_mean": total / n,
            "top1_share": agree / n, "positions": n}
