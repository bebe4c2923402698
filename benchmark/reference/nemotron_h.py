"""Plain forward pass of a Nemotron-H hybrid decoder (``model_type``
``nemotron_h``: NVIDIA-Nemotron-3-Super-120B-A12B's block): float32
``jax.numpy`` at ``highest`` matmul precision, written from the published
config and the family's layer equations (Hugging Face ``modeling_nemotron_h``;
Dao & Gu 2024 for the Mamba-2 recurrence; the DeepSeek-V3 router the family's
expert layer takes: sigmoid scores, a correction bias that only steers the
choice, top-k renormalised and scaled). No kernels, no cache, no batching
tricks, nothing imported from the program:

* block ``i``: ``x <- x + mixer_i(RMSNorm_i(x))``, one mixer a block;
* ``M``: the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t`` as a ``lax.scan`` over TIME (not the chunked
  form the program prefills with), the causal depthwise convolution as an
  explicit sum over its taps, the gated group norm (gate first);
* ``*``: causal grouped-query attention, no positional encoding;
* ``E``: the expert part as a LOOP over the held experts, each applied to
  every token and weighted by that token's routing weight for it (0 where
  the token did not choose it); the chip's share of experts and of the
  vocabulary as the configuration states them: what the absent experts would
  add is left out, here as in the program.

The weights are the seed's (``benchmark.lib.weights_nemotron_h``), made and
upcast to float32 ONE LAYER AT A TIME, so the reference never holds the model.

``precision="int8"`` is the CONTROL, not a reference: the same pass with
every block matrix, every expert matrix and the head rounded to int8 per
output channel (weight-only int8, the precision just below the bf16 the
configuration serves in). The router and the convolution stay as they are,
as weight-only int8 deployments keep them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_nemotron_h as weights_lib

MATRICES = ("in_proj", "out_proj", "q", "k", "v", "o", "latent_down",
            "latent_up", "w1", "w2", "shared_up", "shared_down", "lm_head")


def _fake_int8(w):
    """Round ``[..., in, out]`` to int8 with one scale per output."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12)
    scale = scale / 127.0
    return jnp.round(w / scale) * scale


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ------------------------------------------------------------------- mixers
@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "groups",
                                             "state", "eps"))
def mamba_mixer(u, lw, *, heads, head_dim, groups, state, eps):
    """``u`` (n, T, hidden) -> (n, T, hidden), from zero state."""
    n, t, _h = u.shape
    d_in = heads * head_dim
    gn = groups * state
    proj = u @ lw["in_proj"]
    z, xbc, dt = (proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * gn],
                  proj[..., 2 * d_in + 2 * gn:])
    taps = lw["conv_w"].shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = lw["conv_b"]
    for j in range(taps):       # out_t = sum_j w_j in_{t - (taps-1) + j}
        conv = conv + padded[:, j:j + t, :] * lw["conv_w"][:, j]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_in].reshape(n, t, heads, head_dim)
    per = heads // groups
    b = jnp.repeat(xbc[..., d_in:d_in + gn].reshape(n, t, groups, state),
                   per, axis=2)                        # head h: group h // per
    c = jnp.repeat(xbc[..., d_in + gn:].reshape(n, t, groups, state),
                   per, axis=2)
    dt = jax.nn.softplus(dt + lw["dt_bias"])           # (n, T, heads)
    a = -jnp.exp(lw["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp                      # (n, H, P) (n, H, N)
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return s, jnp.einsum("nhpk,nhk->nhp", s, c_t)

    s0 = jnp.zeros((n, heads, head_dim, state), jnp.float32)
    _s, y = jax.lax.scan(step, s0, (x.transpose(1, 0, 2, 3),
                                    b.transpose(1, 0, 2, 3),
                                    c.transpose(1, 0, 2, 3),
                                    dt.transpose(1, 0, 2)))
    y = y.transpose(1, 0, 2, 3) + lw["D"][None, None, :, None] * x
    y = y.reshape(n, t, d_in) * jax.nn.silu(z)         # gate, then norm
    y = y.reshape(n, t, groups, d_in // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(n, t, d_in) * lw["gated_norm"]) @ lw["out_proj"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads"))
def attention_mixer(u, lw, *, heads, kv_heads):
    n, t, _h = u.shape
    hd = lw["q"].shape[1] // heads
    q = (u @ lw["q"]).reshape(n, t, heads, hd)
    k = (u @ lw["k"]).reshape(n, t, kv_heads, hd)
    v = (u @ lw["v"]).reshape(n, t, kv_heads, hd)
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


def route(u, lw, top_k, scale, normalize):
    """``(n, E)`` float32: each token's weight for each expert of the
    ROUTER's width, 0 where the expert is not among its ``top_k``."""
    s = jax.nn.sigmoid(u @ lw["gate"])
    order = jnp.argsort(-(s + lw["e_score_correction_bias"]), axis=-1)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order[:, :top_k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "normalize",
                                             "lo"))
def moe_mixer(u, lw, *, top_k, scale, normalize, lo):
    """The held experts' part (``lo`` is the first of them) plus the
    shared expert."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    w = route(u, lw, top_k, scale, normalize)
    held = lw["w1"].shape[0]
    latent = u @ lw["latent_down"]

    def one_expert(acc, inp):
        w1, w2, w_e = inp                              # w_e (n,)
        return acc + w_e[:, None] * (_relu2(latent @ w1) @ w2), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(latent),
        (lw["w1"], lw["w2"], w[:, lo:lo + held].T))
    out = routed @ lw["latent_up"] \
        + _relu2(u @ lw["shared_up"]) @ lw["shared_down"]
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return _rms_norm(x, norm, eps) @ lm_head


# -------------------------------------------------------------------- model
@functools.partial(jax.jit, static_argnames=("int8",))
def _upcast(a, *, int8):
    """One leaf to float32 (and, for the control, through int8) in one
    fused pass: an expert layer's stacked matrices are 1.4 GB each."""
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


def mixer(cfg: dict, kind: str, u, lw):
    """One block's mixer on the normed input ``u``."""
    if kind == "M":
        return mamba_mixer(
            u, lw, heads=cfg["mamba_num_heads"],
            head_dim=cfg["mamba_head_dim"], groups=cfg["n_groups"],
            state=cfg["ssm_state_size"],
            eps=float(cfg["layer_norm_epsilon"]))
    if kind == "*":
        return attention_mixer(u, lw, heads=cfg["num_attention_heads"],
                               kv_heads=cfg["num_key_value_heads"])
    return moe_mixer(u, lw, top_k=cfg["num_experts_per_tok"],
                     scale=float(cfg["routed_scaling_factor"]),
                     normalize=bool(cfg["norm_topk_prob"]),
                     lo=int(cfg.get("experts_held", (0, 0))[0]))


def logits(cfg: dict, seed: int, ids, precision: str = "float32"):
    """``[n, T, vocab]`` float32 logits of the full forward over ``ids``
    (``[n, T]``; rows shorter than T are padded at the end, which a causal
    model's earlier positions never see)."""
    ids = jnp.asarray(ids, jnp.int32)
    eps = float(cfg["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        top = layer_weights(cfg, seed, -1, precision)
        x = top["embed"][ids]
        for layer, kind in enumerate(cfg["hybrid_override_pattern"]):
            lw = layer_weights(cfg, seed, layer, precision)
            x = x + mixer(cfg, kind, _rms_norm(x, lw["norm"], eps), lw)
            del lw
        return _head(x, top["norm_f"], top["lm_head"], eps=eps)


def served_token_gaps(cfg, seed, prompts, served, width: int,
                      control: bool = False, rows_at_once: int = 4) -> dict:
    """As ``reference.llama_like.served_token_gaps``: for each request run
    the reference once over prompt + served tokens and read, at every
    served token's position, the gap by which that token's logit lies
    below the reference's best; with ``control`` the token judged is the
    one the int8 pass puts first. Requests go through ``rows_at_once`` at a
    time, longest first, each group padded to its longest row rounded up
    to 1024 (never past ``width``), so a group costs what its own lengths
    ask and the shapes compiled stay few."""
    order = sorted(range(len(prompts)),
                   key=lambda i: -(len(prompts[i]) + len(served[i])))
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
        if control:     # first, so that only one pass's logits are held
            judged = jnp.argmax(logits(cfg, seed, ids, "int8"), axis=-1)
        ref = logits(cfg, seed, ids)
        best = jnp.max(ref, axis=-1)
        chosen = jnp.take_along_axis(
            ref, jnp.asarray(judged)[..., None], axis=-1)[..., 0]
        gap = jnp.where(jnp.asarray(mask), best - chosen, 0.0)
        widest = max(widest, float(jnp.max(gap)))
        total += float(jnp.sum(gap))
        agree += int(jnp.sum((gap == 0) & jnp.asarray(mask)))
        n += int(mask.sum())
    return {"logit_gap_max": widest, "logit_gap_mean": total / n,
            "top1_share": agree / n, "positions": n}
