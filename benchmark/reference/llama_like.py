"""Plain forward pass of a Llama-shaped decoder (Mistral-7B-v0.3's block):
float32 ``jax.numpy`` at ``highest`` matmul precision, written from the
published architecture (Jiang et al. 2023, "Mistral 7B"; the
``mistralai/Mistral-7B-v0.3`` config; the Hugging Face ``modeling_mistral``
equations: RMSNorm, rotate-half RoPE, grouped-query causal attention, SwiGLU).
No kernels, no cache, no batching tricks, nothing imported from the program.

The weights are the seed's bf16 weights (``benchmark.lib.weights``), made and
upcast to float32 ONE LAYER AT A TIME, so the reference never holds the model.

``precision="int8"`` is the CONTROL, not a reference: the same pass with every
block matrix and the head rounded to int8 per output channel (weight-only
int8, the precision just below the bf16 the configuration serves in).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights as weights_lib

MATRICES = ("q", "k", "v", "o", "gate", "up", "down")


def _fake_int8(w):
    """Round a ``[in, out]`` matrix to int8 with one scale per output."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12)
    scale = scale / 127.0
    return jnp.round(w / scale) * scale


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE on ``[n, T, heads, d]`` at positions 0..T-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps"))
def _block(x, lw, *, heads, kv_heads, theta, eps):
    n, t, _h = x.shape
    y = _rms_norm(x, lw["input_norm"], eps)
    hd = lw["q"].shape[1] // heads
    q = _rope((y @ lw["q"]).reshape(n, t, heads, hd), theta)
    k = _rope((y @ lw["k"]).reshape(n, t, kv_heads, hd), theta)
    v = (y @ lw["v"]).reshape(n, t, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(n, t, heads * hd) @ lw["o"]
    y = _rms_norm(x, lw["post_norm"], eps)
    return x + (jax.nn.silu(y @ lw["gate"]) * (y @ lw["up"])) @ lw["down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return _rms_norm(x, norm, eps) @ lm_head


def _layer_weights(cfg, seed, layer, precision):
    made = weights_lib.make("llama_like", cfg, seed, jnp.bfloat16,
                            layers=[layer])
    out = {name: a.astype(jnp.float32) for (_l, name), a in made.items()}
    if precision == "int8":
        for name in out:
            if name in MATRICES or name == "lm_head":
                out[name] = _fake_int8(out[name])
    return out


def logits(cfg: dict, seed: int, ids, precision: str = "float32"):
    """``[n, T, vocab]`` float32 logits of the full forward over ``ids``
    (``[n, T]``; rows shorter than T are padded at the end, which a causal
    model's earlier positions never see)."""
    if cfg.get("sliding_window"):
        raise NotImplementedError("this reference has no sliding window")
    ids = jnp.asarray(ids, jnp.int32)
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))
    with jax.default_matmul_precision("highest"):
        top = _layer_weights(cfg, seed, -1, precision)
        x = top["embed"][ids]
        for layer in range(cfg["num_hidden_layers"]):
            x = _block(x, _layer_weights(cfg, seed, layer, precision), **kw)
        head = top["embed"].T if cfg.get("tie_word_embeddings") \
            else top["lm_head"]
        return _head(x, top["norm"], head, eps=kw["eps"])


def served_token_gaps(cfg, seed, prompts, served, width: int,
                      control: bool = False, rows_at_once: int = 4) -> dict:
    """For each request (prompt, served tokens): run the reference once over
    prompt + served tokens and read, at every served token's position, the
    gap by which that token's logit lies below the reference's best. With
    ``control`` the token judged at each position is not the served one but
    the one the int8 pass puts first there. Every row is padded to
    ``width`` (one compiled shape) and ``rows_at_once`` rows go through
    together. Returns the widest gap, the mean gap, the share of positions
    whose token is the reference's own first choice, and the number of
    positions compared."""
    widest, total, agree, n = 0.0, 0.0, 0, 0
    for lo in range(0, len(prompts), rows_at_once):
        part_p = prompts[lo:lo + rows_at_once]
        part_s = served[lo:lo + rows_at_once]
        ids = np.zeros((rows_at_once, width), np.int32)
        mask = np.zeros((rows_at_once, width), bool)
        judged = np.zeros((rows_at_once, width), np.int32)
        for i, (p, s) in enumerate(zip(part_p, part_s)):
            row = list(p) + list(s[:-1])
            ids[i, :len(row)] = row
            mask[i, len(p) - 1:len(p) - 1 + len(s)] = True
            judged[i, len(p) - 1:len(p) - 1 + len(s)] = s
        ref = logits(cfg, seed, ids)
        if control:
            judged = jnp.argmax(logits(cfg, seed, ids, "int8"), axis=-1)
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
