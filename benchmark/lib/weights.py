"""Weights from ``--seed``, made on the device, in the type they are run in.

One table of leaves per architecture: ``(layer, name, shape, init)``, where
init is a normal's std, or ``"ones"`` (norm scales) / ``"zeros"`` (biases).
Every leaf
draws from its own key, folded from the seed, the layer and the leaf's
position, so the driver (all leaves in one jitted call) and the plain
reference (one layer at a time) make bit-identical arrays without handing
each other anything.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int):
    """``--seed`` may exceed 31 bits: fold the high part in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


# ----------------------------------------------------------------- tables
def gpt2_leaves(cfg: dict):
    """``[(layer or -1, name, shape, init)]`` in a fixed order. init is a
    float std, or ``"ones"`` / ``"zeros"``. GPT-2: N(0, 0.02), residual
    projections scaled by 1/sqrt(2 * n_layer)."""
    h, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * h
    std = cfg.get("initializer_range", 0.02)
    res = std / math.sqrt(2 * layers)
    out = [(-1, "wte", (cfg["vocab_size"], h), std),
           (-1, "wpe", (cfg["n_positions"], h), std),
           (-1, "ln_f.w", (h,), "ones"), (-1, "ln_f.b", (h,), "zeros")]
    for i in range(layers):
        out += [(i, "ln_1.w", (h,), "ones"), (i, "ln_1.b", (h,), "zeros"),
                (i, "attn.c_attn.w", (h, 3 * h), std),
                (i, "attn.c_attn.b", (3 * h,), "zeros"),
                (i, "attn.c_proj.w", (h, h), res),
                (i, "attn.c_proj.b", (h,), "zeros"),
                (i, "ln_2.w", (h,), "ones"), (i, "ln_2.b", (h,), "zeros"),
                (i, "mlp.c_fc.w", (h, inner), std),
                (i, "mlp.c_fc.b", (inner,), "zeros"),
                (i, "mlp.c_proj.w", (inner, h), res),
                (i, "mlp.c_proj.b", (h,), "zeros")]
    return out


def llama_like_leaves(cfg: dict):
    """Llama-shaped decoder (Mistral): every matrix N(0, initializer_range),
    stored ``[in, out]``; RMSNorm scales are ones."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    std = cfg.get("initializer_range", 0.02)
    out = [(-1, "embed", (cfg["vocab_size"], h), std),
           (-1, "norm", (h,), "ones")]
    if not cfg.get("tie_word_embeddings"):
        out.append((-1, "lm_head", (h, cfg["vocab_size"]), std))
    for i in range(cfg["num_hidden_layers"]):
        out += [(i, "input_norm", (h,), "ones"),
                (i, "q", (h, nq), std), (i, "k", (h, nkv), std),
                (i, "v", (h, nkv), std), (i, "o", (nq, h), std),
                (i, "post_norm", (h,), "ones"),
                (i, "gate", (h, inter), std), (i, "up", (h, inter), std),
                (i, "down", (inter, h), std)]
    return out


LEAVES = {"gpt2": gpt2_leaves, "llama_like": llama_like_leaves}


# ------------------------------------------------------------------ making
def _leaf(key, layer, index, shape, init, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1), index)
    return (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)


@functools.lru_cache(maxsize=None)
def _builder(rows, dtype):
    """One jitted program per (shapes, inits, dtype): layer numbers and leaf
    positions are traced, so every block of a model shares one program."""

    @jax.jit
    def build(key, layers, positions):
        return [_leaf(key, layers[j], positions[j], shape, init, dtype)
                for j, (shape, init) in enumerate(rows)]

    return build


def make(arch: str, cfg: dict, seed: int, dtype, layers=None) -> dict:
    """``{(layer, name): array}`` for the whole model, or only for the
    layers listed (``-1`` = the leaves outside the blocks), in ONE jitted
    call on the default device."""
    table = [(pos, *row) for pos, row in enumerate(LEAVES[arch](cfg))
             if layers is None or row[0] in layers]
    build = _builder(tuple((shape, init) for *_x, shape, init in table),
                     jnp.dtype(dtype))
    arrays = build(root_key(seed),
                   np.asarray([row[1] for row in table], np.int32),
                   np.asarray([row[0] for row in table], np.int32))
    return {(layer, name): a
            for (_pos, layer, name, _s, _i), a in zip(table, arrays)}
