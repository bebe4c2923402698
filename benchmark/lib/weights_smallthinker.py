"""Weights of a ``smallthinker`` configuration from ``--seed``, made on the
device in float32 (the configuration trains float32 parameters), one layer a
call.

The table has one row a leaf, ``(layer or -1, name, shape, init, dtype)``,
in a fixed order; every leaf draws from its own key, folded from the seed,
the layer and the leaf's position in the table (``lib/weights_nemotron_h.py``
has the scheme and the builder, which this table reuses), so the driver and
the plain reference make bit-identical arrays without handing each other
anything. ``init`` is a normal's std or ``"ones"``: every matrix N(0,
``initializer_range``), the two projections that write into the residual
stream (``attn.o``, ``moe.down``) scaled by ``1 / sqrt(2 * layers)`` as
``gpt2-medium``'s are, norms 1 (all of it listed under the configuration's
``assumed``).

The expert matrices are stacked ``[experts held, in, out]``: a share's
weights are one draw of that shape, so a test that needs the experts of
several shares to be one layer's makes the uncut layer and slices it.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import root_key
from benchmark.lib.weights_nemotron_h import F32, _builder


def layer_rows(cfg: dict):
    """``[(name, shape, init, dtype)]`` of a block: every layer has the same
    leaves (what differs by layer, rotary and window, has no weight)."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    res = std / math.sqrt(2 * cfg["num_hidden_layers"])
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    held, wide = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    return [("input_norm", (h,), "ones", F32),
            ("attn.q", (h, nq), std, F32), ("attn.k", (h, nkv), std, F32),
            ("attn.v", (h, nkv), std, F32), ("attn.o", (nq, h), res, F32),
            ("post_norm", (h,), "ones", F32),
            ("moe.router", (h, cfg["router_width"]), std, F32),
            ("moe.gate", (held, h, wide), std, F32),
            ("moe.up", (held, h, wide), std, F32),
            ("moe.down", (held, wide, h), res, F32)]


def leaves(cfg: dict):
    """``[(layer or -1, name, shape, init, dtype)]`` in a fixed order."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    out = [(-1, "embed", (cfg["vocab_size"], h), std, F32),
           (-1, "final_norm", (h,), "ones", F32),
           (-1, "head", (h, cfg["vocab_size"]), std, F32)]
    for i in range(cfg["num_hidden_layers"]):
        out += [(i, *row) for row in layer_rows(cfg)]
    return out


def make(cfg: dict, seed: int, layers=None) -> dict:
    """``{(layer, name): float32 array}`` for the whole model or only the
    layers listed (``-1``: the leaves outside the blocks); one jitted call
    a layer, so that no call holds more than a layer's draws."""
    table = [(pos, *row) for pos, row in enumerate(leaves(cfg))]
    wanted = sorted({row[1] for row in table} if layers is None
                    else set(layers))
    key, out = root_key(seed), {}
    for layer in wanted:
        part = [row for row in table if row[1] == layer]
        build = _builder(tuple((shape, init, jnp.dtype(dt).name)
                               for _p, _l, _n, shape, init, dt in part))
        arrays = build(key, np.asarray([r[1] for r in part], np.int32),
                       np.asarray([r[0] for r in part], np.int32))
        out.update({(layer, r[2]): a for r, a in zip(part, arrays)})
    return out
