"""Weights of a ``deepseek_v3`` configuration from ``--seed``, made on the
device in the types they are run in, one layer a call.

The table has one row a leaf, ``(layer or -1, name, shape, init, dtype)``,
in a fixed order; every leaf draws from its own key, folded from the seed,
the layer and the leaf's position in the table (``lib/weights_nemotron_h.py``
has the scheme and the builder, which this table reuses), so the driver and
the plain reference make bit-identical arrays without handing each other
anything. ``init`` is a normal's std, ``"ones"`` or ``"zeros"``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark.lib.flops_deepseek_v3 import dims
from benchmark.lib.weights import root_key
from benchmark.lib.weights_nemotron_h import F32, RUN, _builder


def layer_rows(cfg: dict, layer: int):
    """``[(name, shape, init, dtype)]`` of block ``layer``."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    d, rank = dims(cfg), cfg["kv_lora_rank"]
    rows = [("input_norm", (h,), "ones", F32),
            ("q", (h, d["q"]), std, RUN), ("kv_a", (h, d["row"]), std, RUN),
            ("kv_a_norm", (rank,), "ones", F32),
            ("kv_b", (rank, d["kv_b"]), std, RUN),
            ("o", (d["o"], h), std, RUN),
            ("post_norm", (h,), "ones", F32)]
    if layer < cfg["first_k_dense_replace"]:
        wide = cfg["intermediate_size"]
        return rows + [("gate", (h, wide), std, RUN),
                       ("up", (h, wide), std, RUN),
                       ("down", (wide, h), std, RUN)]
    held, wide = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    return rows + [
        ("router", (h, cfg["router_width"]), std, RUN),
        ("e_score_correction_bias", (cfg["router_width"],), "zeros", F32),
        ("w_gate", (held, h, wide), std, RUN),
        ("w_up", (held, h, wide), std, RUN),
        ("w_down", (held, wide, h), std, RUN),
        ("shared_gate", (h, d["shared"]), std, RUN),
        ("shared_up", (h, d["shared"]), std, RUN),
        ("shared_down", (d["shared"], h), std, RUN)]


def leaves(cfg: dict):
    """``[(layer or -1, name, shape, init, dtype)]`` in a fixed order."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    out = [(-1, "embed", (cfg["vocab_size"], h), std, RUN),
           (-1, "norm", (h,), "ones", F32),
           (-1, "lm_head", (h, cfg["vocab_size"]), std, RUN)]
    for i in range(cfg["num_hidden_layers"]):
        out += [(i, *row) for row in layer_rows(cfg, i)]
    return out


def make(cfg: dict, seed: int, dtype, layers=None) -> dict:
    """``{(layer, name): array}`` for the whole model or only the layers
    listed (``-1``: the leaves outside the blocks); one jitted call a
    layer, so that no call holds more than a layer's float32 draws."""
    run = jnp.dtype(dtype).name
    table = [(pos, *row) for pos, row in enumerate(leaves(cfg))]
    wanted = sorted({row[1] for row in table} if layers is None
                    else set(layers))
    key, out = root_key(seed), {}
    for layer in wanted:
        part = [row for row in table if row[1] == layer]
        build = _builder(tuple(
            (shape, init, run if dt == RUN else dt)
            for _p, _l, _n, shape, init, dt in part))
        arrays = build(key, np.asarray([r[1] for r in part], np.int32),
                       np.asarray([r[0] for r in part], np.int32))
        out.update({(layer, r[2]): a for r, a in zip(part, arrays)})
    return out
