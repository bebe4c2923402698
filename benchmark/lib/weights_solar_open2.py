"""Weights of a ``solar_open2`` configuration from ``--seed``, made on the
device in the types they are run in, one layer a call.

The table has one row a leaf, ``(layer or -1, name, shape, init, dtype)``,
in a fixed order; every leaf draws from its own key, folded from the seed,
the layer and the leaf's position in the table (``lib/weights_nemotron_h.py``
has the scheme and the builder, which this table reuses: its ``_leaf`` has
every initialisation this family needs), so the driver and the plain
reference make bit-identical arrays without handing each other anything.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from benchmark.lib.flops_solar_open2 import dims
from benchmark.lib.weights import root_key
from benchmark.lib.weights_nemotron_h import F32, RUN, _builder

#: the time step's range (log-uniform) a CHANNEL, Mamba-2's, as the Kimi
#: Linear reference initialisation takes it
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4


def layer_rows(cfg: dict, layer: int):
    """``[(name, shape, init, dtype)]`` of block ``layer``."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    d = dims(cfg)
    rows = [("input_norm", (h,), "ones", F32)]
    if layer in cfg["gqa_layers"]:
        rows += [("q", (h, d["q"]), std, RUN), ("k", (h, d["kv"]), std, RUN),
                 ("v", (h, d["kv"]), std, RUN), ("g", (h, d["q"]), std, RUN),
                 ("o", (d["q"], h), std, RUN)]
    else:
        taps = ("uniform", 1.0 / math.sqrt(d["taps"]))
        rank, wide = d["lin_head_dim"], d["lin"]
        rows += [("q", (h, wide), std, RUN), ("k", (h, wide), std, RUN),
                 ("v", (h, wide), std, RUN),
                 ("f_a", (h, rank), std, RUN), ("f_b", (rank, wide), std, RUN),
                 ("g_a", (h, rank), std, RUN), ("g_b", (rank, wide), std, RUN),
                 ("b", (h, d["lin_heads"]), std, RUN),
                 ("o", (wide, h), std, RUN),
                 ("q_conv", (wide, d["taps"]), taps, RUN),
                 ("k_conv", (wide, d["taps"]), taps, RUN),
                 ("v_conv", (wide, d["taps"]), taps, RUN),
                 ("A_log", (d["lin_heads"],), "a_log", F32),
                 ("dt_bias", (wide,), ("dt_bias", DT_MIN, DT_MAX, DT_FLOOR),
                  F32),
                 ("o_norm", (rank,), "ones", F32)]
    held, wide = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    return rows + [
        ("post_norm", (h,), "ones", F32),
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
