"""Weights of an ``olmo_hybrid`` configuration from ``--seed``, made on the
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

from benchmark.lib.flops_olmo_hybrid import FULL, LINEAR, dims
from benchmark.lib.weights import root_key
from benchmark.lib.weights_nemotron_h import F32, RUN, _builder

#: the time step's range (log-uniform), as the gated-delta-net reference
#: initialisation takes it from Mamba-2
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4


def layer_rows(cfg: dict, kind: str):
    """``[(name, shape, init, dtype)]`` of one block of ``kind``."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    d = dims(cfg)
    nh = cfg["linear_num_value_heads"]
    taps = cfg["linear_conv_kernel_dim"]
    if kind == LINEAR:
        rows = [("q", (h, d["key"]), std, RUN),
                ("k", (h, d["key"]), std, RUN),
                ("v", (h, d["value"]), std, RUN),
                ("a", (h, nh), std, RUN), ("b", (h, nh), std, RUN),
                ("g", (h, d["value"]), std, RUN),
                ("o", (d["value"], h), std, RUN),
                ("conv_w", (d["conv"], taps),
                 ("uniform", 1.0 / math.sqrt(taps)), RUN),
                ("A_log", (nh,), "a_log", F32),
                ("dt_bias", (nh,), ("dt_bias", DT_MIN, DT_MAX, DT_FLOOR),
                 F32),
                ("o_norm", (cfg["linear_value_head_dim"],), "ones", F32)]
    elif kind == FULL:
        rows = [("q", (h, d["q"]), std, RUN), ("k", (h, d["kv"]), std, RUN),
                ("v", (h, d["kv"]), std, RUN), ("o", (d["q"], h), std, RUN),
                ("q_norm", (d["q"],), "ones", F32),
                ("k_norm", (d["kv"],), "ones", F32)]
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return rows + [
        ("post_attn_norm", (h,), "ones", F32),
        ("gate", (h, cfg["intermediate_size"]), std, RUN),
        ("up", (h, cfg["intermediate_size"]), std, RUN),
        ("down", (cfg["intermediate_size"], h), std, RUN),
        ("post_mlp_norm", (h,), "ones", F32)]


def leaves(cfg: dict):
    """``[(layer or -1, name, shape, init, dtype)]`` in a fixed order."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    out = [(-1, "embed", (cfg["vocab_size"], h), std, RUN),
           (-1, "norm", (h,), "ones", F32),
           (-1, "lm_head", (h, cfg["vocab_size"]), std, RUN)]
    for i, kind in enumerate(cfg["layer_types"]):
        out += [(i, *row) for row in layer_rows(cfg, kind)]
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
