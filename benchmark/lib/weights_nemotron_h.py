"""Weights of a ``nemotron_h`` configuration from ``--seed``, made on the
device in the types they are run in, one layer a call.

The table has one row a leaf, ``(layer or -1, name, shape, init, dtype)``,
in a fixed order; every leaf draws from its own key, folded from the seed,
the layer and the leaf's position in the table (as ``lib/weights.py`` does),
so the driver and the plain reference make bit-identical arrays without
handing each other anything. ``init`` is a normal's std, ``"ones"`` /
``"zeros"``, ``("uniform", a)`` for U(-a, a), ``"a_log"`` for
``log(U[1, 16])`` or ``("dt_bias", lo, hi, floor)`` for the inverse softplus
of a log-uniform time step (the Mamba-2 reference initialisation).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.flops_nemotron_h import dims
from benchmark.lib.weights import root_key

F32, RUN = "float32", "run"      # "run": the dtype the model is served in


def layer_rows(cfg: dict, kind: str):
    """``[(name, shape, init, dtype)]`` of one block of ``kind``."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    d = dims(cfg)
    rows = [("norm", (h,), "ones", F32)]
    if kind == "M":
        nh = cfg["mamba_num_heads"]
        rows += [
            ("in_proj", (h, d["in_proj"]), std, RUN),
            ("conv_w", (d["conv"], cfg["conv_kernel"]),
             ("uniform", 1.0 / math.sqrt(cfg["conv_kernel"])), RUN),
            ("conv_b", (d["conv"],), std, RUN),
            ("A_log", (nh,), "a_log", F32),
            ("dt_bias", (nh,), ("dt_bias", cfg["time_step_min"],
                                cfg["time_step_max"],
                                cfg["time_step_floor"]), F32),
            ("D", (nh,), "ones", F32),
            ("gated_norm", (d["d_in"],), "ones", F32),
            ("out_proj", (d["d_in"], h), std, RUN)]
    elif kind == "*":
        rows += [("q", (h, d["q"]), std, RUN), ("k", (h, d["kv"]), std, RUN),
                 ("v", (h, d["kv"]), std, RUN), ("o", (d["q"], h), std, RUN)]
    elif kind == "E":
        lat, wid = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
        held = cfg["n_routed_experts"]          # the experts held here
        shared = cfg["moe_shared_expert_intermediate_size"]
        rows += [
            ("gate", (h, cfg["router_width"]), std, RUN),
            ("e_score_correction_bias", (cfg["router_width"],), "zeros", F32),
            ("latent_down", (h, lat), std, RUN),
            ("latent_up", (lat, h), std, RUN),
            ("w1", (held, lat, wid), std, RUN),
            ("w2", (held, wid, lat), std, RUN),
            ("shared_up", (h, shared), std, RUN),
            ("shared_down", (shared, h), std, RUN)]
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return rows


def leaves(cfg: dict):
    """``[(layer or -1, name, shape, init, dtype)]`` in a fixed order."""
    h, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    out = [(-1, "embed", (cfg["vocab_size"], h), std, RUN),
           (-1, "norm_f", (h,), "ones", F32),
           (-1, "lm_head", (h, cfg["vocab_size"]), std, RUN)]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        out += [(i, *row) for row in layer_rows(cfg, kind)]
    return out


def _leaf(key, layer, index, shape, init, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1), index)
    if init == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    if isinstance(init, tuple) and init[0] == "uniform":
        return jax.random.uniform(k, shape, jnp.float32, -init[1],
                                  init[1]).astype(dtype)
    if isinstance(init, tuple) and init[0] == "dt_bias":
        _name, lo, hi, floor = init
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)


@functools.lru_cache(maxsize=None)
def _builder(rows):
    """One jitted program per (shapes, inits, dtypes): layer numbers and
    leaf positions are traced, so blocks of one kind share a program."""

    @jax.jit
    def build(key, layers, positions):
        return [_leaf(key, layers[j], positions[j], shape, init,
                      jnp.dtype(dtype))
                for j, (shape, init, dtype) in enumerate(rows)]

    return build


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
