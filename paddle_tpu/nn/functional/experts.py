"""Dropless routed experts for a chip that holds a SHARE of them.

The router scores every expert of the model (sigmoid scores, a correction
bias that only steers the choice, top-k, weights renormalised over the
chosen and scaled: the DeepSeek-V3 / Nemotron-H router); the chip computes
the part of the result that the experts it holds, ``[lo, lo + E_held)``,
give for the tokens routed to them. No capacity, no dropped token; what the
absent experts would add is another chip's part (on one chip: left out).

``distributed.fleet.MoELayer`` is the GShard layer of the reference API
(softmax gate, capacity, dispatch / combine one-hots over per-expert
sublayers); this is the serving-side layer over stacked expert weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import dispatch
from ...core.tensor import Tensor, as_tensor

__all__ = ["sigmoid_topk_route", "held_experts_relu2"]


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def route_arrays(u, gate, bias, k, scale, normalize):
    """``u`` (n, hidden), ``gate`` (hidden, E), ``bias`` (E,): float32
    scores at full matmul precision. Returns ``(idx (n, k) int32, weights
    (n, k) float32)``."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(u.astype(f32), gate.astype(f32),
                               precision=jax.lax.Precision.HIGHEST))
    _top, idx = jax.lax.top_k(s + bias.astype(f32)[None, :], k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if normalize:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def combine_arrays(idx, w, lo, held, valid=None):
    """``(n, E_held)`` float32: the weight each held expert's output gets
    in each token's sum (0 where the token did not choose it)."""
    n = idx.shape[0]
    local = idx - lo
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & valid[:, None]
    return jnp.zeros((n, held), jnp.float32).at[
        jnp.arange(n)[:, None], jnp.where(mine, local, 0)].add(
            jnp.where(mine, w, 0.0))


def load_arrays(idx, lo, held, valid=None):
    """``(E_held + 2,)`` int32: the tokens each held expert received, then
    the (token, expert) pairs that landed on held experts and the pairs
    selected in all."""
    local = idx - lo
    live = jnp.ones(idx.shape, bool) if valid is None \
        else jnp.broadcast_to(valid[:, None], idx.shape)
    mine = (local >= 0) & (local < held) & live
    per = jnp.zeros((held,), jnp.int32).at[
        jnp.where(mine, local, 0).reshape(-1)].add(
            mine.reshape(-1).astype(jnp.int32))
    return jnp.concatenate([per, jnp.sum(mine, dtype=jnp.int32)[None],
                            jnp.sum(live, dtype=jnp.int32)[None]])


def experts_arrays(x, combine, w1, w2):
    """``sum_e combine[:, e] * relu(x W1_e)^2 W2_e`` over the held
    experts: the product over ALL of them with each token's unchosen
    experts weighted 0. ``x`` (n, latent), ``combine`` (n, E_held),
    ``w1`` (E_held, latent, width), ``w2`` (E_held, width, latent)."""
    f32 = jnp.float32
    h = jnp.einsum("nl,elf->enf", x, w1, preferred_element_type=f32)
    h = jnp.square(jax.nn.relu(h)) * combine.T[:, :, None]
    return jnp.einsum("enf,efl->nl", h.astype(x.dtype), w2,
                      preferred_element_type=f32).astype(x.dtype)


def sigmoid_topk_route(u, gate, bias, k, scale=1.0, normalize=True,
                       name=None):
    """Sigmoid-score top-k router in float32: choose the ``k`` experts with
    the largest ``sigmoid(u @ gate) + bias``; a chosen expert's weight is
    its score WITHOUT the bias, renormalised over the chosen (``normalize``)
    and times ``scale``. ``u`` (n, hidden). Returns ``(idx (n, k) int32,
    weights (n, k) float32)``."""
    def f(ua, ga, ba, **_attrs):
        return route_arrays(ua, ga, ba, k, scale, normalize)

    return dispatch.call(
        "sigmoid_topk_route", f, [_t(u), _t(gate), _t(bias)],
        attrs={"k": int(k), "scale": float(scale),
               "normalize": bool(normalize)})


def held_experts_relu2(x, idx, weights, w1, w2, lo=0, valid=None,
                       name=None):
    """The held experts' part of a routed sum, dropless: for every token
    ``sum_j weights[j] * W2_e relu(W1_e x)^2`` over its chosen experts
    ``e = idx[j]`` with ``lo <= e < lo + E_held``. ``x`` (n, latent),
    ``idx`` / ``weights`` (n, k), ``w1`` (E_held, latent, width), ``w2``
    (E_held, width, latent); ``valid`` (n,) bool drops padding rows.
    Returns (n, latent)."""
    held = _t(w1).shape[0]
    inputs = [_t(x), _t(idx), _t(weights), _t(w1), _t(w2)]
    if valid is not None:
        inputs.append(_t(valid))

    def f(xa, ia, wa, w1a, w2a, *va, **_attrs):
        combine = combine_arrays(ia, wa, lo, held, va[0] if va else None)
        return experts_arrays(xa, combine, w1a, w2a)

    return dispatch.call(
        "held_experts_relu2", f, inputs, attrs={"lo": int(lo)},
        differentiable_mask=[True, False, True, True, True]
        + [False] * (valid is not None))
