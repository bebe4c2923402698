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

__all__ = ["sigmoid_topk_route", "held_experts_relu2",
           "held_experts_swiglu"]


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


def experts_arrays(x, combine, mats):
    """``sum_e combine[:, e] * E_e(x)`` over the held experts: the product
    over ALL of them with each token's unchosen experts weighted 0. The
    expert's form follows from the matrices it is made of: two, ``(w1,
    w2)``, give ``relu(x W1_e)^2 W2_e``; three, ``(gate, up, down)``, the
    SwiGLU ``(silu(x G_e) * x U_e) D_e``. ``x`` (n, in), ``combine``
    (n, E_held), first matrices (E_held, in, width), last (E_held, width,
    out)."""
    f32 = jnp.float32
    *first, last = mats
    h = jnp.einsum("nl,elf->enf", x, first[0], preferred_element_type=f32)
    if len(first) == 1:
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.silu(h) * jnp.einsum("nl,elf->enf", x, first[1],
                                        preferred_element_type=f32)
    h = h * combine.T[:, :, None]
    return jnp.einsum("enf,efl->nl", h.astype(x.dtype), last,
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


def _held_experts(op, x, idx, weights, mats, lo, valid):
    """The held experts' part of a routed sum, dropless, for an expert
    made of ``mats`` (``experts_arrays`` has the forms)."""
    mats = [_t(m) for m in mats]
    held = mats[0].shape[0]
    inputs = [_t(x), _t(idx), _t(weights), *mats]
    if valid is not None:
        inputs.append(_t(valid))

    def f(xa, ia, wa, *rest, **_attrs):
        ms, va = rest[:len(mats)], rest[len(mats):]
        combine = combine_arrays(ia, wa, lo, held, va[0] if va else None)
        return experts_arrays(xa, combine, ms)

    return dispatch.call(
        op, f, inputs, attrs={"lo": int(lo)},
        differentiable_mask=[True, False, True] + [True] * len(mats)
        + [False] * (valid is not None))


def held_experts_relu2(x, idx, weights, w1, w2, lo=0, valid=None,
                       name=None):
    """The held experts' part of a routed sum, dropless: for every token
    ``sum_j weights[j] * W2_e relu(W1_e x)^2`` over its chosen experts
    ``e = idx[j]`` with ``lo <= e < lo + E_held``. ``x`` (n, latent),
    ``idx`` / ``weights`` (n, k), ``w1`` (E_held, latent, width), ``w2``
    (E_held, width, latent); ``valid`` (n,) bool drops padding rows.
    Returns (n, latent)."""
    return _held_experts("held_experts_relu2", x, idx, weights, (w1, w2),
                         lo, valid)


def held_experts_swiglu(x, idx, weights, w_gate, w_up, w_down, lo=0,
                        valid=None, name=None):
    """As ``held_experts_relu2`` for SwiGLU experts: ``sum_j weights[j] *
    D_e (silu(G_e x) * U_e x)``. ``w_gate`` / ``w_up`` (E_held, hidden,
    width), ``w_down`` (E_held, width, hidden). Returns (n, hidden)."""
    return _held_experts("held_experts_swiglu", x, idx, weights,
                         (w_gate, w_up, w_down), lo, valid)
