"""Dropless routed experts for a chip that holds a SHARE of them.

The router scores every expert of the model (``sigmoid_topk_route``: sigmoid
scores, a correction bias that only steers the choice, top-k, weights
renormalised over the chosen and scaled, the DeepSeek-V3 / Nemotron-H
router; ``softmax_topk_route``: the ``k`` largest logits and a softmax over
those ``k``, SmallThinker's); the chip computes the part of the result that
the experts it holds, ``[lo, lo + E_held)``, give for the tokens routed to
them. The routing is the caller's to make: from the expert layer's own
input, or from another tensor of the block (SmallThinker routes from the
attention's input). No capacity, no dropped token; what the
absent experts would add is another chip's part (on one chip: left out).

``distributed.fleet.MoELayer`` is the GShard layer of the reference API
(softmax gate, capacity, dispatch / combine one-hots over per-expert
sublayers); this is the layer over stacked expert weights that the paged
engine serves AND ``Engine.fit`` trains: the router's scores, the chosen
weights and the expert matrices all take gradients (the choice itself and
the correction bias, which only steers it, take none).

**Two forms of the held experts' product, one definition.**
``experts_arrays`` is the definition: every token through ALL held experts,
the unchosen weighted 0. It is also the form a call of few rows takes: 64
decode lanes or a prefill chunk stream every held expert's weights whatever
they choose, and there the masked product was measured 2x faster than any
sort (PERF.md, PR 26 and PR 30). ``grouped_experts_arrays`` computes the
same sum over the (token, expert) pairs that LANDED here, sorted by expert,
as one grouped matmul a matrix (``jax.lax.ragged_dot``, which XLA lowers to
a Mosaic grouped-matmul kernel on a TPU), and multiplies the pairs, not
``rows x held``: the form of a call of ``GROUPED_MIN_ROWS`` rows or more (a
training batch). What it still pays for is its buffer: dropless with static
shapes means all ``rows x k`` pairs are sorted, gathered and un-sorted, four
times the landed ones under even routing (PERF.md sections 5 and 7, PR 39). Which one a call takes follows from its shapes where it is
traced (``takes_grouped_form``): no flag, no environment variable, no
config key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import dispatch
from ...core.tensor import Tensor, as_tensor

__all__ = ["sigmoid_topk_route", "softmax_topk_route", "held_experts_relu2",
           "held_experts_swiglu"]

#: what a gated expert applies to its gate: ``D (act(G x) * U x)``
GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}

#: rows from which a call takes the grouped form: the crossover measured on
#: a v5e (PR 39; 8 held experts of 2048 x 1792 in bfloat16, top-4 of 32,
#: forward + backward, masked against grouped: 512 rows 2.20 / 3.87 ms, 2048
#: rows 7.27 / 6.73, 4096 rows 13.4 / 12.5, 16 384 rows 54.5 / 40.1). The
#: grouped kernel walks the sorted pairs in tiles of 512 rows a group, so
#: below a few tiles a held expert it runs mostly empty tiles while the
#: masked product streams the same weights once; every serving program
#: (decode lanes, prefill chunks of at most 512 rows) stays below it. At 40
#: held experts of 4096 x 1280, top-8 of 320 (Solar Open 2's share, PR 44,
#: forward alone on a v5e, masked against grouped): 256 rows 2.03 / 4.75 ms,
#: 512 rows (a chunk with 256 lanes aboard) 3.59 / 4.99: the masked form
#: multiplies 40x the landed pairs and still wins, because the 1.26 GB of
#: held weights are streamed either way (1.54 ms at 819 GB/s) and the
#: grouped form adds the sort, two gathers and mostly empty tiles. The rule
#: takes neither the held count nor ``top_k`` in.
GROUPED_MIN_ROWS = 2048


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def route_arrays(u, gate, bias, k, scale, normalize, norm_eps=1e-20):
    """``u`` (n, hidden), ``gate`` (hidden, E), ``bias`` (E,): float32
    scores at full matmul precision. Returns ``(idx (n, k) int32, weights
    (n, k) float32)``. ``norm_eps`` is what the renormalisation adds to the
    chosen scores' sum (a published model's own: LFM2 adds 1e-6)."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(u.astype(f32), gate.astype(f32),
                               precision=jax.lax.Precision.HIGHEST))
    _top, idx = jax.lax.top_k(s + bias.astype(f32)[None, :], k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if normalize:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + norm_eps)
    return idx.astype(jnp.int32), w * scale


def softmax_route_arrays(u, gate, k):
    """``u`` (n, hidden), ``gate`` (hidden, E): float32 logits at full
    matmul precision, the ``k`` largest chosen, a softmax over those ``k``.
    Returns ``(idx (n, k) int32, weights (n, k) float32)``."""
    f32 = jnp.float32
    logits = jnp.dot(u.astype(f32), gate.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=1)


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


def experts_arrays(x, combine, mats, activation="silu"):
    """``sum_e combine[:, e] * E_e(x)`` over the held experts: the product
    over ALL of them with each token's unchosen experts weighted 0. The
    expert's form follows from the matrices it is made of: two, ``(w1,
    w2)``, give ``relu(x W1_e)^2 W2_e``; three, ``(gate, up, down)``, the
    gated ``(act(x G_e) * x U_e) D_e`` with ``activation`` ``silu`` (SwiGLU)
    or ``relu`` (ReGLU). ``x`` (n, in), ``combine`` (n, E_held), first
    matrices (E_held, in, width), last (E_held, width, out)."""
    f32 = jnp.float32
    *first, last = mats
    h = jnp.einsum("nl,elf->enf", x, first[0], preferred_element_type=f32)
    if len(first) == 1:
        h = jnp.square(jax.nn.relu(h))
    else:
        h = GATE_ACTIVATIONS[activation](h) * jnp.einsum(
            "nl,elf->enf", x, first[1], preferred_element_type=f32)
    h = h * combine.T[:, :, None]
    return jnp.einsum("enf,efl->nl", h.astype(x.dtype), last,
                      preferred_element_type=f32).astype(x.dtype)


def takes_grouped_form(rows: int) -> bool:
    """Whether a call over ``rows`` tokens takes the grouped form: decided
    from the call's shapes alone, where it is traced."""
    return rows >= GROUPED_MIN_ROWS


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_of_pairs(x, perm, inv, live, k):
    """``x[perm // k]``: the token row of each sorted pair. Its cotangent is
    gathered back through ``inv`` (the inverse permutation) and summed over
    a token's ``k`` pairs: no scatter in either direction. ``live`` marks
    the sorted rows that belong to a group: the cotangent of a row past the
    last group is whatever a grouped matmul's transpose left there (on a TPU
    the kernel does not write such rows) and is dropped."""
    return x[perm // k]


def _rows_fwd(x, perm, inv, live, k):
    return x[perm // k], (inv, live)


def _rows_bwd(k, res, g):
    inv, live = res
    g = jnp.where(live[:, None], g, 0)
    return (g[inv].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None, None)


_rows_of_pairs.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _permute(a, perm, inv):
    """``a[perm]`` for a permutation whose inverse is ``inv``: the cotangent
    is ``g[inv]``, a gather where autodiff would scatter."""
    return a[perm]


_permute.defvjp(lambda a, perm, inv: (a[perm], (perm, inv)),
                lambda res, g: (g[res[1]], None, None))


def grouped_experts_arrays(x, idx, w, mats, lo, valid=None,
                           activation="silu"):
    """``experts_arrays``'s sum computed over the pairs that landed here.

    The ``n * k`` (token, choice) pairs are sorted by held expert (a stable
    sort; pairs of absent experts and of padding rows go last, to no
    group), each pair's token row is gathered, every matrix of the expert
    is ONE grouped matmul over the group sizes, the routing weight scales
    the expert's hidden row (as the masked form does), and the rows are
    un-sorted and summed over a token's ``k`` choices. Dropless whatever the
    imbalance: the buffer holds every pair a token could land here, and the
    grouped matmul works only on the rows inside a group, so an expert that
    receives every pair or none costs what its pairs cost. Differentiable in
    ``x``, ``w`` and the matrices. ``x`` (n, in), ``idx`` / ``w`` (n, k),
    matrices as ``experts_arrays``."""
    f32 = jnp.float32
    n, k = idx.shape
    *first, last = mats
    held = last.shape[0]

    def grouped(rows, mat):
        with jax.named_scope("moe.grouped_matmul"):
            return jax.lax.ragged_dot(rows, mat, sizes,
                                      preferred_element_type=f32)

    with jax.named_scope("moe.group"):
        local = idx - lo
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine = mine & valid[:, None]
        group = jnp.where(mine, local, held).reshape(-1)
        perm = jnp.argsort(group, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(perm).at[perm].set(
            jnp.arange(n * k, dtype=jnp.int32))
        sizes = jnp.zeros((held,), jnp.int32).at[group].add(1, mode="drop")
        live = jnp.arange(n * k, dtype=jnp.int32) < jnp.sum(sizes)
        xs = _rows_of_pairs(x, perm, inv, live, k)
        ws = _permute(jnp.where(mine, w, 0.0).reshape(-1), perm, inv)
    h = grouped(xs, first[0])
    if len(first) == 1:
        h = jnp.square(jax.nn.relu(h))
    else:
        h = GATE_ACTIVATIONS[activation](h) * grouped(xs, first[1])
    y = grouped((h * ws[:, None]).astype(x.dtype), last)
    with jax.named_scope("moe.group"):
        # rows past the last group belong to no expert: whatever the kernel
        # left there is masked before the sum over a token's choices
        y = _permute(y, inv, perm).reshape(n, k, -1)
        return jnp.sum(jnp.where(mine[:, :, None], y, 0.0),
                       axis=1).astype(x.dtype)


def sigmoid_topk_route(u, gate, bias, k, scale=1.0, normalize=True,
                       norm_eps=1e-20, name=None):
    """Sigmoid-score top-k router in float32: choose the ``k`` experts with
    the largest ``sigmoid(u @ gate) + bias``; a chosen expert's weight is
    its score WITHOUT the bias, renormalised over the chosen (``normalize``:
    divided by their sum plus ``norm_eps``) and times ``scale``. ``u`` (n,
    hidden). Returns ``(idx (n, k) int32, weights (n, k) float32)``; the
    weights carry gradients to ``u`` and ``gate``."""
    def f(ua, ga, ba, **_attrs):
        return route_arrays(ua, ga, ba, k, scale, normalize, norm_eps)

    return dispatch.call(
        "sigmoid_topk_route", f, [_t(u), _t(gate), _t(bias)],
        attrs={"k": int(k), "scale": float(scale),
               "normalize": bool(normalize), "norm_eps": float(norm_eps)})


def softmax_topk_route(u, gate, k, name=None):
    """Top-k router with a softmax over the CHOSEN logits, in float32:
    choose the ``k`` experts with the largest ``u @ gate``; their weights
    are ``softmax`` of those ``k`` logits (they sum to 1, so a
    renormalisation over the chosen changes nothing). ``u`` (n, hidden).
    Returns ``(idx (n, k) int32, weights (n, k) float32)``; the weights
    carry gradients to ``u`` and ``gate``."""
    def f(ua, ga, **_attrs):
        return softmax_route_arrays(ua, ga, k)

    return dispatch.call("softmax_topk_route", f, [_t(u), _t(gate)],
                         attrs={"k": int(k)})


def _held_experts(op, x, idx, weights, mats, lo, valid, activation="silu"):
    """The held experts' part of a routed sum, dropless, for an expert
    made of ``mats`` (``experts_arrays`` has the forms), in the form the
    call's rows ask for (``takes_grouped_form``). Under O1 autocast the
    token rows and the expert matrices are the matmuls' operands and are
    cast as a white-listed op's are; the routing weights stay float32."""
    mats = [_t(m) for m in mats]
    held = mats[0].shape[0]
    inputs = [_t(x), _t(idx), _t(weights), *mats]
    if valid is not None:
        inputs.append(_t(valid))
    level, amp_dtype = dispatch.amp_state()
    grouped = takes_grouped_form(inputs[0].shape[0])

    def f(xa, ia, wa, *rest, **_attrs):
        ms, va = rest[:len(mats)], rest[len(mats):]
        va = va[0] if va else None
        if level == "O1":
            xa, ms = xa.astype(amp_dtype), [m.astype(amp_dtype) for m in ms]
        if grouped:
            return grouped_experts_arrays(xa, ia, wa, ms, lo, va, activation)
        return experts_arrays(xa, combine_arrays(ia, wa, lo, held, va), ms,
                              activation)

    attrs = {"lo": int(lo)}
    if activation != "silu":
        attrs["activation"] = activation
    return dispatch.call(
        op, f, inputs, attrs=attrs,
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
                        valid=None, name=None, activation="silu"):
    """As ``held_experts_relu2`` for gated experts: ``sum_j weights[j] *
    D_e (act(G_e x) * U_e x)``, ``activation`` ``silu`` (SwiGLU, the
    default) or ``relu`` (ReGLU). ``w_gate`` / ``w_up`` (E_held, hidden,
    width), ``w_down`` (E_held, width, hidden). Returns (n, hidden)."""
    if activation not in GATE_ACTIVATIONS:
        raise ValueError(f"activation {activation!r} is none of "
                         f"{sorted(GATE_ACTIVATIONS)}")
    return _held_experts("held_experts_swiglu", x, idx, weights,
                         (w_gate, w_up, w_down), lo, valid, activation)
