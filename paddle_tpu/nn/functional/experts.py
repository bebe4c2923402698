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
training batch). Dropless with static shapes means a buffer of all ``rows x
k`` pairs; since PR 48 only integers are that long (the sort, its inverse,
the group sizes). Every operation on ROWS (the gather of token rows, the
grouped matmuls, the gate, their transposes) walks the live prefix of the
sorted order a stride at a time, in one loop forward and one backward whose
trip count is ``ceil(landed pairs / pair_stride)``; what is still paid over
``rows x k`` whatever landed is one gather of the result buffer's rows and
one masked sum over a token's choices a direction (PERF.md sections 5 and 6,
PR 48). Which form a call takes follows from its shapes where it is traced
(``takes_grouped_form``), and so does the stride (``pair_stride``): no flag,
no environment variable, no config key.
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


#: rows of the grouped kernel's tile: a stride is whole tiles
_TILE_ROWS = 512

#: the stride of the grouped form's walk, in units of the pairs that EVEN
#: routing lands on the held experts: a balanced step walks one stride, a
#: step whose router favours the held experts walks more. Timed on a v5e
#: (PR 48; one routed layer alone, forward + backward, bfloat16, ms; the
#: form before the walk / half this stride / this stride / twice it).
#: LFM2's layer, 16 384 x 4 pairs, 8 of 32 experts of 2048 x 1792 held,
#: stride 32 768: even routing (16 296 landed) 39.7 / 32.1 / 35.2 / 40.6;
#: 1.7x even, where its router drifts to (27 838) 48.2 / 48.9 / 45.2 /
#: 50.8; 2 % landed 28.7 / 18.3 / 22.4 / 27.3; every pair landed (65 536)
#: 74.9 / 94.6 / 86.9 / 83.3. SmallThinker's, 16 384 x 6 pairs, 8 of 64
#: experts of 2560 x 768 held, stride 24 576: even (12 432) 47.7 / 27.3 /
#: 26.3 / 30.4; 1.7x even (21 107) 51.1 / 30.7 / 30.1 / 34.6; 2 %, where
#: its router sheds the held experts to (2005) 43.4 / 18.7 / 21.3 / 25.2;
#: every pair (98 304) 81.2 / 85.9 / 82.8 / 80.5. Half the stride wins
#: where little lands and loses where a balanced step then walks two; a
#: trip costs its three weight-gradient kernels whatever lands (each
#: writes a whole (held, in, out) float32 result). The worst case, every
#: pair landing here, costs 2-16 % more than the form before the walk.
EVEN_LOADS_A_STRIDE = 2


def pair_stride(rows: int, k: int, held: int, num_experts=None) -> int:
    """Rows of the sorted pair buffer the grouped form walks at a time:
    ``EVEN_LOADS_A_STRIDE`` times the pairs that even routing lands on
    ``held`` of ``num_experts`` experts (``2 x rows x k x held /
    num_experts``), rounded up to the grouped kernel's 512-row tile and no
    longer than the buffer. Nothing is dropped: a step walks as many
    strides as its landed pairs fill. Decided from what the call sees where
    it is traced; a caller that does not say how wide its router is walks
    the whole buffer as one stride."""
    pairs = rows * k
    even = pairs if num_experts is None else -(-pairs * held // num_experts)
    tiles = lambda r: -(-r // _TILE_ROWS) * _TILE_ROWS
    return min(tiles(EVEN_LOADS_A_STRIDE * even), tiles(pairs))


def strides_walked(landed, stride: int):
    """Strides a call walks for ``landed`` pairs on its held experts."""
    return -(-landed // stride)


def pair_walk(rows: int, k: int, held: int, num_experts=None):
    """``(stride, pairs)`` of a call over ``rows`` tokens: what it walks at
    a time and how long its sorted buffer is; None where a call of that
    many rows takes the masked form and sorts nothing."""
    if not takes_grouped_form(rows):
        return None
    return pair_stride(rows, k, held, num_experts), rows * k


def _unwritten(shape, dtype):
    """A buffer the strides write into: on a TPU whatever the memory held
    (no pass over it to zero it), on a CPU zeros. Rows no stride wrote are
    masked where the buffer is read."""
    return jax.lax.empty(shape, dtype)


def _grouped(rows, mat, sizes):
    """``rows[r] @ mat[group of r]``, float32: (rows, in) x (E, in, out)."""
    with jax.named_scope("moe.grouped_matmul"):
        return jax.lax.ragged_dot(rows, mat, sizes,
                                  preferred_element_type=jnp.float32)


def _grouped_t(ct, mat, sizes):
    """The transpose of ``_grouped`` in its rows: ``ct[r] @ mat[group]^T``,
    as autodiff writes it (the cotangent float32, the matrix as it is)."""
    return _grouped(ct, jnp.swapaxes(mat, 1, 2), sizes)


_WEIGHT_GRADIENT = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_w(rows, ct, sizes):
    """The transpose of ``_grouped`` in its matrices: per group ``rows^T
    ct``, (E, in, out) float32, over the rows inside a group alone."""
    with jax.named_scope("moe.grouped_matmul"):
        return jax.lax.ragged_dot_general(
            rows, ct, sizes, _WEIGHT_GRADIENT,
            preferred_element_type=jnp.float32)


def _gate(hs, ws, activation, dtype):
    """The expert's hidden row from its first products, times the pair's
    routing weight (as the masked form has it), in the operands' dtype."""
    if len(hs) == 1:
        h = jnp.square(jax.nn.relu(hs[0]))
    else:
        h = GATE_ACTIVATIONS[activation](hs[0]) * hs[1]
    return (h * ws[:, None]).astype(dtype)


def _stride_of(i, stride, k, x, ws, perm, offsets):
    """Stride ``i`` of the sorted buffer: where it starts, its pairs' token
    ids, token rows and routing weights, and the part of every group that
    lies in it."""
    start = i * stride
    pairs = jax.lax.dynamic_slice(perm, (start,), (stride,))
    tokens = pairs // k
    cut = jnp.clip(offsets, start, start + stride)
    return start, tokens, x[tokens], ws[pairs], cut[1:] - cut[:-1]


def _put(buf, rows, start):
    """A stride's rows into the buffer, in place."""
    return jax.lax.dynamic_update_slice(
        buf, rows, (start,) + (0,) * (rows.ndim - 1))


def _by_choice(buf, order, landed):
    """The buffer's rows back in pair order as (choices, tokens, ...): one
    gather through ``order``, where each (choice, token) pair lies in the
    sorted order; choices first, so that no layout changes before the sum
    over them. A pair that did not land here reads 0 whatever its row holds
    (a row past the last group that the kernel did not write, a row of a
    stride never walked)."""
    rows = buf[order].reshape(landed.shape + buf.shape[1:])
    return jnp.where(landed.reshape(landed.shape + (1,) * (buf.ndim - 1)),
                     rows, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _walk(x, ws, mats, perm, order, offsets, landed, stride, activation):
    """The grouped form's arrays: ``x`` (n, in), ``ws`` (n * k,) the
    routing weights by pair (0 where the pair did not land), the sort's
    integers (``perm`` padded to whole strides, ``order`` and ``landed`` (k,
    n) as ``_by_choice`` reads them, ``offsets`` where each group starts).
    One loop forward, one backward, each of ``ceil(landed pairs / stride)``
    trips."""
    return _walk_fwd(x, ws, mats, perm, order, offsets, landed, stride,
                     activation)[0]


def _walk_fwd(x, ws, mats, perm, order, offsets, landed, stride, activation):
    *first, last = mats
    k = landed.shape[0]

    def body(i, y):
        with jax.named_scope("moe.group"):
            start, _tokens, xs, wi, part = _stride_of(
                i, stride, k, x, ws, perm, offsets)
        hs = [_grouped(xs, m, part) for m in first]
        with jax.named_scope("moe.group"):
            h = _gate(hs, wi, activation, x.dtype)
        ys = _grouped(h, last, part)
        with jax.named_scope("moe.group"):
            return _put(y, ys, start)

    with jax.named_scope("moe.group"):
        y = _unwritten((perm.shape[0], last.shape[-1]), jnp.float32)
    y = jax.lax.fori_loop(0, strides_walked(offsets[-1], stride), body, y)
    with jax.named_scope("moe.group"):
        out = jnp.sum(_by_choice(y, order, landed), axis=0).astype(x.dtype)
    return out, (x, ws, mats, perm, order, offsets, landed)


def _walk_bwd(stride, activation, res, g):
    """One loop of the forward's trip count. A stride's hidden rows are
    computed again (nothing is kept whose size follows the landed pairs),
    the matrices' gradients add up in float32 carries in place, and the
    stride's rows of the gradients in ``x`` and in the routing weights go
    into buffers that are summed over a token's choices once after the
    loop."""
    f32 = jnp.float32
    x, ws, mats, perm, order, offsets, landed = res
    *first, last = mats
    k = landed.shape[0]

    def body(i, carry):
        dxs, dws, dmats = carry
        with jax.named_scope("moe.group"):
            start, tokens, xs, wi, part = _stride_of(
                i, stride, k, x, ws, perm, offsets)
            live = start + jnp.arange(stride, dtype=jnp.int32) < offsets[-1]
            # a sorted row's cotangent is its token's; a row past the last
            # group belongs to no pair that landed
            dy = jnp.where(live[:, None], g[tokens].astype(f32), 0)
        hs = [_grouped(xs, m, part) for m in first]
        with jax.named_scope("moe.group"):
            h, gate_vjp = jax.vjp(
                lambda wi, *hs: _gate(hs, wi, activation, x.dtype), wi, *hs)
        d_last = _grouped_w(h, dy, part)
        dh = _grouped_t(dy, last, part).astype(h.dtype)
        with jax.named_scope("moe.group"):
            dwi, *dhs = gate_vjp(dh)
        dx = sum(_grouped_t(d, m, part).astype(x.dtype)
                 for d, m in zip(dhs, first))
        d_first = [_grouped_w(xs, d, part) for d in dhs]
        with jax.named_scope("moe.group"):
            return (_put(dxs, dx, start), _put(dws, dwi, start),
                    [acc + d for acc, d in zip(dmats, d_first + [d_last])])

    with jax.named_scope("moe.group"):
        carry = (_unwritten((perm.shape[0], x.shape[1]), x.dtype),
                 _unwritten(perm.shape, f32),
                 [jnp.zeros(m.shape, f32) for m in mats])
    dxs, dws, dmats = jax.lax.fori_loop(
        0, strides_walked(offsets[-1], stride), body, carry)
    with jax.named_scope("moe.group"):
        return (jnp.sum(_by_choice(dxs, order, landed), axis=0),
                _by_choice(dws, order, landed).T.reshape(-1),
                tuple(d.astype(m.dtype) for d, m in zip(dmats, mats)),
                None, None, None, None)


_walk.defvjp(_walk_fwd, _walk_bwd)


def grouped_experts_arrays(x, idx, w, mats, lo, valid=None,
                           activation="silu", num_experts=None):
    """``experts_arrays``'s sum computed over the pairs that landed here.

    The ``n * k`` (token, choice) pairs are sorted by held expert (a stable
    sort; pairs of absent experts and of padding rows go last, to no
    group). That much is integers over ``n x k``. Everything that moves
    ROWS walks the live prefix of the sorted order, ``pair_stride`` pairs at
    a time, in one loop whose trip count is ``ceil(landed / stride)``: the
    stride's token rows are gathered, every matrix of the expert is ONE
    grouped matmul over the part of each group that lies in the stride, the
    routing weight scales the expert's hidden row (as the masked form
    does), and the stride's float32 rows are written into the result
    buffer in place. After the loop the buffer is un-sorted and summed over
    a token's ``k`` choices, once. Dropless whatever the imbalance: every
    pair that landed is multiplied, and all ``n x k`` landing here walks
    the whole buffer. What the call pays over ``n x k`` whatever landed is
    the sort's integers, that one float32 gather and one masked sum a
    direction. Differentiable in ``x``, ``w`` and the matrices, by hand
    (``_walk_bwd``). ``x`` (n, in), ``idx`` / ``w`` (n, k), matrices as
    ``experts_arrays``; ``num_experts`` the router's width."""
    n, k = idx.shape
    held = mats[-1].shape[0]
    stride = pair_stride(n, k, held, num_experts)
    with jax.named_scope("moe.group"):
        local = idx - lo
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine = mine & valid[:, None]
        group = jnp.where(mine, local, held).reshape(-1)
        perm = jnp.argsort(group, stable=True).astype(jnp.int32)
        # the inverse permutation and the group sizes without a scatter: on
        # a v5e a scatter of 98 304 integers is 0.5-0.9 ms, a sort of them
        # 0.08 (PERF.md section 6, PR 48)
        order = jnp.argsort(perm).astype(jnp.int32).reshape(n, k).T
        sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(sizes, dtype=jnp.int32)])
        # whole strides: the last one's rows past ``n * k`` are no pair's
        perm = jnp.pad(perm, (0, -(n * k) % stride))
        ws = jnp.where(mine, w, 0.0).reshape(-1)
    return _walk(x, ws, tuple(mats), perm, order.reshape(-1), offsets,
                 mine.T, stride, activation)


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


def _held_experts(op, x, idx, weights, mats, lo, valid, activation="silu",
                  num_experts=None):
    """The held experts' part of a routed sum, dropless, for an expert
    made of ``mats`` (``experts_arrays`` has the forms), in the form the
    call's rows ask for (``takes_grouped_form``); ``num_experts``, the
    router's width, sizes the grouped form's stride. Under O1 autocast the
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
            return grouped_experts_arrays(xa, ia, wa, ms, lo, va, activation,
                                          num_experts)
        return experts_arrays(xa, combine_arrays(ia, wa, lo, held, va), ms,
                              activation)

    attrs = {"lo": int(lo)}
    if activation != "silu":
        attrs["activation"] = activation
    if num_experts is not None:
        attrs["num_experts"] = int(num_experts)
    return dispatch.call(
        op, f, inputs, attrs=attrs,
        differentiable_mask=[True, False, True] + [True] * len(mats)
        + [False] * (valid is not None))


def held_experts_relu2(x, idx, weights, w1, w2, lo=0, valid=None,
                       name=None, num_experts=None):
    """The held experts' part of a routed sum, dropless: for every token
    ``sum_j weights[j] * W2_e relu(W1_e x)^2`` over its chosen experts
    ``e = idx[j]`` with ``lo <= e < lo + E_held``. ``x`` (n, latent),
    ``idx`` / ``weights`` (n, k), ``w1`` (E_held, latent, width), ``w2``
    (E_held, width, latent); ``valid`` (n,) bool drops padding rows;
    ``num_experts`` is the router's width, from which a call that takes the
    grouped form sizes its stride (``pair_stride``). Returns (n, latent)."""
    return _held_experts("held_experts_relu2", x, idx, weights, (w1, w2),
                         lo, valid, num_experts=num_experts)


def held_experts_swiglu(x, idx, weights, w_gate, w_up, w_down, lo=0,
                        valid=None, name=None, activation="silu",
                        num_experts=None):
    """As ``held_experts_relu2`` for gated experts: ``sum_j weights[j] *
    D_e (act(G_e x) * U_e x)``, ``activation`` ``silu`` (SwiGLU, the
    default) or ``relu`` (ReGLU). ``w_gate`` / ``w_up`` (E_held, hidden,
    width), ``w_down`` (E_held, width, hidden). Returns (n, hidden)."""
    if activation not in GATE_ACTIVATIONS:
        raise ValueError(f"activation {activation!r} is none of "
                         f"{sorted(GATE_ACTIVATIONS)}")
    return _held_experts("held_experts_swiglu", x, idx, weights,
                         (w_gate, w_up, w_down), lo, valid, activation,
                         num_experts)
