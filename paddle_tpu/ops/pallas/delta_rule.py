"""The gated delta rule's decode step as a Pallas TPU kernel: every lane's
state read ONCE and written ONCE.

One token a lane (``nn.functional.gated_delta_step``)::

    u  = beta (v - alpha S^T k)        S' = alpha S + k u^T
    o  = S'^T q = alpha S^T q + (k . q) u

A lane-layer's state is 30 x 96 x 192 float32 (2.2 MB); 128 lanes x 6 layers
are 1.7 GB, so a step that passes over it three times (``S^T k``, the
update, ``S'^T q``, as XLA runs the plain form) moves 5 GB where 3.4 are
needed. Here a tile of the state comes into VMEM, both reductions, the
rank-one update and the read-out happen on it, and it goes back: the kernel
is bound by those bytes.

* **The packed state**, ``(B, H / p, d_k, p * d_v)``: ``p`` heads side by
  side in the minor dimension so that it is whole lane tiles (``p`` = 2 at
  ``d_v`` = 192; ``nn.functional.delta_rule`` has why). ``d_k`` lies on the
  sublanes, so ``S^T k`` and ``S^T q`` are sums down the sublanes of ``S *
  k`` with ``k`` broadcast along the lanes, and what comes out, ``u`` and
  ``o``, are rows in the layout ``v`` arrives in.
* **``k`` and ``q`` as columns.** The broadcast along lanes needs them
  sublane-oriented. The wrapper transposes them once (a few MB, XLA) into
  ``(B, blocks, d_k, 128)``: a block's columns are its heads' ``k``, then
  their ``q``; the kernel slices a column and broadcasts it.
* **What is per head** (``alpha``, ``beta``, ``k . q``: three numbers a head
  a lane) arrives as scalar-prefetch operands in SMEM and is spread over its
  head's lanes of the row by a select; ``v`` comes in as it lies.
* **Fresh and idle lanes in the same visit**: a lane whose sequence starts
  here (``fresh``, a scalar-prefetch flag) has its tile zeroed as it is
  loaded; an idle lane comes in as ``alpha = 1, beta = 0`` and gets its
  state back unchanged. The engine's generic ``where`` over the whole state
  before and after would each be another pass.
* The state is aliased in place (``input_output_aliases``): a donated cache
  is updated where it lies.

**A decay a key channel** (Kimi delta attention; ``alpha`` (B, H, d_k)):
``u = beta (v - S^T (alpha * k))``, ``S' = Diag(alpha) S + k u^T``, ``o =
S^T (alpha * q) + (k . q) u``. The decay is a third column beside ``k`` and
``q``: broadcast along the lanes it scales each ROW of the tile by its own
factor, once, and both reductions read the scaled tile; ``p`` is 1 (the
family's ``d_v`` is 128). Same visit, same bytes, one product a cell more.

``INTERPRET = True`` runs the same kernel through the Pallas interpreter so
CPU tests cover the kernel's own code. The kernel's name on a device trace
is ``delta_rule_step``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import import_pallas

pl, pltpu = import_pallas()

#: run the kernel in the Pallas interpreter (CPU testing of kernel code)
INTERPRET = False

#: bytes of state a grid step holds: in and out, each double-buffered, stay
#: under 6 MiB of VMEM, and a step's ~1.4 MB each way hides its overhead
BLOCK_BYTES = 3 << 19

_LANES = 128


def rows_per_block(groups: int, key_dim: int, width: int, packed: int,
                   columns: int = 2) -> int:
    """Packed rows (``packed`` heads each) a grid step takes: the most that
    divide ``groups``, fit ``BLOCK_BYTES`` and whose heads' ``columns``
    columns each (``k`` and ``q``; with a decay a channel that too) fit one
    128-lane tile."""
    best = 0
    for n in range(1, groups + 1):
        if (groups % n == 0 and n * key_dim * width * 4 <= BLOCK_BYTES
                and columns * n * packed <= _LANES):
            best = n
    return best


def supports(state_shape, key_dim: int, packed: int,
             channel: bool = False) -> bool:
    """Whether the kernel was written for this packed state ``(B, H / p,
    d_k, p * d_v)``: whole lane tiles in the minor dimension, whole float32
    sublane tiles of ``d_k``, and a block of rows that fits. ``channel``:
    with the decay a key channel, which takes ``p`` = 1."""
    if len(state_shape) != 4 or not packed or (channel and packed != 1):
        return False
    _b, groups, dk, width = state_shape
    return (dk == key_dim and width % _LANES == 0 and width % packed == 0
            and dk % 8 == 0
            and rows_per_block(groups, dk, width, packed,
                               3 if channel else 2) > 0)


def _kernel(fresh_ref, alpha_ref, beta_ref, qk_ref, s_ref, cols_ref, v_ref,
            s_out, o_ref, *, rows, packed, value_dim):
    lane, block = pl.program_id(0), pl.program_id(1)
    fresh = fresh_ref[lane] > 0
    dk, width = s_ref.shape[2], s_ref.shape[3]
    head_of_lane = jax.lax.broadcasted_iota(
        jnp.int32, (dk, width), 1) // value_dim
    head_of_row = head_of_lane[:1]
    cols = cols_ref[0, 0]                                  # (d_k, 128)

    def column(first):
        """Columns ``first .. first + packed`` of ``cols``, each broadcast
        over its head's lanes of the row."""
        out = jnp.broadcast_to(cols[:, first:first + 1], (dk, width))
        for j in range(1, packed):
            out = jnp.where(head_of_lane == j,
                            cols[:, first + j:first + j + 1], out)
        return out

    def per_head(ref, head):
        """``ref``'s numbers for heads ``head .. head + packed`` of this
        lane as one (1, width) row."""
        out = jnp.full((1, width), ref[lane, head], jnp.float32)
        for j in range(1, packed):
            out = jnp.where(head_of_row == j, ref[lane, head + j], out)
        return out

    for r in range(rows):
        head = (block * rows + r) * packed
        s = s_ref[0, r]                                    # (d_k, p * d_v)
        s = jnp.where(fresh, jnp.zeros_like(s), s)
        kb = column(r * packed)
        qb = column((rows + r) * packed)
        s_k = jnp.sum(s * kb, axis=0, keepdims=True)       # S^T k
        s_q = jnp.sum(s * qb, axis=0, keepdims=True)       # S^T q
        alpha = per_head(alpha_ref, head)
        u = per_head(beta_ref, head) * (v_ref[0, 0, r:r + 1] - alpha * s_k)
        s_out[0, r] = alpha * s + kb * u
        o_ref[0, 0, r:r + 1] = alpha * s_q + per_head(qk_ref, head) * u


def _channel_kernel(fresh_ref, beta_ref, qk_ref, s_ref, cols_ref, v_ref,
                    s_out, o_ref, *, rows):
    lane, block = pl.program_id(0), pl.program_id(1)
    fresh = fresh_ref[lane] > 0
    dk, width = s_ref.shape[2], s_ref.shape[3]
    cols = cols_ref[0, 0]                                  # (d_k, 128)

    def column(at):
        return jnp.broadcast_to(cols[:, at:at + 1], (dk, width))

    for r in range(rows):
        head = block * rows + r
        s = s_ref[0, r]                                    # (d_k, d_v)
        s = jnp.where(fresh, jnp.zeros_like(s), s)
        s = column(2 * rows + r) * s                       # Diag(alpha) S
        kb = column(r)
        s_k = jnp.sum(s * kb, axis=0, keepdims=True)       # S^T (alpha * k)
        s_q = jnp.sum(s * column(rows + r), axis=0, keepdims=True)
        u = beta_ref[lane, head] * (v_ref[0, 0, r:r + 1] - s_k)
        s_out[0, r] = s + kb * u
        o_ref[0, 0, r:r + 1] = s_q + qk_ref[lane, head] * u


def delta_rule_step(q, k, v, alpha, beta, state, fresh=None, idle=None,
                    packed=1):
    """``nn.functional.delta_rule.step_arrays`` on a packed state, one visit
    a tile. ``q`` / ``k`` (B, H, d_k), ``v`` (B, H, d_v), ``alpha`` /
    ``beta`` (B, H), ``state`` (B, H / packed, d_k, packed * d_v) float32,
    ``fresh`` / ``idle`` (B,) bool or None. With ``alpha`` (B, H, d_k), a
    decay a key channel, it is ``channel_step_arrays`` (``packed`` 1).
    Returns ``(o (B, H, d_v) float32, new state)``; ``supports`` says which
    shapes."""
    f32 = jnp.float32
    bsz, h, dk = q.shape
    dv = v.shape[-1]
    channel = alpha.ndim == 3
    grp, width = h // packed, packed * dv
    rows = rows_per_block(grp, dk, width, packed, 3 if channel else 2)
    blocks = grp // rows
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha, beta = alpha.astype(f32), beta.astype(f32)
    if idle is not None:
        alpha = jnp.where(idle[(slice(None),) + (None,) * (alpha.ndim - 1)],
                          1.0, alpha)
        beta = jnp.where(idle[:, None], 0.0, beta)
    fresh = (jnp.zeros((bsz,), jnp.int32) if fresh is None
             else fresh.astype(jnp.int32))
    if idle is not None:        # an idle lane's state is not even zeroed
        fresh = jnp.where(idle, 0, fresh)

    def columns(a):     # (B, H, d_k) -> (B, blocks, d_k, rows * packed)
        return a.reshape(bsz, blocks, rows * packed, dk).transpose(0, 1, 3, 2)

    cols = jnp.concatenate([columns(k), columns(q)]
                           + ([columns(alpha)] if channel else []), axis=-1)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, _LANES - cols.shape[-1]),))

    # what is a number a head a lane, in SMEM before the tiles
    per_head = (beta, jnp.sum(q * k, axis=-1))
    if channel:
        kernel = functools.partial(_channel_kernel, rows=rows)
    else:
        per_head = (alpha,) + per_head
        kernel = functools.partial(_kernel, rows=rows, packed=packed,
                                   value_dim=dv)
    scalars = (fresh,) + per_head
    new, o = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((bsz, blocks, rows, width), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(bsz, blocks),
            in_specs=[
                pl.BlockSpec((1, rows, dk, width),
                             lambda b, n, *_: (b, n, 0, 0)),
                pl.BlockSpec((1, 1, dk, _LANES),
                             lambda b, n, *_: (b, n, 0, 0)),
                pl.BlockSpec((1, 1, rows, width),
                             lambda b, n, *_: (b, n, 0, 0))],
            out_specs=[
                pl.BlockSpec((1, rows, dk, width),
                             lambda b, n, *_: (b, n, 0, 0)),
                pl.BlockSpec((1, 1, rows, width),
                             lambda b, n, *_: (b, n, 0, 0))]),
        # the state (the first operand after the prefetched scalars) in place
        input_output_aliases={len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=INTERPRET,
        name="delta_rule_step",
    )(*scalars, state.astype(f32), cols, v.reshape(bsz, blocks, rows, width))
    return o.reshape(bsz, h, dv), new
