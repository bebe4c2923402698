"""Paged decode attention as a Pallas TPU kernel: one new token a lane, K/V
read from the pages the lane holds.

The serving decode step (``nn.functional.block_multihead_attention`` with
T = 1) attends each lane's single query over that lane's cached tokens. The
composite gathers every entry of every lane's block table; this kernel walks
the table and copies in ``ceil(len / block_size)`` pages a lane and no more,
so the step's K/V traffic follows the lengths the tick actually has. One
compiled program for every mix of lengths: the table and the lengths are
scalar-prefetch operands, the work list is walked at run time.

* **Pools as the engine holds them**, ``(num_blocks, block_size, KVH, D)``,
  seen as ``(num_blocks, block_size * KVH, D)``: a page is one contiguous
  copy and its rows are (token, kv head) pairs. No per-step transpose or
  copy of a pool.
* **All query heads through the MXU at once.** The (H, D) query block meets a
  chunk of ``rows`` page rows in one product, (H, rows) scores; a score
  counts where the row's kv head is the query head's (``row % KVH ==
  head // group``) and its token lies inside the lane's length. The MXU has
  to take in every K/V row once whichever way the heads are grouped, so the
  masked product costs it nothing extra, and the pages need no re-ordering.
* **Online softmax in float32** over the chunks of a lane. QK^T from the
  pages' own dtype with float32 accumulation (a product of two bfloat16
  numbers is exact in float32). For the PV product the float32
  probabilities go in as two bfloat16 halves (``p = hi + lo`` to 16 bits of
  mantissa) stacked into one product, again with float32 accumulation;
  float32 pages use full-precision products.
* **Pages that are K and V at once** (``value_cache=None``): a latent
  (MLA) cache keeps ONE row a token, ``[c | k_r]``, that the absorbed form
  reads as its key (the whole row) and as its value (the row's first
  ``value_dim`` columns), one K/V head under every query head. The pool is
  then ``(num_blocks, block_size, D)``, a page is copied in once and the PV
  product reads the first ``value_dim`` lanes of the same buffer, so a
  latent row leaves HBM once a step. ``D`` and ``value_dim`` are whole lane
  tiles: the engine pads a 576-wide row to 640 with zeros (a bfloat16 array
  whose minor dimension is 576 is laid out in 640 lanes anyway), and the
  queries carry zeros there.
* **One software pipeline over (lane, chunk) work items**: while a chunk is
  being multiplied the next one's pages (the same lane's, or the next live
  lane's first) are in flight, two buffers deep. A lane with ``seq_len <= 0``
  (the sentinel lanes of mid-prefill and stalled slots) is skipped: no page
  read, zeros out.

``INTERPRET = True`` runs the same kernel through the Pallas interpreter so
CPU tests cover the kernel's own code. The kernel's name on a device trace
is ``paged_decode_attn``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import import_pallas

pl, pltpu = import_pallas()

NEG_INF = -1e30

#: run the kernel in the Pallas interpreter (CPU testing of kernel code)
INTERPRET = False

#: page rows (token x kv head) a pipeline stage multiplies at once: wide
#: enough that a stage's copies and products hide its loop overhead, small
#: enough that the double-buffered K and V chunks stay ~1 MiB of VMEM
CHUNK_ROWS = 2048


def _sublanes(dtype) -> int:
    """Rows of a vector register's tile of ``dtype``."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def supports(q_shape, q_dtype, cache_shape, cache_dtype,
             value_dim=None) -> bool:
    """Whether the kernel was written for these shapes: one query a lane,
    float pages of the queries' dtype, lane-dense heads (D a multiple of
    128) and pages that fill whole sublane tiles. The query heads may be
    any multiple of the K/V heads, a group of one included (``H == KVH``):
    a count that is no whole sublane tile (30) is padded to one in the
    query block (32), and the pad rows see no K/V row and are never
    written out. The pages are a K pool beside a V pool of the same shape,
    ``(num_blocks, block_size, KVH, D)``, or with ``value_dim`` ONE pool
    ``(num_blocks, block_size, D)`` whose rows are a token's key and, in
    their first ``value_dim`` columns (whole lane tiles, at most D), its
    value. K/V heads fill whole sublane tiles or divide one."""
    _b, t, h, d = q_shape
    if value_dim is None:
        _nb, bs, kvh, dc = cache_shape
    else:
        (_nb, bs, dc), kvh = cache_shape, 1
        if value_dim % 128 or not 0 < value_dim <= dc:
            return False
    dt = jnp.dtype(cache_dtype)
    if t != 1 or dt != jnp.dtype(q_dtype) or dc != d or h % kvh:
        return False
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    # a pool is read as (num_blocks, block_size * KVH, D): that view is the
    # same bytes only where KVH fills whole sublane tiles or divides one (30
    # heads of bfloat16 are laid out in 32, and the view would be a copy of
    # the pool every step: the caller pads such a pool's heads itself)
    tile = _sublanes(dt)
    return (d % 128 == 0 and (bs * kvh) % tile == 0
            and (kvh % tile == 0 or tile % kvh == 0))


def _kernel(tables_ref, lens_ref, q_ref, *refs,
            block_size, kv_heads, group, pages_per_chunk, scale, value_dim):
    # ``value_dim`` None: a K pool and a V pool, a buffer each; else one
    # pool and one buffer, read as keys whole and as values in its first
    # ``value_dim`` lanes
    if value_dim is None:
        (k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_scr, l_scr,
         acc_scr) = refs
    else:
        k_hbm, o_ref, kbuf, sems, m_scr, l_scr, acc_scr = refs
        v_hbm = vbuf = None
    # ``heads`` counts the query block's rows, pad rows included: a pad
    # row's ``head // group`` is no K/V head, so it sees nothing
    lanes, heads, _d = q_ref.shape
    page_rows = block_size * kv_heads
    chunk_rows = pages_per_chunk * page_rows
    chunk_tokens = pages_per_chunk * block_size
    exact = q_ref.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact else None

    def length(lane):
        return lens_ref[jnp.minimum(lane, lanes - 1)]

    def next_live(lane):
        """The first lane at or after ``lane`` with tokens to attend, or
        ``lanes``."""
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(i < lanes, length(i) <= 0),
            lambda i: i + 1, lane)

    def pages_of(lane, chunk):
        held = pl.cdiv(length(lane), block_size)
        return jnp.minimum(pages_per_chunk, held - chunk * pages_per_chunk)

    def copies(lane, chunk, slot, page):
        blk = tables_ref[lane, chunk * pages_per_chunk + page]
        rows = pl.ds(page * page_rows, page_rows)
        k_copy = pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot, rows],
                                       sems.at[0, slot])
        if v_hbm is None:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot, rows],
                                      sems.at[1, slot]))

    def each_copy(lane, chunk, slot, act):
        """``act`` on every copy (K and V, or the one of a shared page) of
        every page the item holds."""
        def one(page, carry):
            for c in copies(lane, chunk, slot, page):
                act(c)
            return carry
        jax.lax.fori_loop(0, pages_of(lane, chunk), one, 0)

    def start_fetch(lane, chunk, slot):
        each_copy(lane, chunk, slot, lambda c: c.start())

    def wait_fetch(lane, chunk, slot):
        each_copy(lane, chunk, slot, lambda c: c.wait())

    def advance(lane, chunk):
        """The work item after (lane, chunk): the lane's next chunk, or the
        next live lane's first; ``lanes`` once there is none."""
        last = (chunk + 1) * chunk_tokens >= length(lane)
        nxt = jnp.where(last, next_live(lane + 1), lane)
        return jnp.minimum(nxt, lanes), jnp.where(last, 0, chunk + 1)

    def start_if_any(lane, chunk, slot):
        @pl.when(lane < lanes)
        def _():
            start_fetch(lane, chunk, slot)

    # lanes with nothing to attend give zeros; rows of a chunk that no copy
    # fills must hold numbers (a masked probability of 0 times a NaN is one)
    o_ref[...] = jnp.zeros_like(o_ref)
    if vbuf is None:
        kbuf[...] = jnp.zeros_like(kbuf)
    else:
        vbuf[...] = jnp.zeros_like(vbuf)

    row = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk_rows), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk_rows), 0)
    own_head = row % kv_heads == head // group
    token = row // kv_heads

    first = next_live(0)
    start_if_any(first, 0, 0)

    def step(carry):
        # this item's pages are in flight; start the next one's, then wait
        lane, chunk, slot = carry
        nxt_lane, nxt_chunk = advance(lane, chunk)
        start_if_any(nxt_lane, nxt_chunk, 1 - slot)
        n_tokens = length(lane)

        @pl.when(chunk == 0)
        def _():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        wait_fetch(lane, chunk, slot)
        s = jax.lax.dot_general(
            q_ref[lane], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision) * scale
        live = jnp.logical_and(
            own_head, token + chunk * chunk_tokens < n_tokens)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)      # a masked score gives exactly 0
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        values = vbuf[slot] if vbuf is not None \
            else kbuf[slot, :, :value_dim]
        if exact:
            pv = jnp.dot(p, values, preferred_element_type=jnp.float32,
                         precision=precision)
        else:
            hi = p.astype(jnp.bfloat16)
            lo = (p - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            both = jnp.dot(jnp.concatenate([hi, lo], axis=0), values,
                           preferred_element_type=jnp.float32)
            pv = both[:heads] + both[heads:]
        acc_scr[...] = alpha * acc_scr[...] + pv
        m_scr[...] = m_new

        @pl.when((chunk + 1) * chunk_tokens >= n_tokens)
        def _():
            o_ref[lane] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

        return nxt_lane, nxt_chunk, 1 - slot

    jax.lax.while_loop(lambda c: c[0] < lanes, step,
                       (first, jnp.int32(0), jnp.int32(0)))


def paged_decode_attention(q, key_cache, value_cache, block_tables, seq_lens,
                           scale=None, value_dim=None):
    """Attention of one query a lane over the lane's paged K/V.

    Args:
      q: (B, 1, H, D).
      key_cache / value_cache: (num_blocks, block_size, KVH, D), the new
        token's K/V already written; only read. With ``value_cache=None``
        and ``value_dim``: ``key_cache`` (num_blocks, block_size, D) is the
        one pool of a latent cache, a row the key of its token and, in its
        first ``value_dim`` columns, the value, under every query head.
      block_tables: (B, max_blocks) int32; entries past a lane's length are
        never looked at.
      seq_lens: (B,) int32, the new token included; a lane with
        ``seq_len <= 0`` reads no page and gives zeros.

    Returns (B, 1, H, D), or (B, 1, H, value_dim) over shared pages, in
    ``q``'s dtype. ``supports`` says which shapes.
    """
    b, _t, h, d = q.shape
    hp = -(-h // _sublanes(q.dtype)) * _sublanes(q.dtype)
    shared = value_cache is None
    nb, bs = key_cache.shape[:2]
    kvh = 1 if shared else key_cache.shape[2]
    dv = value_dim if shared else d
    page_rows = bs * kvh
    pages_per_chunk = max(1, min(CHUNK_ROWS // page_rows,
                                 block_tables.shape[1]))
    chunk_rows = pages_per_chunk * page_rows
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _kernel, block_size=bs, kv_heads=kvh, group=h // kvh,
        pages_per_chunk=pages_per_chunk, scale=sc,
        value_dim=value_dim if shared else None)
    pools = [key_cache.reshape(nb, page_rows, d)]
    if not shared:
        pools.append(value_cache.reshape(nb, page_rows, d))
    rows = q.reshape(b, h, d)
    if hp != h:
        rows = jnp.pad(rows, ((0, 0), (0, hp - h), (0, 0)))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hp, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec((b, hp, d), lambda i, *_: (0, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((b, hp, dv), lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk_rows, d), pool.dtype) for pool in pools
            ] + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, dv), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=INTERPRET,
        name="paged_decode_attn",
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      rows, *pools)
    return (out[:, :h] if hp != h else out).reshape(b, 1, h, dv)
