"""Flash attention (forward + backward) as Pallas TPU kernels.

Replaces the reference's FlashAttention-2 CUDA library integration
(reference: third_party/flashattn; op `flash_attn` at
paddle/phi/ops/yaml/ops.yaml:1635). Design:

* layout — the kernels take their blocks from the (B, S, H*d) array as the
  model holds it (the fused projection's result, split and reshaped for
  free): grid (batch, head groups, q_tiles, kv_tiles), a block is
  (1, tile, 128) at lane-block index = the head group, and carries
  ``heads_per_block`` = 128 // d heads side by side (two at d 64); a head
  of d = n * 128 lanes is a block of its own; any other head (an odd head
  count, d 16 with 3 heads) is padded to a block of whole lane tiles. No
  transpose into (B*H, S, d), and for heads that pack no pad of d and no
  slice of the results ("Heads of a block" below).
* forward — online-softmax over KV tiles, the kv axis innermost so the fp32
  accumulators in VMEM scratch persist across kv steps; the MXU consumes
  (Bq, 128) x (128, Bk) tiles; causal tiles above the diagonal are skipped
  with @pl.when, and INSIDE a tile the diagonal crosses only the part at or
  below it runs (``_tile_blocks``: bands of ``SUB_BLOCK`` query rows, each
  against the keys it can see), so no FLOPs are spent on masked blocks
  whatever the tile. The band body runs once a head of the block, each head
  with its own max, sum and accumulator. Also emits the per-row logsumexp
  (the FA2 "L" residual) for backward.
* window — a causal call may be sliding-window attention: query i sees key
  j iff 0 <= i - j < window. A tile wholly under the band is skipped like
  one above the diagonal and, since its index map names the block of the
  nearest tile inside the band, not fetched either; a tile the band's lower
  edge crosses gets a second masked run of keys, at its low end; the tiles
  between the two edges run unmasked. All three kernels; a call without a
  window (or with one no shorter than the keys) is built as it was.
* backward — the FA2 recompute strategy, O(S·d) memory: residuals are only
  (q, k, v, out, lse); each backward tile recomputes p = exp(qk·scale−lse)
  on the fly. Two kernels: dQ iterates kv innermost accumulating
  dq += ds·K; dK/dV iterates q innermost accumulating dv += pᵀ·dO and
  dk += dsᵀ·Q, where ds = p·(dp − Δ)·scale, dp = dO·Vᵀ and
  Δ = rowsum(dO∘O), which each kernel makes for its band from the blocks of
  dO and O as they lie (no pass of XLA's over the two arrays, no Δ in
  HBM). dK/dV computes everything TRANSPOSED, sᵀ = K·Qᵀ (keys x rows), so
  that pᵀ and dsᵀ are what it holds and both accumulations are plain
  products (no contraction over the leading dimension of a scores-sized
  array). The full (S, S) probability matrix is never materialized in
  either pass.
* the logsumexp is lane-dense ROWS in HBM, (B, H // hpb, hpb, S_padded)
  float32 in blocks of (1, 1, hpb, block_q), the rows of a block's heads
  together, from the forward kernel's store to the backward kernels' loads:
  4 bytes a value (a (.., S, 1) column is a whole (8, 128) tile to every 8
  values, 128 times that). The forward turns a band's (rows, 1) column
  into the row in VMEM before the store; dK/dV's transposed scores take a
  (1, rows) block as it lies, broadcast down the keys; dQ, which keeps
  (rows x keys), turns its block back into a column in VMEM.

``block_q`` / ``block_k`` are exposed for tuning (reference
flash_attn's num_splits analog); ``INTERPRET=True`` runs the same kernels
through the Pallas interpreter so CPU tests cover the real kernel code.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp

from ...core.residuals import KEPT_RESIDUALS, keep as _keep
from . import import_pallas

pl, pltpu = import_pallas()

NEG_INF = -1e30

# Tiles of a NON-causal call, as they were before PR 36 (which timed causal
# calls only): such a tile runs whole, so its f32 scores have to fit VMEM
# beside the operands; 2048 x 2048 does not (Mosaic refuses it on a v5e).
# Override per call via flash_attention_fwd(..., block_q=..., block_k=...).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
DEFAULT_BWD_BLOCK_Q = 512
DEFAULT_BWD_BLOCK_K = 512

# Tile of a CAUSAL call, both sides, both passes: the one measured winner, so
# nothing is probed and no probe's noise picks a loser. On a v5e (PR 36;
# bf16, bands of 256) 2048 x 2048 is first or within 0.4 % of it at every
# length measured: S = 2048, d 64: 0.300 / 0.425 ms (forward / backward) a
# call of 32 heads against 0.430 / 0.513 at 1024 x 1024; S = 8192, d 128:
# 1.190 / 1.329 ms of 8 heads against 1.328 / 1.485; at S = 1024 every tile
# of 1024 or more IS the sequence; 512-wide tiles lose 40-120 %. A band's
# scores are 256 rows of the tile's keys, whatever the tile.
CAUSAL_BLOCK = 2048


def _causal_block_for(seq):
    """Causal tile for ONE side, from that side's length: 1024 where 2048
    would pad the side by 1024 rows or more (S = 3000 as 2 x 2 tiles of 2048
    runs 1.6 times the scores of 3 x 3 of 1024)."""
    if seq > CAUSAL_BLOCK and -seq % CAUSAL_BLOCK >= 1024:
        return 1024
    return CAUSAL_BLOCK


def _bwd_block_for(seq):
    """Non-causal backward tile for ONE side (q or k), from that side's
    length: 1024 where it divides a short sequence (otherwise padding
    wastes up to a third of the grid), else the 512 tiles whose four
    S x S f32 arrays of dK/dV fit VMEM."""
    if seq <= 2048 and seq % 1024 == 0:
        return 1024
    return DEFAULT_BWD_BLOCK_Q


#: run kernels in the Pallas interpreter (CPU testing of kernel code)
INTERPRET = False

#: scoped VMEM a causal call of tiles wider than 1024 asks for: beside a
#: band's scores (256 x 2048 float32, 2 MB an array, and a block of two
#: heads keeps two heads' at once: one padded tile of 2048 at d 64 is
#: 19.7 MB) a row of several tiles keeps running state (max, sum,
#: accumulator, each row a whole (8, 128) tile of f32) and double-buffered
#: operands, against the compiler's default limit of 16 MB, which refused
#: every causal call longer than one tile (S 4096, S 8192). A v5e core has
#: 128 MiB. Calls of tiles up to 1024 (S <= 1024, the GPT-2 cells') ask for
#: nothing.
MULTI_TILE_VMEM_BYTES = 32 * 1024 * 1024

#: rows of queries in a band of a causal tile (``_tile_blocks``)
SUB_BLOCK = 256
#: bands of a grid step up to which the loop over a block's heads is
#: unrolled (``_unroll_heads``): a tile is at most 8 bands, and a grid of
#: several causal tiles has a class on the diagonal and one below it, 16
UNROLL_HEADS_BANDS = 8
_LANES = 128

# Candidate tile grids of a non-causal call for the measured autotuner
# (ops/pallas/autotune.py). Small on purpose: each candidate costs one
# Pallas compile at first sight of a new (shape-class, chip) key; winners
# persist to disk.
FWD_TILE_CANDIDATES = [(1024, 1024), (512, 512), (512, 1024), (1024, 512),
                       (2048, 512)]
BWD_TILE_CANDIDATES = [(512, 512), (1024, 1024), (256, 512), (512, 1024),
                       (1024, 512)]


def _tuned_blocks(kind, heads, s_q, s_k, d, dtype, causal, scale):
    """(block_q, block_k) for this shape class on this chip: a causal call's
    constant, a non-causal call's measured winner.

    Falls back to the hand-tuned v5e constants when autotuning is off or
    the backend is not a real TPU (reference
    phi/kernels/autotune/switch_autotune.cc gate). Benchmarks run on
    noise at the BUCKETED sequence lengths (tile ranking is data- and
    batch-mostly-independent; one sequence of at most 8 heads, or the most
    below that lie as the caller's do (``_head_layout``), keeps the probe
    cheap).
    """
    from . import autotune as at

    if causal:
        return _causal_block_for(s_q), _causal_block_for(s_k)
    if INTERPRET or not at.should_autotune():
        if kind == "fwd":
            return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
        return _bwd_block_for(s_q), _bwd_block_for(s_k)

    hpb = _head_layout(heads, d)[0]
    sq_b, sk_b = at.seq_bucket(s_q), at.seq_bucket(s_k)
    key = at.make_key(f"flash_{kind}", sq=sq_b, sk=sk_b, d=d, hpb=hpb,
                      dt=str(jnp.dtype(dtype)), causal=bool(causal))
    cached = at.get_cache().get(key)
    if cached is not None:
        return tuple(cached)

    heads = _probe_heads(heads, d)
    # probe on noise, not zeros (constant-folding could skip real work)
    nvar = 3
    qs, ks, vs = [], [], []
    for i in range(nvar):
        kp = jax.random.key(i)
        qs.append(jax.random.normal(kp, (1, sq_b, heads, d)).astype(dtype))
        ks.append(jax.random.normal(
            jax.random.fold_in(kp, 1), (1, sk_b, heads, d)).astype(dtype))
        vs.append(jax.random.normal(
            jax.random.fold_in(kp, 2), (1, sk_b, heads, d)).astype(dtype))
    # amortize per-call dispatch under the kernel: chain K applications
    # data-dependently inside ONE program (the kernel's q-shaped output
    # feeds the next iteration), sized so device time dominates
    kernel_flops = 4.0 * heads * sq_b * sk_b * d * (0.5 if causal else 1.0)
    reps = at.probe_reps(kernel_flops)
    jitted = {}
    if kind == "fwd":
        candidates, default = FWD_TILE_CANDIDATES, (DEFAULT_BLOCK_Q,
                                                    DEFAULT_BLOCK_K)

        def run(c, i):
            fn = jitted.get(c)
            if fn is None:
                kern = functools.partial(
                    _flash_fwd_bshd, causal=causal, scale=scale,
                    block_q=c[0], block_k=c[1])

                def chained(q0, k0, v0):
                    return jax.lax.fori_loop(
                        0, reps, lambda _, q: kern(q, k0, v0)[0], q0)

                fn = jitted[c] = jax.jit(chained)
            j = i % nvar
            return fn(qs[j], ks[j], vs[j])
    else:
        candidates = BWD_TILE_CANDIDATES
        default = (_bwd_block_for(s_q), _bwd_block_for(s_k))
        fwd = jax.jit(functools.partial(
            _flash_fwd_bshd, causal=causal, scale=scale,
            block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K))
        outs, lses = zip(*(fwd(qs[j], ks[j], vs[j])
                           for j in range(nvar)))

        def run(c, i):
            fn = jitted.get(c)
            if fn is None:
                kern = functools.partial(
                    _flash_bwd_bshd, causal=causal, scale=scale,
                    block_q=c[0], block_k=c[1])

                def chained(q0, k0, v0, o0, l0, g0):
                    return jax.lax.fori_loop(
                        0, reps,
                        lambda _, q: kern(q, k0, v0, o0, l0, g0)[0], q0)

                fn = jitted[c] = jax.jit(chained)
            j = i % nvar
            return fn(qs[j], ks[j], vs[j], outs[j], lses[j], outs[j])

    return tuple(at.autotune(
        key, candidates, run, default, warmup=2, iters=5,
        describe=lambda c: flash_plan(sq_b, sk_b, causal, *c, heads, d,
                                      jnp.dtype(dtype).itemsize)))


def _causal_run(q_idx, kv_idx, block_q, block_k, offset, window=None):
    """Tile intersects the bottom-right-aligned causal region and, with a
    ``window``, the band under it: its last key is one the tile's first
    query still sees."""
    run = kv_idx * block_k <= q_idx * block_q + (block_q - 1) + offset
    if window is not None:
        run = run & (kv_idx * block_k + (block_k - 1)
                     > q_idx * block_q + offset - window)
    return run


def _tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal, offset,
               corner=None, keys_axis=1, window=None):
    """Mask of a tile's scores: the key exists and, causal, the query sees
    it (with a ``window``: is one of the ``window`` keys up to its own).
    ``corner`` (r0, c0, rows, cols): of that part of the tile only.
    ``keys_axis`` 0: of the transposed scores (keys x rows)."""
    r0, c0, rows, cols = corner or (0, 0, block_q, block_k)
    shape = (rows, cols) if keys_axis else (cols, rows)
    q_pos = _at(q_idx * block_q, r0) + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - keys_axis)
    k_pos = _at(kv_idx * block_k, c0) + jax.lax.broadcasted_iota(
        jnp.int32, shape, keys_axis)
    mask = k_pos < seq_k
    if causal:
        mask = mask & (q_pos + offset >= k_pos)
    if window is not None:
        mask = mask & (q_pos + offset < k_pos + window)
    return mask


def _at(base, off):
    return base + off if off else base


# --------------------------------------------------------------------------
# The work inside a causal tile. Query row r of a tile sees its key c iff
# r - c >= d0, d0 = kv_idx*block_k - (seq_k - seq_q) - q_idx*block_q, and
# c < kv_valid (the keys of the tile that exist); under a ``window`` also
# iff r - c <= w0 = d0 + window - 1 (the query's own key and the window - 1
# before it). A tile's work is a static list of BLOCKS (r0, r1, c1, mc0,
# guard, c0, mc1): the scores of the band of query rows [r0, r1) against
# keys [c0, c1), the keys one of its rows sees; only keys [c0, mc1) (the
# window's lower edge) and [mc0, c1) (the diagonal, the tail) can hold a
# masked score and get the mask, c0 <= mc1 <= mc0 <= c1 (an empty range:
# none does; without a window c0 = mc1 = 0); ``guard``: a row of the band
# may see no key of the tile (the forward's fully-masked-row guard).
# --------------------------------------------------------------------------
def _floor_to(x, m):
    return x // m * m


def _ceil_to(x, m):
    return -(-x // m) * m


def _tile_blocks(d0, block_q, block_k, kv_valid, band, window=None):
    """Blocks of the causal tile at ``d0``: bands of ``band`` query rows,
    their keys cut at the lane width (a side no longer than its unit is one
    piece; a longer one is whole units, ``_geometry``)."""
    band, lanes = min(band, block_q), min(_LANES, block_k)
    blocks = []
    for r0 in range(0, block_q, band):
        r1 = r0 + band
        # keys SOME row sees: c <= r1 - 1 - d0; EVERY row: c <= r0 - d0
        c1 = min(max(_ceil_to(min(r1 - d0, kv_valid), lanes), 0), block_k)
        mc0 = min(max(_floor_to(min(r0 - d0 + 1, kv_valid), lanes), 0), c1)
        c0 = mc1 = 0
        guard = r0 < d0
        if window is not None:
            # keys SOME row sees: c >= r0 - w0; EVERY row: c >= r1 - 1 - w0
            w0 = d0 + window - 1
            c0 = min(max(_floor_to(r0 - w0, lanes), 0), c1)
            mc1 = min(max(_ceil_to(r1 - 1 - w0, lanes), c0), c1)
            if mc1 > mc0:       # the two edges meet: the mask everywhere
                mc0 = mc1 = c0
            guard = guard or r1 - 1 - w0 >= kv_valid
        if c1 > c0:
            blocks.append((r0, r1, c1, mc0, guard, c0, mc1))
    return tuple(blocks)


def _canonical_d0(d0, block_q, block_k, window):
    """The ``d0`` that stands for a tile's class: every tile whose rows all
    see all its keys is the one at ``1 - block_k`` (without a window every
    tile from there down; under one those whose window's edge lies below
    the tile too, which exist where the window spans a tile and a half)."""
    if window is None:
        return max(d0, 1 - block_k)
    return 1 - block_k if block_q - window <= d0 <= 1 - block_k else d0


def _geometry(seq_q, seq_k, block_q, block_k, band=None):
    """(block_q, block_k, padded seq_q, padded seq_k) as the kernels'
    wrappers tile a call: a side shorter than the tile is one tile.
    ``band`` (a causal call's): a tile longer than a band is whole bands,
    wider than the lanes whole lane groups, whatever the sequence's length
    or the caller's pin, so that no tile the diagonal crosses runs whole
    (a ragged 2000 x 2000 tile's f32 scores alone are 16 MB of VMEM)."""
    block_q = min(block_q, max(seq_q, 8))
    block_k = min(block_k, max(seq_k, 8))
    if band:
        if block_q > band:
            block_q = _ceil_to(block_q, band)
        if block_k > _LANES:
            block_k = _ceil_to(block_k, _LANES)
    return (block_q, block_k, _ceil_to(seq_q, block_q),
            _ceil_to(seq_k, block_k))


@functools.lru_cache(maxsize=64)      # a model's layers ask alike
def _grid_classes(seq_q, seq_k, causal, block_q, block_k, band, window=None):
    """The grid's tiles by what they run:
    ``((blocks, ((d0, tail), ...), n_tiles), ...)``. A causal tile is told
    by its ``d0`` (``_canonical_d0``: held at 1 - block_k where every row
    sees every key) and by whether it holds the padded tail; tiles above the
    diagonal or, under a ``window``, wholly below the band (``_causal_run``
    false) are in no class. Non-causal tiles are one class of one block,
    masked everywhere (the mask is the tail's)."""
    block_q, block_k, sp_q, sp_k = _geometry(seq_q, seq_k, block_q, block_k,
                                             band if causal else None)
    n_q, n_k = sp_q // block_q, sp_k // block_k
    if not causal:
        return ((((0, block_q, block_k, 0, True, 0, 0),), (), n_q * n_k),)
    kv_tail = seq_k - (n_k - 1) * block_k
    by_key = {}
    for qi in range(n_q):
        for ki in range(n_k):
            d0 = ki * block_k - (seq_k - seq_q) - qi * block_q
            if d0 > block_q - 1 or (window is not None
                                    and d0 + window - 1 < 1 - block_k):
                continue
            key = (_canonical_d0(d0, block_q, block_k, window),
                   ki == n_k - 1 and kv_tail < block_k)
            by_key[key] = by_key.get(key, 0) + 1
    by_blocks = {}
    for (d0, tail), n in by_key.items():
        blocks = _tile_blocks(d0, block_q, block_k,
                              kv_tail if tail else block_k, band, window)
        keys, count = by_blocks.get(blocks, ((), 0))
        by_blocks[blocks] = (keys + ((d0, tail),), count + n)
    return tuple((blocks, keys, n) for blocks, (keys, n) in by_blocks.items())


def _stats_shape(batch, heads, hpb, sp_q):
    """The per-row float32 logsumexp as it lies in HBM between the kernels:
    a lane-dense row a head, the heads of a block together (the block of a
    grid step is the whole of that axis)."""
    return (batch, heads // hpb, hpb, sp_q)


def _hbm_bytes(shape):
    """Bytes of a float32 array in HBM: its last dimension is whole 128-lane
    tiles, and the one before it whole sublanes (8) unless it is 1, 2 or 4,
    which XLA tiles as they are (a (.., S, 1) column is a tile of 8 x 128 to
    every 8 values, a (.., 1, S) row 128 values a tile)."""
    *lead, sub, lane = shape
    return (math.prod(lead) * (sub if sub in (1, 2, 4) else _ceil_to(sub, 8))
            * _ceil_to(lane, _LANES) * 4)


def flash_plan(seq_q, seq_k, causal, block_q, block_k, heads=1,
               head_dim=_LANES, itemsize=2, window=None):
    """What the three kernels execute for a call, fixed when it is traced:
    ``tiles`` (the grid a block of heads, queries x keys), ``sub_block``
    (the rows of a band of a causal tile; None where a tile runs whole),
    ``executed_share``, the area of scores computed over the padded
    seq_q x seq_k square (1.0 non-causal; 0.625 for one causal tile of
    1024 in bands of 256), ``heads_per_block``, the heads that share a
    128-lane block of the (B, S, H*d) array (1: a head is a block of its
    own, padded to whole lane tiles), ``io_bytes``, what one head's q
    occupies in HBM as the kernels read it (its share of the block: S_padded
    x d where heads pack, S_padded x 128 for a lone 64-wide head), and
    ``stats_bytes``, what a head's logsumexp occupies there, the one per-row
    statistic that crosses HBM (the forward writes it, both backward kernels
    read it; delta is theirs, made in VMEM). A call with a ``window`` also
    says it and ``tiles_run``, the tiles of the grid that do any work (the
    others are neither run nor fetched)."""
    bq, bk, sp_q, sp_k = _geometry(seq_q, seq_k, block_q, block_k,
                                   SUB_BLOCK if causal else None)
    classes = _grid_classes(seq_q, seq_k, causal, block_q, block_k,
                            SUB_BLOCK, window)
    area = sum(n * sum((r1 - r0) * (c1 - c0)
                       for r0, r1, c1, _mc0, _g, c0, _mc1 in blocks)
               for blocks, _keys, n in classes)
    hpb, head_lanes = _head_layout(heads, head_dim)
    plan = {"tiles": [sp_q // bq, sp_k // bk],
            "sub_block": min(SUB_BLOCK, bq) if causal else None,
            "executed_share": area / (sp_q * sp_k),
            "heads_per_block": hpb,
            "io_bytes": sp_q * head_lanes * itemsize,
            "stats_bytes": _hbm_bytes(_stats_shape(1, hpb, hpb, sp_q)) // hpb}
    if window is not None:
        plan.update(window=window,
                    tiles_run=sum(n for _b, _k, n in classes))
    return plan


def _plan_entry(kind, seq_q, seq_k, causal, block_q, block_k, heads,
                head_dim, dtype, window=None):
    mask = ("full" if not causal else "causal" if window is None
            else f"window{window}")
    return (f"flash_{kind}[{seq_q}x{seq_k},{mask},{block_q}x{block_k}]",
            flash_plan(seq_q, seq_k, causal, block_q, block_k, heads,
                       head_dim, jnp.dtype(dtype).itemsize, window))


def _stamp_plan(*call):
    """From the vjp rules: the plan on the ``compile.trace`` entry of the
    program being traced (the start-up record; outside a trace, nothing)."""
    from ...observability import trace as _trace
    _trace.compile_note(*_plan_entry(*call))


def _log_plan(*call):
    """From the jitted wrappers, so once a signature a process: the plan on
    the autotuner's logger at ``FLAGS_log_level`` 1."""
    from ...core import flags
    if flags.get_flag("log_level") >= 1:
        logging.getLogger("paddle_tpu.autotune").info(
            "%s: %s", *_plan_entry(*call))


def _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k, offset, body,
              window=None):
    """``body(block)`` for every block of the class the tile is in."""
    if len(classes) == 1:
        for block in classes[0][0]:
            body(block)
        return
    d0 = kv_idx * block_k - offset - q_idx * block_q
    if window is None:
        d0 = jnp.maximum(d0, 1 - block_k)
    else:                           # ``_canonical_d0`` of a traced d0
        d0 = jnp.where((d0 >= block_q - window) & (d0 <= 1 - block_k),
                       1 - block_k, d0)
    last = kv_idx == num_kv - 1
    tails = any(tail for _b, keys, _n in classes for _d0, tail in keys)
    for blocks, keys, _n in classes:
        cond = False
        for value, tail in keys:
            here = d0 == value
            if tails:
                here = here & (last if tail else jnp.logical_not(last))
            cond = here | cond

        @pl.when(cond)
        def _class(blocks=blocks):
            for block in blocks:
                body(block)


def _key_runs(block):
    """The block's keys, relative to its first, as runs ``(start, stop,
    maskable)``, the empty ones left out."""
    _r0, _r1, c1, mc0, _guard, c0, mc1 = block
    runs = ((c0, mc1, True), (mc1, mc0, False), (mc0, c1, True))
    return [(a - c0, b - c0, m) for a, b, m in runs if b > a]


def _masked_keys(x, block, masks, fn, keys_axis=1):
    """``fn(part, mask)`` on the runs of keys of the block's scores ``x``
    ((rows, keys), or (keys, rows) at ``keys_axis`` 0) that can hold masked
    scores (``masks``: ``_block_masks``), the rest as they are."""
    runs, masks = _key_runs(block), iter(masks)
    if len(runs) == 1:
        return fn(x, next(masks)) if runs[0][2] else x
    parts = []
    for a, b, maskable in runs:
        part = x[:, a:b] if keys_axis else x[a:b]
        parts.append(fn(part, next(masks)) if maskable else part)
    return jnp.concatenate(parts, axis=keys_axis)


def _block_masks(block, q_idx, kv_idx, block_q, block_k, seq_k, causal,
                 offset, keys_axis=1, window=None):
    """The masks of the block's maskable runs of keys, in their order
    (``_key_runs``), or None where it has none."""
    r0, r1, _c1, _mc0, _guard, c0, _mc1 = block
    masks = [_tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal,
                        offset, corner=(r0, c0 + a, r1 - r0, b - a),
                        keys_axis=keys_axis, window=window)
             for a, b, maskable in _key_runs(block) if maskable]
    return masks or None


def _scores(q, k, scale):
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _probs(s, lse, block, masks, keys_axis=1):
    """p = exp(s - lse), zero where masked. The mask guards (not just exp
    underflow): for fully-masked rows lse is garbage (~NEG_INF) and
    exp(NEG_INF - lse) would be 1, not 0."""
    if masks is None:
        return jnp.exp(s - lse)
    s = _masked_keys(s, block, masks,
                     lambda x, m: jnp.where(m, x, NEG_INF), keys_axis)
    return _masked_keys(jnp.exp(s - lse), block, masks,
                        lambda x, m: jnp.where(m, x, 0.0), keys_axis)


# --------------------------------------------------------------------------
# Heads of a block. The kernels take their blocks from the (B, S, H*d) array
# the model holds: a block is ``_LANES`` lanes wide and carries
# ``heads_per_block`` heads side by side (two at d 64), or one head of d
# lanes (d a multiple of 128; any other width is padded to one). The band
# body runs once a head (``_for_heads``), on whole blocks: the band's queries
# and dO are taken with the other heads' lanes zeroed (``_head_lanes``), so a
# product that CONTRACTS over the lanes (scores, dP) adds exact zeros and one
# that GIVES lanes is the head's in the head's lanes (P^T dO, dS^T Q: zero in
# the others, accumulated as it is; P V, dS K: the head's lanes chosen). The
# MXU passes are those of a head padded to 128 lanes, and nothing moves
# across lanes.
# --------------------------------------------------------------------------
def _head_layout(heads, d):
    """``(heads_per_block, lanes a head)`` of a call of ``heads`` heads of
    ``d``: heads that tile 128 lanes exactly share a block as they lie in
    (B, S, H*d); any other head is one block of d lanes, padded to whole
    lane tiles."""
    if _LANES % d == 0 and heads * d % _LANES == 0:
        return _LANES // d, d
    return 1, _ceil_to(d, _LANES)


def _probe_heads(heads, d):
    """Heads of the autotuner's probe: the most, up to 8 or one block's,
    that lie as the caller's ``heads`` do (9 heads of 64 are each padded to
    a block of their own, and so are the probe's 7: 8 would pack)."""
    hpb = _head_layout(heads, d)[0]
    return next(n for n in range(min(heads, max(8, hpb)), 0, -1)
                if _head_layout(n, d)[0] == hpb)


def _head_keep(h, hpb, shape):
    """Which lanes of a (n, lanes) block are head ``h``'s (None: all, the
    block is one head's)."""
    if hpb == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    d = shape[1] // hpb
    return (lane >= h * d) & (lane < (h + 1) * d)


def _head_lanes(x, keep):
    """The block ``x`` with the other heads' lanes zeroed."""
    return x if keep is None else jnp.where(keep, x, jnp.zeros_like(x))


def _set_head(ref, idx, keep, value):
    """``ref[idx] = value`` in the head's lanes, the others' as they are."""
    ref[idx] = value if keep is None else jnp.where(keep, value, ref[idx])


def _for_heads(hpb, unroll, body):
    """``body(h)`` for every head of the block, one after the other.
    ``unroll`` False: a real loop (h a traced index), so that a block of
    several heads is the one-head body in code size and compile time."""
    if hpb == 1:
        body(0)
    else:
        def step(h, carry):
            body(h)
            return carry

        jax.lax.fori_loop(0, hpb, step, 0, unroll=unroll)


def _unroll_heads(classes):
    """Whether a grid step's loop over its block's heads is unrolled: where
    its body is few bands (up to ``UNROLL_HEADS_BANDS``), a real loop (the
    head a traced index) where it is more. Timed either way on a v5e (PR
    46, bf16, d 64, the kernels alone, the backward's ms a call unrolled /
    as a loop): a non-causal 4096 in tiles of 512, 1 band, 11.62 / 12.45;
    causal 1024, one tile of 4 bands (the GPT-2 cells'), 0.908 / 1.157;
    causal 2048, one tile of 8, 1.503 / 1.765; causal 4096 and 8192 in
    tiles of 2048, 16 bands in two classes, 9.87 / 6.52 and 36.0 / 24.8
    (LFM2's; 64 MB of scoped VMEM for 32 changes neither). A square causal
    grid of whole tiles has no count between 8 and 16. Compiled for a
    described v5e the two forms' schedules a head are alike at 16 bands
    (the forward's: 68 800 bundles unrolled, 2 x 36 478 as a loop), so what
    the chip pays is not in them: the unrolled kernels are 69-102 thousand
    bundles of code where every kernel that runs well is under 54 thousand
    (8 bands unrolled 22-35, 16 as a loop 36-54), and instruction fetch is
    the suspect (ROADMAP S5 (e)). Unrolling also doubles what Mosaic
    compiles (7.4 s -> 18 s for the three kernels at S 8192)."""
    return sum(len(blocks) for blocks, _k, _n in classes) <= UNROLL_HEADS_BANDS


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, seq_q, seq_k, classes,
                one_pass, hpb, unroll, window):
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2)
    num_kv = pl.num_programs(3)
    # Bottom-right-aligned causal diagonal (matches tril(..., k=t-s) in the
    # XLA reference path): query i attends keys <= i + (seq_k - seq_q).
    causal_offset = seq_k - seq_q

    if not one_pass:
        @pl.when(kv_idx == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)
    elif seq_q > seq_k:
        # the first seq_q - seq_k rows see no key: no band writes them
        o_ref[0] = jnp.zeros_like(o_ref[0])
        lse_ref[0, 0] = jnp.full_like(lse_ref[0, 0], NEG_INF)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset,
                          window)

    def _block(block):
        rows, cols = slice(*block[:2]), slice(block[5], block[2])

        def _head(h):
            q = q_ref[0, rows]    # (rows, lanes)
            k = k_ref[0, cols]    # (keys, lanes)
            v = v_ref[0, cols]
            masks = _block_masks(block, q_idx, kv_idx, block_q, block_k,
                                 seq_k, causal, causal_offset, window=window)
            keep = _head_keep(h, hpb, q.shape)
            s = _scores(_head_lanes(q, keep), k, scale)
            if masks is not None:
                s = _masked_keys(s, block, masks,
                                 lambda x, m: jnp.where(m, x, NEG_INF))

            if one_pass:
                # the band's only keys: nothing to merge, nothing to carry
                m_new = jnp.max(s, axis=1, keepdims=True)
                p = jnp.exp(s - m_new)
                if block[4]:
                    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
                l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
                acc = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if keep is None:
                    o_ref[0, rows] = (acc / l).astype(o_ref.dtype)
                else:       # the heads' lanes meet in the idle accumulator
                    _set_head(acc_scr, rows, keep, acc / l)
                lse_ref[0, 0, pl.ds(h, 1), rows] = (m_new + jnp.log(l)).T
                return
            m_prev = m_scr[h, rows]                # (rows, 1)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                 # (rows, keys)
            if block[4]:
                # fully-masked rows (causal, seq_q > seq_k): m_new ==
                # NEG_INF and exp(s - m_new) == 1; zero them so l stays 0,
                # out stays 0
                p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h, rows] = (alpha * l_scr[h, rows]
                              + jnp.sum(p, axis=1, keepdims=True))
            m_scr[h, rows] = m_new
            _set_head(acc_scr, rows, keep,
                      acc_scr[rows] * alpha + jax.lax.dot_general(
                          p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

        _for_heads(hpb, unroll, _head)
        if one_pass and hpb > 1:
            o_ref[0, rows] = acc_scr[rows].astype(o_ref.dtype)

    @pl.when(run)
    def _step():
        _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k,
                  causal_offset, _block, window)

    if not one_pass:
        @pl.when(kv_idx == num_kv - 1)
        def _finish():
            def _head(h):
                keep = _head_keep(h, hpb, acc_scr.shape)
                l = jnp.maximum(l_scr[h], 1e-30)
                _set_head(acc_scr, slice(None), keep, acc_scr[:] / l)
                lse_ref[0, 0, pl.ds(h, 1), :] = (m_scr[h] + jnp.log(l)).T

            _for_heads(hpb, unroll, _head)
            o_ref[0] = acc_scr[:].astype(o_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_k, seq_q, seq_k,
               classes, hpb, unroll, window):
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2)
    num_kv = pl.num_programs(3)
    causal_offset = seq_k - seq_q

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset,
                          window)

    def _block(block):
        rows, cols = slice(*block[:2]), slice(block[5], block[2])

        def _head(h):
            q = q_ref[0, rows]
            k = k_ref[0, cols]
            v = v_ref[0, cols]
            do = do_ref[0, rows]
            out = out_ref[0, rows].astype(jnp.float32)
            masks = _block_masks(block, q_idx, kv_idx, block_q, block_k,
                                 seq_k, causal, causal_offset, window=window)
            keep = _head_keep(h, hpb, q.shape)
            do_h = _head_lanes(do, keep)
            # row -> column
            lse = lse_ref[0, 0, pl.ds(h, 1), rows].reshape(-1, 1)
            # delta = rowsum(dO * out) of the head, the blocks as they lie
            delta = jnp.sum(do_h.astype(jnp.float32) * out, axis=1,
                            keepdims=True)
            s = _scores(_head_lanes(q, keep), k, scale)
            p = _probs(s, lse, block, masks)
            dp = jax.lax.dot_general(
                do_h, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale          # (rows, keys) fp32
            dq_scr[rows] += _head_lanes(jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32), keep)

        _for_heads(hpb, unroll, _head)

    @pl.when(run)
    def _step():
        _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k,
                  causal_offset, _block, window)

    @pl.when(kv_idx == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, causal, block_q, block_k,
                seq_q, seq_k, classes, hpb, unroll, window):
    q_idx = pl.program_id(3)       # q innermost in this kernel
    kv_idx = pl.program_id(2)
    num_q = pl.num_programs(3)
    num_kv = pl.num_programs(2)
    causal_offset = seq_k - seq_q

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset,
                          window)

    def _block(block):
        rows, cols = slice(*block[:2]), slice(block[5], block[2])

        def _head(h):
            q = q_ref[0, rows]
            k = k_ref[0, cols]
            v = v_ref[0, cols]
            do = do_ref[0, rows]
            out = out_ref[0, rows].astype(jnp.float32)
            masks = _block_masks(block, q_idx, kv_idx, block_q, block_k,
                                 seq_k, causal, causal_offset, keys_axis=0,
                                 window=window)
            keep = _head_keep(h, hpb, q.shape)
            # queries and dO with the head's lanes alone: the products that
            # give lanes (P^T dO, dS^T Q) are then zero in the others'
            q_h, do_h = _head_lanes(q, keep), _head_lanes(do, keep)
            lse = lse_ref[0, 0, pl.ds(h, 1), rows]  # (1, rows): down the keys
            delta = jnp.sum(do_h.astype(jnp.float32) * out, axis=1,
                            keepdims=True).T       # column -> row
            # everything (keys, rows): the statistics broadcast as they lie
            # and no product contracts over the leading dimension of the
            # scores
            s_t = _scores(k, q_h, scale)
            p_t = _probs(s_t, lse, block, masks, keys_axis=0)
            # dv += P^T dO
            dv_scr[cols] += jax.lax.dot_general(
                p_t.astype(do.dtype), do_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(
                v, do_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds_t = p_t * (dp_t - delta) * scale
            # dk += dS^T Q
            dk_scr[cols] += jax.lax.dot_general(
                ds_t.astype(q.dtype), q_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _for_heads(hpb, unroll, _head)

    @pl.when(run)
    def _step():
        _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k,
                  causal_offset, _block, window)

    @pl.when(q_idx == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _to_blocks(x, block_s, lanes):
    """(B, S, H, d) -> (B, S_padded, H * lanes): the array the kernels take
    their blocks from. Heads that pack (``lanes`` == d) and a sequence of
    whole tiles: the reshape alone, which is the model's own (B, S, H*d)
    array; else zeros to the tile and to ``lanes`` a head."""
    b, s, h, d = x.shape
    pad_s = -s % block_s
    if pad_s or lanes != d:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0), (0, lanes - d)))
    return x.reshape(b, s + pad_s, h * lanes)


def _from_blocks(x, s, h, d):
    """``_to_blocks`` undone: (B, S_padded, H * lanes) -> (B, S, H, d)."""
    b, sp, width = x.shape
    x = x.reshape(b, sp, h, width // h)
    return x if (sp, width) == (s, h * d) else x[:, :s, :, :d]


def _compiler_params(causal, block_q, block_k):
    """Mosaic's parameters for a call: the default (None) unless it is a
    causal call of tiles wider than 1024, which needs more scoped VMEM than
    the default limit (``MULTI_TILE_VMEM_BYTES``)."""
    if causal and max(block_q, block_k) > 1024:
        return pltpu.CompilerParams(vmem_limit_bytes=MULTI_TILE_VMEM_BYTES)
    return None


def _band_tile(j, i, tile_i, tile_j, n_j, lo, hi):
    """Tile ``j`` of the other side held inside the tiles that tile ``i``
    of this side meets under a window, those of positions ``i * tile_i +
    lo`` to ``i * tile_i + hi``: a grid step outside the band names the
    block of the nearest step inside it, which is then not fetched again."""
    first = jnp.maximum(i * tile_i + lo, 0) // tile_j
    last = jnp.maximum(i * tile_i + hi, 0) // tile_j
    return jnp.clip(j, jnp.minimum(first, n_j - 1), jnp.minimum(last, n_j - 1))


def _flash_fwd_bshd(q, k, v, *, causal, scale, block_q, block_k,
                    window=None):
    """q/k/v: (B, S, H, d) -> (out (B, S, H, d), lse fp32 (B, H //
    heads_per_block, heads_per_block, Sq_padded))."""
    return _fwd_call(q, k, v, causal=causal, scale=float(scale),
                     block_q=block_q, block_k=block_k, band=SUB_BLOCK,
                     interpret=INTERPRET, window=window)


# One jitted function a kernel: a model's layers call it with one signature,
# so the program that holds them traces the kernel body and lowers it to
# Mosaic once, not once a layer (the module's switches are arguments, so a
# test that flips one is not served the other's trace).
_STATIC = ("causal", "scale", "block_q", "block_k", "band", "interpret",
           "window")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, *, causal, scale, block_q, block_k, band, interpret,
              window=None):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    _log_plan("fwd", s_q, s_k, causal, block_q, block_k, h, d, q.dtype,
              window)
    classes = _grid_classes(s_q, s_k, causal, block_q, block_k, band, window)
    block_q, block_k, sp_q, sp_k = _geometry(s_q, s_k, block_q, block_k,
                                             band if causal else None)
    hpb, head_lanes = _head_layout(h, d)
    lanes = hpb * head_lanes
    q = _to_blocks(q, block_q, head_lanes)
    k = _to_blocks(k, block_k, head_lanes)
    v = _to_blocks(v, block_k, head_lanes)

    grid = (b, h // hpb, sp_q // block_q, sp_k // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=s_q, seq_k=s_k, classes=classes,
        # a causal row of tiles one tile long: every band meets all its
        # keys at once (a non-causal call keeps the kernel it had). On a
        # v5e the running-state form runs the same bands 1.44 times as long
        # at S 1024, d 64 (1.20 at S 2048): 4.6 % of a GPT-2 345M step
        one_pass=causal and sp_k == block_k, hpb=hpb,
        unroll=_unroll_heads(classes), window=window)
    q_spec = pl.BlockSpec((1, block_q, lanes), lambda b, g, i, j: (b, i, g))
    kv_spec = pl.BlockSpec((1, block_k, lanes), _kv_index(
        window, block_q, block_k, sp_k // block_k, s_k - s_q))
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(_stats_shape(b, h, hpb, sp_q),
                                 jnp.float32)],
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, hpb, block_q),
                         lambda b, g, i, j: (b, g, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hpb, block_q, 1), jnp.float32),
            pltpu.VMEM((hpb, block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ],
        compiler_params=_compiler_params(causal, block_q, block_k),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return _from_blocks(out, s_q, h, d), lse


def _kv_index(window, block_q, block_k, n_k, offset):
    """Index map of the K / V blocks of a grid (batch, heads, q tiles, kv
    tiles): tile j as it is, or under a ``window`` held inside the band of
    q tile i (``_band_tile``)."""
    if window is None:
        return lambda b, g, i, j: (b, j, g)
    return lambda b, g, i, j: (
        b, _band_tile(j, i, block_q, block_k, n_k, offset - (window - 1),
                      offset + block_q - 1), g)


def _flash_bwd_bshd(q, k, v, out, lse, do, *, causal, scale, block_q,
                    block_k, window=None):
    """FA2 backward. All of q/k/v/out/do: (B, S, H, d); lse as the forward
    gives it. Returns (dq, dk, dv), (B, S, H, d)."""
    return _bwd_call(q, k, v, out, lse, do, causal=causal,
                     scale=float(scale), block_q=block_q, block_k=block_k,
                     band=SUB_BLOCK, interpret=INTERPRET, window=window)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, out, lse, do, *, causal, scale, block_q, block_k,
              band, interpret, window=None):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    _log_plan("bwd", s_q, s_k, causal, block_q, block_k, h, d, q.dtype,
              window)
    classes = _grid_classes(s_q, s_k, causal, block_q, block_k, band, window)
    block_q, block_k, sp_q, sp_k = _geometry(s_q, s_k, block_q, block_k,
                                             band if causal else None)
    hpb, head_lanes = _head_layout(h, d)
    lanes = hpb * head_lanes

    # delta = rowsum(dO * out) is the kernels' own, a band and a head at a
    # time from the blocks of dO and out as they lie: no pass of XLA's over
    # the two arrays, and no (B, S, H) array to turn into rows
    q = _to_blocks(q, block_q, head_lanes)
    do = _to_blocks(do, block_q, head_lanes)
    out = _to_blocks(out, block_q, head_lanes)
    k = _to_blocks(k, block_k, head_lanes)
    v = _to_blocks(v, block_k, head_lanes)
    if lse.shape[3] < sp_q:     # fwd may have tiled with a different block
        lse = jnp.pad(lse, ((0, 0),) * 3 + ((0, sp_q - lse.shape[3]),))
    elif lse.shape[3] > sp_q:
        lse = lse[..., :sp_q]

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              seq_q=s_q, seq_k=s_k, classes=classes, hpb=hpb,
              unroll=_unroll_heads(classes), window=window)
    q_spec = pl.BlockSpec((1, block_q, lanes), lambda b, g, i, j: (b, i, g))
    k_spec = pl.BlockSpec((1, block_k, lanes), _kv_index(
        window, block_q, block_k, sp_k // block_k, s_k - s_q))
    row_spec = pl.BlockSpec((1, 1, hpb, block_q),
                            lambda b, g, i, j: (b, g, 0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, h // hpb, sp_q // block_q, sp_k // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, q_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, lanes), jnp.float32)],
        compiler_params=_compiler_params(causal, block_q, block_k),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, out)

    # dk/dv: kv outer, q inner; under a window q tile j held inside the
    # band of kv tile i
    def q_tile(i, j):
        if window is None:
            return j
        return _band_tile(j, i, block_k, block_q, sp_q // block_q,
                          s_q - s_k, s_q - s_k + block_k - 1 + window - 1)

    qi_spec = pl.BlockSpec((1, block_q, lanes),
                           lambda b, g, i, j: (b, q_tile(i, j), g))
    rowi_spec = pl.BlockSpec((1, 1, hpb, block_q),
                             lambda b, g, i, j: (b, g, 0, q_tile(i, j)))
    kv_spec = pl.BlockSpec((1, block_k, lanes), lambda b, g, i, j: (b, i, g))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(b, h // hpb, sp_k // block_k, sp_q // block_q),
        in_specs=[qi_spec, kv_spec, kv_spec, qi_spec, rowi_spec, qi_spec],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((block_k, lanes), jnp.float32),
                        pltpu.VMEM((block_k, lanes), jnp.float32)],
        compiler_params=_compiler_params(causal, block_q, block_k),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, out)
    return (_from_blocks(dq, s_q, h, d), _from_blocks(dk, s_k, h, d),
            _from_blocks(dv, s_k, h, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, block_q, block_k, window=None):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, window)[0]


def _resolve_blocks(kind, block_q, block_k, q, k, causal, scale):
    """Caller-pinned tiles win; unset ones come from the autotuner. The
    vjp rules run while the model's step is being TRACED, so the probe
    is built and run in an eval context: it must execute on the chip,
    not be staged into the caller's program (where its timings would be
    trace time)."""
    if block_q is not None and block_k is not None:
        return block_q, block_k
    _b, s, h, d = q.shape
    with jax.core.eval_context():
        tq, tk = _tuned_blocks(kind, h, s, k.shape[1], d, q.dtype, causal,
                               scale)
    return (tq if block_q is None else block_q,
            tk if block_k is None else block_k)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window):
    """(out (B, S, H, d), lse (B, H // hpb, hpb, S_padded)) as the kernel
    gives them."""
    block_q, block_k = _resolve_blocks("fwd", block_q, block_k, q, k,
                                       causal, scale)
    _stamp_plan("fwd", q.shape[1], k.shape[1], causal, block_q, block_k,
                q.shape[2], q.shape[3], q.dtype, window)
    return _flash_fwd_bshd(q, k, v, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k, window=window)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, window):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, window)
    out = _keep(out, KEPT_RESIDUALS[0])
    # the kernel's rows as they come: kept, and read by the backward
    # kernels, with no pass of XLA's over them
    lse = _keep(lse, KEPT_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    block_q, block_k = _resolve_blocks("bwd", block_q, block_k, q, k,
                                       causal, scale)
    _stamp_plan("bwd", q.shape[1], k.shape[1], causal, block_q, block_k,
                q.shape[2], q.shape[3], q.dtype, window)
    return _flash_bwd_bshd(q, k, v, out, lse, g, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k, window=window)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None, window=None):
    """Public entry: q/k/v (batch, seq, heads, head_dim). ``block_q`` /
    ``block_k`` tune the tile sizes (unset: the autotuner's pick on a TPU,
    else DEFAULT_BLOCK_Q/K, forward and backward). ``window`` (a causal
    call's): query ``i`` sees key ``j`` iff ``0 <= i - j < window``, its own
    key and the ``window - 1`` before it (``i`` counted from the keys' end
    where the sides differ in length, as the causal diagonal is); a window
    no shorter than the keys is no window, and the call is the causal one,
    kernel for kernel."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a causal call's, and at least 1")
        if window >= k.shape[1]:
            window = None
    return _flash_attention(q, k, v, causal, scale, block_q, block_k, window)
