"""Flash attention (forward + backward) as Pallas TPU kernels.

Replaces the reference's FlashAttention-2 CUDA library integration
(reference: third_party/flashattn; op `flash_attn` at
paddle/phi/ops/yaml/ops.yaml:1635). Design:

* forward — online-softmax over KV tiles: grid (batch*heads, q_tiles,
  kv_tiles) with the kv axis innermost so the fp32 accumulators in VMEM
  scratch persist across kv steps; the MXU consumes (Bq, d) x (d, Bk)
  tiles; causal tiles above the diagonal are skipped with @pl.when, and
  INSIDE a tile the diagonal crosses only the part at or below it runs
  (``_tile_blocks``: bands of ``SUB_BLOCK`` query rows, each against the
  keys it can see), so no FLOPs are spent on masked blocks whatever the
  tile. Also emits the per-row logsumexp
  (the FA2 "L" residual) for backward.
* backward — the FA2 recompute strategy, O(S·d) memory: residuals are only
  (q, k, v, out, lse); each backward tile recomputes p = exp(qk·scale−lse)
  on the fly. Two kernels: dQ iterates kv innermost accumulating
  dq += ds·K; dK/dV iterates q innermost accumulating dv += pᵀ·dO and
  dk += dsᵀ·Q, where ds = p·(dp − Δ)·scale, dp = dO·Vᵀ and
  Δ = rowsum(dO∘O) is precomputed by one fused XLA reduction. dK/dV
  computes everything TRANSPOSED, sᵀ = K·Qᵀ (keys x rows), so that pᵀ and
  dsᵀ are what it holds and both accumulations are plain products (no
  contraction over the leading dimension of a scores-sized array). The
  full (S, S) probability matrix is never materialized in either pass.
* the per-row statistics (logsumexp, Δ) are lane-dense ROWS in HBM,
  (BH, 1, S_padded) float32 in blocks of (1, 1, block_q), from the forward
  kernel's store to the backward kernels' loads: 4 bytes a value (a
  (.., S, 1) column is a whole (8, 128) tile to every 8 values, 128 times
  that). The forward turns a band's (rows, 1) column into the row in VMEM
  before the store; dK/dV's transposed scores take a (1, rows) block as
  it lies, broadcast down the keys; dQ, which keeps (rows x keys), turns
  its block back into a column in VMEM.

``block_q`` / ``block_k`` are exposed for tuning (reference
flash_attn's num_splits analog); ``INTERPRET=True`` runs the same kernels
through the Pallas interpreter so CPU tests cover the real kernel code.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import math
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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

#: the two residuals of a call that the backward kernels read and the forward
#: kernel alone can make, as the forward rule names them
#: (``jax.ad_checkpoint.checkpoint_name``): ``out`` as (B, S, H, d), the array
#: the block goes on with, and the logsumexp as the forward kernel writes it,
#: lane-dense (BH, 1, S_padded) float32 rows. A rematerialised block keeps
#: exactly these beside its input (``models/_remat.py``), so its backward
#: recomputes everything but the kernel; outside a ``jax.checkpoint`` a name
#: is the identity.
KEPT_RESIDUALS = ("flash_out", "flash_lse")

#: scoped VMEM a call of several 2048-wide causal tiles asks for: beside the
#: band's scores such a tile keeps running state (max, sum, accumulator,
#: each row a whole (8, 128) tile of f32) and double-buffered operands,
#: 19.2 MB at d 64 against the compiler's default limit of 16 MB, which
#: refused every causal call longer than one tile (S 4096, S 8192). A v5e
#: core has 128 MiB. One-tile calls (S <= 2048) ask for nothing and compile
#: the kernels they compiled before.
MULTI_TILE_VMEM_BYTES = 32 * 1024 * 1024

#: rows of queries in a band of a causal tile (``_tile_blocks``)
SUB_BLOCK = 256
_LANES = 128

# Candidate tile grids of a non-causal call for the measured autotuner
# (ops/pallas/autotune.py). Small on purpose: each candidate costs one
# Pallas compile at first sight of a new (shape-class, chip) key; winners
# persist to disk.
FWD_TILE_CANDIDATES = [(1024, 1024), (512, 512), (512, 1024), (1024, 512),
                       (2048, 512)]
BWD_TILE_CANDIDATES = [(512, 512), (1024, 1024), (256, 512), (512, 1024),
                       (1024, 512)]


def _tuned_blocks(kind, bh, s_q, s_k, d, dtype, causal, scale):
    """(block_q, block_k) for this shape class on this chip: a causal call's
    constant, a non-causal call's measured winner.

    Falls back to the hand-tuned v5e constants when autotuning is off or
    the backend is not a real TPU (reference
    phi/kernels/autotune/switch_autotune.cc gate). Benchmarks run on
    noise at the BUCKETED sequence lengths (tile ranking is data- and
    batch-mostly-independent; batch*heads is capped at 8 to keep the
    probe cheap).
    """
    from . import autotune as at

    if causal:
        return _causal_block_for(s_q), _causal_block_for(s_k)
    if INTERPRET or not at.should_autotune():
        if kind == "fwd":
            return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
        return _bwd_block_for(s_q), _bwd_block_for(s_k)

    sq_b, sk_b = at.seq_bucket(s_q), at.seq_bucket(s_k)
    key = at.make_key(f"flash_{kind}", sq=sq_b, sk=sk_b, d=d,
                      dt=str(jnp.dtype(dtype)), causal=bool(causal))
    cached = at.get_cache().get(key)
    if cached is not None:
        return tuple(cached)

    bh_b = min(bh, 8)
    # probe on noise, not zeros (constant-folding could skip real work)
    nvar = 3
    qs, ks, vs = [], [], []
    for i in range(nvar):
        kp = jax.random.key(i)
        qs.append(jax.random.normal(kp, (bh_b, sq_b, d)).astype(dtype))
        ks.append(jax.random.normal(
            jax.random.fold_in(kp, 1), (bh_b, sk_b, d)).astype(dtype))
        vs.append(jax.random.normal(
            jax.random.fold_in(kp, 2), (bh_b, sk_b, d)).astype(dtype))
    # amortize per-call dispatch under the kernel: chain K applications
    # data-dependently inside ONE program (the kernel's q-shaped output
    # feeds the next iteration), sized so device time dominates
    kernel_flops = 4.0 * bh_b * sq_b * sk_b * d * (0.5 if causal else 1.0)
    reps = at.probe_reps(kernel_flops)
    jitted = {}
    if kind == "fwd":
        candidates, default = FWD_TILE_CANDIDATES, (DEFAULT_BLOCK_Q,
                                                    DEFAULT_BLOCK_K)

        def run(c, i):
            fn = jitted.get(c)
            if fn is None:
                kern = functools.partial(
                    _flash_fwd_bhsd, causal=causal, scale=scale,
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
            _flash_fwd_bhsd, causal=causal, scale=scale,
            block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K))
        outs, lses = zip(*(fwd(qs[j], ks[j], vs[j])
                           for j in range(nvar)))

        def run(c, i):
            fn = jitted.get(c)
            if fn is None:
                kern = functools.partial(
                    _flash_bwd_bhsd, causal=causal, scale=scale,
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
        describe=lambda c: flash_plan(sq_b, sk_b, causal, *c, kind)))


def _causal_run(q_idx, kv_idx, block_q, block_k, offset):
    """Tile intersects the bottom-right-aligned causal region."""
    return kv_idx * block_k <= q_idx * block_q + (block_q - 1) + offset


def _tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal, offset,
               corner=None, keys_axis=1):
    """Mask of a tile's scores: the key exists and, causal, the query sees
    it. ``corner`` (r0, c0, rows, cols): of that part of the tile only.
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
    return mask


def _at(base, off):
    return base + off if off else base


# --------------------------------------------------------------------------
# The work inside a causal tile. Query row r of a tile sees its key c iff
# r - c >= d0, d0 = kv_idx*block_k - (seq_k - seq_q) - q_idx*block_q, and
# c < kv_valid (the keys of the tile that exist). A tile's work is a static
# list of BLOCKS (r0, r1, c1, mc0, guard): the scores of the band of query
# rows [r0, r1) against keys [0, c1), the keys one of its rows sees; only
# keys [mc0, c1) can hold a masked score and get the mask (mc0 == c1: none
# does); ``guard``: a row of the band may see no key of the tile (the
# forward's fully-masked-row guard).
# --------------------------------------------------------------------------
def _floor_to(x, m):
    return x // m * m


def _ceil_to(x, m):
    return -(-x // m) * m


def _tile_blocks(d0, block_q, block_k, kv_valid, band):
    """Blocks of the causal tile at ``d0``: bands of ``band`` query rows,
    their keys cut at the lane width (a side no longer than its unit is one
    piece; a longer one is whole units, ``_geometry``)."""
    band, lanes = min(band, block_q), min(_LANES, block_k)
    blocks = []
    for r0 in range(0, block_q, band):
        r1 = r0 + band
        # keys SOME row sees: c <= r1 - 1 - d0; EVERY row: c <= r0 - d0
        c1 = min(max(_ceil_to(min(r1 - d0, kv_valid), lanes), 0), block_k)
        if c1:
            mc0 = min(max(_floor_to(min(r0 - d0 + 1, kv_valid), lanes), 0),
                      c1)
            blocks.append((r0, r1, c1, mc0, r0 < d0))
    return tuple(blocks)


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
def _grid_classes(seq_q, seq_k, causal, block_q, block_k, band):
    """The grid's tiles by what they run:
    ``((blocks, ((d0, tail), ...), n_tiles), ...)``. A causal tile is told
    by its ``d0`` (held at 1 - block_k, from where on every row sees every
    key) and by whether it holds the padded tail; tiles above the diagonal
    (``_causal_run`` false) are in no class. Non-causal tiles are one
    class of one block, masked everywhere (the mask is the tail's)."""
    block_q, block_k, sp_q, sp_k = _geometry(seq_q, seq_k, block_q, block_k,
                                             band if causal else None)
    n_q, n_k = sp_q // block_q, sp_k // block_k
    if not causal:
        return ((((0, block_q, block_k, 0, True),), (), n_q * n_k),)
    kv_tail = seq_k - (n_k - 1) * block_k
    by_key = {}
    for qi in range(n_q):
        for ki in range(n_k):
            d0 = ki * block_k - (seq_k - seq_q) - qi * block_q
            if d0 > block_q - 1:
                continue
            key = (max(d0, 1 - block_k), ki == n_k - 1 and kv_tail < block_k)
            by_key[key] = by_key.get(key, 0) + 1
    by_blocks = {}
    for (d0, tail), n in by_key.items():
        blocks = _tile_blocks(d0, block_q, block_k,
                              kv_tail if tail else block_k, band)
        keys, count = by_blocks.get(blocks, ((), 0))
        by_blocks[blocks] = (keys + ((d0, tail),), count + n)
    return tuple((blocks, keys, n) for blocks, (keys, n) in by_blocks.items())


def _stats_shape(bh, sp_q):
    """The per-row float32 statistics of ``bh`` heads (logsumexp, delta) as
    they lie in HBM between the kernels: one lane-dense row a head."""
    return (bh, 1, sp_q)


def _hbm_bytes(shape):
    """Bytes of a float32 array in HBM: its last dimension is whole 128-lane
    tiles, and the one before it whole sublanes (8) unless it is 1 (a
    (.., S, 1) column is a tile of 8 x 128 to every 8 values, a (.., 1, S)
    row 128 values a tile)."""
    *lead, sub, lane = shape
    return (math.prod(lead) * (sub if sub == 1 else _ceil_to(sub, 8))
            * _ceil_to(lane, _LANES) * 4)


def flash_plan(seq_q, seq_k, causal, block_q, block_k, kind="fwd"):
    """What the three kernels execute for a call, fixed when it is traced:
    ``tiles`` (the grid a head, queries x keys), ``sub_block`` (the rows
    of a band of a causal tile; None where a tile runs whole),
    ``executed_share``, the area of scores computed over the padded
    seq_q x seq_k square (1.0 non-causal; 0.625 for one causal tile of
    1024 in bands of 256), and ``stats_bytes``, what a head's per-row
    statistics occupy in HBM as the kernels lay them out: the logsumexp
    the forward (``kind`` "fwd") writes, the logsumexp and delta the
    backward ("bwd") reads."""
    bq, bk, sp_q, sp_k = _geometry(seq_q, seq_k, block_q, block_k,
                                   SUB_BLOCK if causal else None)
    area = sum(n * sum((r1 - r0) * c1 for r0, r1, c1, _mc0, _g in blocks)
               for blocks, _keys, n in _grid_classes(
                   seq_q, seq_k, causal, block_q, block_k, SUB_BLOCK))
    return {"tiles": [sp_q // bq, sp_k // bk],
            "sub_block": min(SUB_BLOCK, bq) if causal else None,
            "executed_share": area / (sp_q * sp_k),
            "stats_bytes": ((2 if kind == "bwd" else 1)
                            * _hbm_bytes(_stats_shape(1, sp_q)))}


def _plan_entry(kind, seq_q, seq_k, causal, block_q, block_k):
    return (f"flash_{kind}[{seq_q}x{seq_k},{'causal' if causal else 'full'},"
            f"{block_q}x{block_k}]",
            flash_plan(seq_q, seq_k, causal, block_q, block_k, kind))


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


def _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k, offset, body):
    """``body(block)`` for every block of the class the tile is in."""
    if len(classes) == 1:
        for block in classes[0][0]:
            body(block)
        return
    d0 = jnp.maximum(kv_idx * block_k - offset - q_idx * block_q,
                     1 - block_k)
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


def _masked_keys(x, block, fn, keys_axis=1):
    """``fn`` on the keys of the block's scores ``x`` ((rows, keys), or
    (keys, rows) at ``keys_axis`` 0) that can hold masked scores, the rest
    as they are."""
    _r0, _r1, c1, mc0, _guard = block
    if mc0 == 0:
        return fn(x)
    if mc0 == c1:
        return x
    seen, maskable = (x[:, :mc0], x[:, mc0:]) if keys_axis else (x[:mc0],
                                                                 x[mc0:])
    return jnp.concatenate([seen, fn(maskable)], axis=keys_axis)


def _block_mask(block, q_idx, kv_idx, block_q, block_k, seq_k, causal,
                offset, keys_axis=1):
    """The mask of the block's maskable keys, or None where it has none."""
    r0, r1, c1, mc0, _guard = block
    if mc0 == c1:
        return None
    return _tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal, offset,
                      corner=(r0, mc0, r1 - r0, c1 - mc0),
                      keys_axis=keys_axis)


def _scores(q, k, scale):
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _probs(s, lse, block, mask, keys_axis=1):
    """p = exp(s - lse), zero where masked. The mask guards (not just exp
    underflow): for fully-masked rows lse is garbage (~NEG_INF) and
    exp(NEG_INF - lse) would be 1, not 0."""
    if mask is None:
        return jnp.exp(s - lse)
    s = _masked_keys(s, block, lambda x: jnp.where(mask, x, NEG_INF),
                     keys_axis)
    return _masked_keys(jnp.exp(s - lse), block,
                        lambda x: jnp.where(mask, x, 0.0), keys_axis)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, seq_q, seq_k, classes,
                one_pass):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)
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
        lse_ref[0] = jnp.full_like(lse_ref[0], NEG_INF)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset)

    def _block(block):
        rows, cols = slice(*block[:2]), slice(0, block[2])
        q = q_ref[0, rows]    # (rows, d)
        k = k_ref[0, cols]    # (keys, d)
        v = v_ref[0, cols]
        s = _scores(q, k, scale)
        mask = _block_mask(block, q_idx, kv_idx, block_q, block_k, seq_k,
                           causal, causal_offset)
        if mask is not None:
            s = _masked_keys(s, block, lambda x: jnp.where(mask, x, NEG_INF))

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
            o_ref[0, rows] = (acc / l).astype(o_ref.dtype)
            lse_ref[0, :, rows] = (m_new + jnp.log(l)).T
            return
        m_prev = m_scr[rows]                   # (rows, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                 # (rows, keys)
        if block[4]:
            # fully-masked rows (causal, seq_q > seq_k): m_new == NEG_INF
            # and exp(s - m_new) == 1; zero them so l stays 0, out stays 0
            p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[rows] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[rows] = acc_scr[rows] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[rows] = m_new
        l_scr[rows] = l_new

    @pl.when(run)
    def _step():
        _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k,
                  causal_offset, _block)

    if not one_pass:
        @pl.when(kv_idx == num_kv - 1)
        def _finish():
            l = jnp.maximum(l_scr[:], 1e-30)
            o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
            lse_ref[0] = (m_scr[:] + jnp.log(l)).T


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_k, seq_q, seq_k,
               classes):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)
    causal_offset = seq_k - seq_q

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset)

    def _block(block):
        rows, cols = slice(*block[:2]), slice(0, block[2])
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        lse = lse_ref[0, :, rows].reshape(-1, 1)    # (1, rows) -> (rows, 1)
        delta = delta_ref[0, :, rows].reshape(-1, 1)
        s = _scores(q, k, scale)
        mask = _block_mask(block, q_idx, kv_idx, block_q, block_k, seq_k,
                           causal, causal_offset)
        p = _probs(s, lse, block, mask)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale          # (rows, keys) fp32
        dq_scr[rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(run)
    def _step():
        _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k,
                  causal_offset, _block)

    @pl.when(kv_idx == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, causal, block_q, block_k,
                seq_q, seq_k, classes):
    q_idx = pl.program_id(2)       # q innermost in this kernel
    kv_idx = pl.program_id(1)
    num_q = pl.num_programs(2)
    num_kv = pl.num_programs(1)
    causal_offset = seq_k - seq_q

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset)

    def _block(block):
        rows, cols = slice(*block[:2]), slice(0, block[2])
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        lse = lse_ref[0, :, rows]              # (1, rows): down the keys
        delta = delta_ref[0, :, rows]
        # everything (keys, rows): the statistics broadcast as they lie and
        # no product contracts over the leading dimension of the scores
        s_t = _scores(k, q, scale)
        mask = _block_mask(block, q_idx, kv_idx, block_q, block_k, seq_k,
                           causal, causal_offset, keys_axis=0)
        p_t = _probs(s_t, lse, block, mask, keys_axis=0)
        # dv += P^T dO
        dv_scr[cols] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta) * scale
        # dk += dS^T Q
        dk_scr[cols] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(run)
    def _step():
        _for_tile(classes, q_idx, kv_idx, num_kv, block_q, block_k,
                  causal_offset, _block)

    @pl.when(q_idx == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pad_bhsd(x, block_s, pad_d):
    pad_s = (-x.shape[1]) % block_s
    if pad_s or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, pad_d)))
    return x


def _compiler_params(causal, sp_q, sp_k, block_q, block_k):
    """Mosaic's parameters for a call: the default (None) unless it is a
    causal call of SEVERAL tiles wider than 1024, which needs more scoped
    VMEM than the default limit (``MULTI_TILE_VMEM_BYTES``)."""
    if (causal and max(block_q, block_k) > 1024
            and (sp_q > block_q or sp_k > block_k)):
        return pltpu.CompilerParams(vmem_limit_bytes=MULTI_TILE_VMEM_BYTES)
    return None


def _flash_fwd_bhsd(q, k, v, *, causal, scale, block_q, block_k):
    """q/k/v: (BH, S, d) -> (out (BH, S, d), lse fp32 (BH, 1, Sq_padded))."""
    return _fwd_call(q, k, v, causal=causal, scale=float(scale),
                     block_q=block_q, block_k=block_k, band=SUB_BLOCK,
                     interpret=INTERPRET)


# One jitted function a kernel: a model's layers call it with one signature,
# so the program that holds them traces the kernel body and lowers it to
# Mosaic once, not once a layer (the module's switches are arguments, so a
# test that flips one is not served the other's trace).
_STATIC = ("causal", "scale", "block_q", "block_k", "band", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, *, causal, scale, block_q, block_k, band, interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    _log_plan("fwd", s_q, s_k, causal, block_q, block_k)
    classes = _grid_classes(s_q, s_k, causal, block_q, block_k, band)
    block_q, block_k, sp_q, sp_k = _geometry(s_q, s_k, block_q, block_k,
                                             band if causal else None)
    pad_d = (-d) % 128
    q = _pad_bhsd(q, block_q, pad_d)
    k = _pad_bhsd(k, block_k, pad_d)
    v = _pad_bhsd(v, block_k, pad_d)
    dp = d + pad_d

    grid = (bh, sp_q // block_q, sp_k // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=s_q, seq_k=s_k, classes=classes,
        # a causal row of tiles one tile long: every band meets all its
        # keys at once (a non-causal call keeps the kernel it had). On a
        # v5e the running-state form runs the same bands 1.44 times as long
        # at S 1024, d 64 (1.20 at S 2048): 4.6 % of a GPT-2 345M step
        one_pass=causal and sp_k == block_k)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp_q, dp), q.dtype),
            jax.ShapeDtypeStruct(_stats_shape(bh, sp_q), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        compiler_params=_compiler_params(causal, sp_q, sp_k, block_q,
                                         block_k),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out[:, :s_q, :d], lse


def _flash_bwd_bhsd(q, k, v, out, lse, do, *, causal, scale, block_q,
                    block_k):
    """FA2 backward. All of q/k/v/out/do: (BH, S, d); lse: (BH, 1,
    Sq_pad_fwd), as the forward gives it. Returns (dq, dk, dv) unpadded."""
    return _bwd_call(q, k, v, out, lse, do, causal=causal,
                     scale=float(scale), block_q=block_q, block_k=block_k,
                     band=SUB_BLOCK, interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, out, lse, do, *, causal, scale, block_q, block_k,
              band, interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    _log_plan("bwd", s_q, s_k, causal, block_q, block_k)
    classes = _grid_classes(s_q, s_k, causal, block_q, block_k, band)
    block_q, block_k, sp_q, sp_k = _geometry(s_q, s_k, block_q, block_k,
                                             band if causal else None)
    pad_d = (-d) % 128

    # Δ = rowsum(dO ∘ O): one fused XLA reduction, fp32, a row like lse
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(_stats_shape(bh, s_q))

    q = _pad_bhsd(q, block_q, pad_d)
    do = _pad_bhsd(do, block_q, pad_d)
    k = _pad_bhsd(k, block_k, pad_d)
    v = _pad_bhsd(v, block_k, pad_d)
    dp = d + pad_d
    if lse.shape[2] < sp_q:     # fwd may have tiled with a different block
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, sp_q - lse.shape[2])))
    elif lse.shape[2] > sp_q:
        lse = lse[..., :sp_q]
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, sp_q - s_q)))

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              seq_q=s_q, seq_k=s_k, classes=classes)
    q_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        out_shape=jax.ShapeDtypeStruct((bh, sp_q, dp), q.dtype),
        grid=(bh, sp_q // block_q, sp_k // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
            q_spec, row_spec, row_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        compiler_params=_compiler_params(causal, sp_q, sp_k, block_q,
                                         block_k),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: kv outer, q inner
    qi_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, j, 0))
    rowi_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, j))
    kv_spec = pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        out_shape=[jax.ShapeDtypeStruct((bh, sp_k, dp), k.dtype),
                   jax.ShapeDtypeStruct((bh, sp_k, dp), v.dtype)],
        grid=(bh, sp_k // block_k, sp_q // block_q),
        in_specs=[qi_spec, kv_spec, kv_spec, qi_spec, rowi_spec, rowi_spec],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        compiler_params=_compiler_params(causal, sp_q, sp_k, block_q,
                                         block_k),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return (dq[:, :s_q, :d], dk[:, :s_k, :d], dv[:, :s_k, :d])


def _bshd_to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _bhsd_to_bshd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, scale, block_q, block_k):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def _resolve_blocks(kind, block_q, block_k, q, k, causal, scale):
    """Caller-pinned tiles win; unset ones come from the autotuner. The
    vjp rules run while the model's step is being TRACED, so the probe
    is built and run in an eval context: it must execute on the chip,
    not be staged into the caller's program (where its timings would be
    trace time)."""
    if block_q is not None and block_k is not None:
        return block_q, block_k
    b, s, h, d = q.shape
    with jax.core.eval_context():
        tq, tk = _tuned_blocks(kind, b * h, s, k.shape[1], d, q.dtype,
                               causal, scale)
    return (tq if block_q is None else block_q,
            tk if block_k is None else block_k)


class _Kept(threading.local):
    """This thread's list of what the forward rules named, while a
    ``kept_residuals()`` block is open."""
    log = None


_kept = _Kept()


@contextlib.contextmanager
def kept_residuals():
    """``[(name, shape, dtype), ...]`` of the residuals the forward rules
    named while the body ran (``remat_block`` reports them)."""
    outer, _kept.log = _kept.log, []
    try:
        yield _kept.log
    finally:
        _kept.log = outer


def _keep(x, name):
    if _kept.log is not None:
        _kept.log.append((name, x.shape, x.dtype))
    return checkpoint_name(x, name)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    """(out (B, S, H, d), lse (BH, 1, S_padded)) as the kernel gives them."""
    b, s, h, d = q.shape
    block_q, block_k = _resolve_blocks("fwd", block_q, block_k, q, k,
                                       causal, scale)
    _stamp_plan("fwd", s, k.shape[1], causal, block_q, block_k)
    out, lse = _flash_fwd_bhsd(
        _bshd_to_bhsd(q), _bshd_to_bhsd(k), _bshd_to_bhsd(v),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k)
    return _bhsd_to_bshd(out, b, h), lse


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k):
    out_bshd, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    out_bshd = _keep(out_bshd, KEPT_RESIDUALS[0])
    # the kernel's rows as they come: kept, and read by the backward
    # kernels, with no pass of XLA's over them
    lse = _keep(lse, KEPT_RESIDUALS[1])
    return out_bshd, (q, k, v, out_bshd, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    block_q, block_k = _resolve_blocks("bwd", block_q, block_k, q, k,
                                       causal, scale)
    _stamp_plan("bwd", s, k.shape[1], causal, block_q, block_k)
    dq, dk, dv = _flash_bwd_bhsd(
        _bshd_to_bhsd(q), _bshd_to_bhsd(k), _bshd_to_bhsd(v),
        _bshd_to_bhsd(out), lse, _bshd_to_bhsd(g),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k)
    return (_bhsd_to_bshd(dq, b, h), _bhsd_to_bshd(dk, b, h),
            _bhsd_to_bshd(dv, b, h))


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None):
    """Public entry: q/k/v (batch, seq, heads, head_dim). ``block_q`` /
    ``block_k`` tune the tile sizes (unset: the autotuner's pick on a TPU,
    else DEFAULT_BLOCK_Q/K, forward and backward)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_attention(q, k, v, causal, scale, block_q, block_k)
