"""Paged (block-table) KV-cache attention for serving.

Reference: block_multi_head_attention
(phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu, exposed via
python/paddle/incubate/nn/functional/block_multihead_attention.py) — the
vLLM-style paged KV cache: the KV history of each sequence lives in
fixed-size physical blocks referenced through a per-sequence block table,
so sequences grow without reallocating or compacting.

TPU-native design: the cache is one (num_blocks, block_size, KVH, D) array
per K/V; a step is (1) scatter the step's new KV into physical slots
computed from the block table (one `.at[].set` with batched indices), then
(2) attend over each sequence's pages. GQA/MQA supported (H a multiple of
KVH). What runs step (2) follows from the call's own shapes, dtypes and
backend (``attention_path``), never from a flag a caller sets:

* **the kernel** — ``ops.pallas.paged_attention.paged_decode_attention``
  (``paged_decode_attn`` on a device trace): the serving decode step, one
  new token a sequence (T = 1) written by this call (``new_k`` / ``new_v``
  given), float pages of the queries' dtype, D a multiple of 128 and pages
  that fill whole sublane tiles, on a TPU. It copies in the pages a
  sequence holds and no others.
* **the composite** — everything else: T > 1 (chunked prefill, speculative
  verify), int8 pages, read-only attention (``new_k=None``, the path
  trainers differentiate through), other shapes, other backends. Per
  sequence it gathers the blocks of its table into a contiguous
  (S_max, KVH, D) view and runs masked attention in float32 — gathers + one
  MXU einsum, all static shapes, fully jittable.
* **the blockwise composite** — the composite's write path (``new_k``
  given, float pages) where a table spans more than ``BLOCKWISE_FROM``
  tokens, so that the scores over a whole table, (T, H, S_max) in float32,
  would be hundreds of megabytes a call (1.2 GB for a 256-token chunk of 64
  heads over 18 432 positions): the same masked attention taken over groups
  of pages with an online softmax, up to the sequence's own length and no
  further. Nothing holds scores wider than a group; a chunk early in a
  long context costs what its context asks, not what the table could hold.

**Latent pages** (``latent_paged_attention``): a latent-attention (MLA)
layer caches ONE row a token, ``[c | k_r]``, in one pool ``(num_blocks,
block_size, D)`` under the same block table. The absorbed form reads a row
as the key of its token (all D columns, under every query head) and as its
value (the first ``value_dim`` columns): multi-query attention with one K/V
head whose values are a slice of its keys. The same three implementations
serve it with the pool in both roles: the kernel for T = 1 (a latent row
leaves HBM once a step), the blockwise composite for a write over a long
table (a chunk of queries against the lane's own pages, a group at a time)
and the gathered composite otherwise. The probabilities meet the values as
ONE bfloat16 number there (relative rounding 2^-9 a probability, averaged
over the keys of a row: far below what rounding ``q_lat`` and ``o_lat`` to
bfloat16 already costs the absorbed form), where the K/V composite splits
them in two: the PV product over a 32 k prefix is most of a chunk's time.

Beside the paged layers, **the window path** (``window_ring_attention``):
sliding-window layers keep no pages. A sequence holds a fixed ring of K/V
rows, the window plus one chunk, written round-robin by position; a query
at position i sees key j iff ``0 <= i - j < window``. Plain XLA over the
ring's rows, which do not grow with the sequence.

Two things a trace cannot see are settled where they can be seen. *How many
devices the program is compiled for* is known when it is lowered: GSPMD
cannot partition a Mosaic kernel, so a program laid out over several
devices (a caller's ``jax.jit`` over sharded pools, a mesh) lowers the
composite there, which the compiler partitions like any other XLA code,
and one device, or a ``shard_map`` region that is manual over every axis,
lowers the kernel (``_lower_decode_attend``). *Whether the call is
differentiated* is known to JAX: the kernel path's derivative rule is the
composite's (``jax.custom_jvp``), so ``jax.vjp`` / ``jax.grad`` through a
call run the composite, value and gradient.

All sit under the named scope ``paged_attention``. ``log_paths`` collects
which of kernel and composite each paged call inside it was lowered to.

int8 page pool (the serving tier's ``kv_dtype="int8"`` knob): pass int8
caches plus sidecar per-(position, head) scale arrays ``k_scale`` /
``v_scale`` of shape (num_blocks, block_size, KVH). New KV is quantized
symmetric-abs-max over the head dim on write (``ops.pallas.serving``),
and the gather dequantizes into the attention math's fp32 accumulation —
the same payload-int8 / sidecar-scales / dequant-at-consumer pattern as
``nn.quant.weight_only_linear``, applied to KV pages. Resident KV shrinks
~2x vs bf16 pages, which is resident-batch headroom on a serving chip.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import mlir

from ...core import dispatch, flags
from ...core.tensor import Tensor, as_tensor
from ...ops.pallas.serving import kv_dequantize_int8, kv_quantize_int8

__all__ = ["block_multihead_attention", "latent_paged_attention",
           "window_ring_attention"]

#: tokens a block table must span before the composite's write path is taken
#: blockwise (the module docstring has why); the serving configurations up
#: to a context of 4096 keep the program they had
BLOCKWISE_FROM = 4096

#: tokens of K/V one step of the blockwise composite attends over: wide
#: enough that a step's products hide the loop's overhead, and the float32
#: scores of a 256-token chunk of 64 heads stay at 64 MiB
BLOCKWISE_GROUP_TOKENS = 1024


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def attention_path(q_shape, q_dtype, cache_shape, cache_dtype,
                   has_new=True, quantized=False, value_dim=None) -> str:
    """``"kernel"`` or ``"composite"``: which implementation a call of
    these shapes and dtypes is traced to on this backend (the module
    docstring has the rule). ``value_dim``: the cache is one pool of latent
    pages."""
    if not has_new or quantized or not flags.get_flag("use_pallas_kernels"):
        return "composite"
    # Pallas is imported where a call first needs it, not with the package
    from ...ops.pallas import paged_attention as kernel
    if not (kernel.INTERPRET or jax.default_backend() == "tpu"):
        return "composite"
    ok = kernel.supports(q_shape, q_dtype, cache_shape, cache_dtype,
                         value_dim)
    return "kernel" if ok else "composite"


_path_log = contextvars.ContextVar("paged_attention_paths", default=None)


@contextlib.contextmanager
def log_paths():
    """A list that gains ``"kernel"`` or ``"composite"`` for every
    ``block_multihead_attention`` call that is traced and lowered inside the
    block: what the program then holds. A program compiled earlier adds
    nothing when it is called again. ``PagedEngine`` fills its health gauge
    from it."""
    seen = []
    token = _path_log.set(seen)
    try:
        yield seen
    finally:
        _path_log.reset(token)


def _log_path(path):
    seen = _path_log.get()
    if seen is not None:
        seen.append(path)


def _gather_attend(qa, kca, vca, bta, sla, ksa, vsa, causal, sc, dv=None):
    """The composite: per sequence, gather the blocks of its table into a
    contiguous view and attend in float32 under a length mask. ``vca`` None:
    ``kca`` is the one pool of latent pages, (num_blocks, block_size, D),
    one K/V head whose rows are keys and, in their first ``dv`` columns,
    values."""
    B, T, H, D = qa.shape
    if vca is None:
        kca = kca[:, :, None, :]
    else:
        dv = D
    _nb, bs, KVH, _ = kca.shape
    s_max = bta.shape[1] * bs
    group = H // KVH

    def per_seq(blocks, length, qb):
        # gather this sequence's pages -> (s_max, KVH, D)
        if ksa is not None:
            k = kv_dequantize_int8(kca[blocks], ksa[blocks])
            v = kv_dequantize_int8(vca[blocks], vsa[blocks])
            k = k.reshape(s_max, KVH, D)
            v = v.reshape(s_max, KVH, D)
        else:
            k = kca[blocks].reshape(s_max, KVH, D)
            v = (vca[blocks].reshape(s_max, KVH, D) if vca is not None
                 else k[..., :dv])
        qg = qb.reshape(T, KVH, group, D)
        s = jnp.einsum("tkgd,skd->tkgs", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * sc
        jpos = jnp.arange(s_max)[None, None, None, :]
        qpos = (length - T + jnp.arange(T)).reshape(T, 1, 1, 1)
        mask = jpos < length
        if causal:
            mask = jpos <= qpos
        # -1e30 (not -inf) + explicit zeroing of fully-masked rows:
        # a padded row (length <= 0) must yield 0, not NaN
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("tkgs,skd->tkgd", p, v.astype(jnp.float32))
        any_valid = mask.any(axis=-1, keepdims=True)
        o = jnp.where(any_valid, o, 0.0)
        return o.reshape(T, H, dv).astype(qb.dtype)

    return jax.vmap(per_seq)(bta, sla, qa)


def _weighted_values(eq, p, v, split=True):
    """``einsum(eq, p, v)`` with float32 probabilities ``p`` and float32
    accumulation. Values narrower than float32 meet the probabilities as
    two halves of the values' dtype (``p = hi + lo``, 16 bits of mantissa
    for bfloat16), as the decode kernel does, or with ``split`` off as one
    number of it; float32 values are multiplied at full precision."""
    if v.dtype == jnp.float32:
        return jnp.einsum(eq, p, v, precision=jax.lax.Precision.HIGHEST)
    hi = p.astype(v.dtype)
    if not split:
        return jnp.einsum(eq, hi, v, preferred_element_type=jnp.float32)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    return (jnp.einsum(eq, hi, v, preferred_element_type=jnp.float32)
            + jnp.einsum(eq, lo, v, preferred_element_type=jnp.float32))


def _scores(eq, q, k):
    """``einsum(eq, q, k)`` in float32: a product of two bfloat16 numbers is
    exact there; float32 operands are multiplied at full precision."""
    exact = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    return jnp.einsum(eq, q, k, preferred_element_type=jnp.float32,
                      precision=exact)


def _blockwise_rows(qa, kca, vca, bta, sla, causal, sc, dv=None):
    B, T, H, D = qa.shape
    shared = vca is None        # latent pages: a row is key and value
    if shared:
        kca = kca[:, :, None, :]
    else:
        dv = D
    _nb, bs, KVH, _ = kca.shape
    group = H // KVH
    pages = max(1, BLOCKWISE_GROUP_TOKENS // bs)
    span = pages * bs                       # tokens a step attends over
    steps = -(-bta.shape[1] // pages)
    bta = jnp.pad(bta, ((0, 0), (0, steps * pages - bta.shape[1])))

    def per_seq(blocks, length, qb):
        qg = qb.reshape(T, KVH, group, D)
        qpos = length - T + jnp.arange(T)

        def step(g, carry):
            m, l, acc = carry
            ids = jax.lax.dynamic_slice_in_dim(blocks, g * pages, pages)
            k = kca[ids].reshape(span, KVH, D)
            v = k[..., :dv] if shared else vca[ids].reshape(span, KVH, D)
            s = _scores("tkgd,skd->tkgs", qg, k) * sc
            jpos = g * span + jnp.arange(span)
            seen = jpos[None, :] < length
            if causal:
                seen = jpos[None, :] <= qpos[:, None]
            seen = jnp.broadcast_to(seen, (T, span))[:, None, None, :]
            s = jnp.where(seen, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            # a row with nothing seen yet has m_new = -1e30: exp(0) is not 0
            p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = alpha[..., None] * acc + _weighted_values(
                "tkgs,skd->tkgd", p, v, split=not shared)
            return m_new, l, acc

        init = (jnp.full((T, KVH, group), -1e30, jnp.float32),
                jnp.zeros((T, KVH, group), jnp.float32),
                jnp.zeros((T, KVH, group, dv), jnp.float32))
        _m, l, acc = jax.lax.fori_loop(
            0, jnp.clip(-(-length // span), 0, steps), step, init)
        # a padded row (nothing seen) yields 0, not NaN
        o = jnp.where(l[..., None] > 0,
                      acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
        return o.reshape(T, H, dv).astype(qb.dtype)

    return jax.vmap(per_seq)(bta, sla, qa)


@functools.partial(jax.custom_jvp, nondiff_argnums=(5, 6, 7))
def _blockwise_attend(qa, kca, vca, bta, sla, causal, sc, dv=None):
    """The composite over page groups with an online softmax: per sequence
    a loop over ``ceil(length / BLOCKWISE_GROUP_TOKENS)`` groups of its
    table, each gathered, scored and folded into a running (max, sum,
    weighted values). ``vca`` None: ``kca`` is the one pool of latent pages
    (``dv`` columns of a row are its value). Differentiated, it is the
    composite (a loop whose trip count is data has no reverse rule)."""
    return _blockwise_rows(qa, kca, vca, bta, sla, causal, sc, dv)


def _jvp_as_composite(primals, tangents, causal, sc, dv):
    """The derivative rule of both write paths: the gathered composite's,
    in the queries and the pool(s) (``vca`` None: one pool)."""
    qa, kca, vca, bta, sla = primals
    n = 2 if vca is None else 3
    return jax.jvp(
        lambda q, k, v=None: _gather_attend(q, k, v, bta, sla, None, None,
                                            causal, sc, dv),
        primals[:n], tangents[:n])


@_blockwise_attend.defjvp
def _blockwise_attend_jvp(causal, sc, dv, primals, tangents):
    return _jvp_as_composite(primals, tangents, causal, sc, dv)


def _composite_decode_attend(qa, *pools_table_lens, sc, dv=None):
    # T = 1: the query's own slot is the last one its length admits, so the
    # length mask is the causal mask
    *pools, bta, sla = pools_table_lens
    kca, vca = pools if len(pools) == 2 else (pools[0], None)
    return _gather_attend(qa, kca, vca, bta, sla, None, None, True, sc, dv)


# The kernel path is a primitive of its own so that the choice the trace
# cannot make is made by its lowering rule, which sees the devices.
_decode_attend_p = Primitive("paged_decode_attend")
_decode_attend_p.def_abstract_eval(
    lambda q, *_pools_table_lens, scale, value_dim: jax.core.ShapedArray(
        q.shape if value_dim is None else q.shape[:-1] + (value_dim,),
        q.dtype))


def _compiler_partitions(axis_context) -> bool:
    """Whether a call lowered in this context is left to GSPMD to partition:
    the test Mosaic's own lowering makes before it refuses a kernel."""
    mesh = getattr(axis_context, "mesh", None)
    if mesh is None:        # jit: the devices the program is compiled for
        return getattr(axis_context, "num_devices", 1) != 1
    # inside shard_map: whole only if manual over every axis of the mesh
    manual = set(axis_context.manual_axes) | set(mesh.manual_axes)
    return bool(axis_context.manual_axes) and manual != set(mesh.axis_names)


def _lower_decode_attend(ctx, qa, *rest, scale, value_dim):
    if _compiler_partitions(ctx.module_context.axis_context):
        _log_path("composite")
        attend = functools.partial(_composite_decode_attend, sc=scale,
                                   dv=value_dim)
    else:
        _log_path("kernel")
        # Pallas is imported where a call first needs it, not with the package
        from ...ops.pallas.paged_attention import paged_decode_attention

        def attend(q, *pools_table_lens):
            *pools, bta, sla = pools_table_lens
            if value_dim is None:
                return paged_decode_attention(q, *pools, bta, sla,
                                              scale=scale)
            return paged_decode_attention(q, pools[0], None, bta, sla,
                                          scale=scale, value_dim=value_dim)
    return mlir.lower_fun(attend, multiple_results=False)(ctx, qa, *rest)


mlir.register_lowering(_decode_attend_p, _lower_decode_attend)


@functools.partial(jax.custom_jvp, nondiff_argnums=(5, 6))
def _decode_attend(qa, kca, vca, bta, sla, sc, dv=None):
    """The T = 1 write-path attention of float pages (``vca`` None: ``kca``
    is the one pool of latent pages): the kernel where the program is
    compiled whole, the composite where it is partitioned or
    differentiated."""
    pools = (kca,) if vca is None else (kca, vca)
    return _decode_attend_p.bind(qa, *pools, bta, sla, scale=sc,
                                 value_dim=dv)


@_decode_attend.defjvp
def _decode_attend_jvp(sc, dv, primals, tangents):
    return _jvp_as_composite(primals, tangents, True, sc, dv)


def _new_rows_slots(bta_i, sla_i, T, nb, bs):
    """``(block, offset)`` (B, T) of the T newest positions of each
    sequence: flat slot of new token t of seq b is pos = len - T + t. Rows
    with seq_len < T (padded batch rows) would yield negative positions
    that WRAP into live blocks: they get block ``nb``, which a
    ``mode="drop"`` write leaves out."""
    pos = sla_i[:, None] - T + jnp.arange(T)[None, :]         # (B, T)
    blk = jnp.take_along_axis(bta_i, jnp.maximum(pos, 0) // bs, axis=1)
    return jnp.where(pos >= 0, blk, nb), jnp.maximum(pos, 0) % bs


# jitted so that the N attention layers of a serving program trace and lower
# this step once and call it N times: traced a layer, it was most of the
# seconds a process spends tracing its prefill and decode programs
@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "use_kernel"))
def _write_and_attend(qa, kca, vca, bta, sla, new, scales, *, causal, scale,
                      use_kernel):
    """One ``block_multihead_attention`` call on arrays: write ``new`` =
    (K, V) where given, then attend through the kernel or the composite
    (int8 pages carry ``scales`` = (K, V) sidecars)."""
    B, T, H, D = qa.shape
    nb, bs, KVH, _ = kca.shape
    if H % KVH:
        raise ValueError(f"H={H} not a multiple of KVH={KVH}")
    sla_i = sla.astype(jnp.int32)
    bta_i = bta.astype(jnp.int32)
    quantized = scales is not None
    ksa, vsa = scales if quantized else (None, None)

    if new is not None:
        nk, nv = new
        blk, off = _new_rows_slots(bta_i, sla_i, T, nb, bs)
        if quantized:
            qk, sk = kv_quantize_int8(nk)
            qv, sv = kv_quantize_int8(nv)
            kca = kca.at[blk, off].set(qk, mode="drop")
            vca = vca.at[blk, off].set(qv, mode="drop")
            ksa = ksa.at[blk, off].set(sk, mode="drop")
            vsa = vsa.at[blk, off].set(sv, mode="drop")
        else:
            kca = kca.at[blk, off].set(nk, mode="drop")
            vca = vca.at[blk, off].set(nv, mode="drop")

    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    if use_kernel:
        out = _decode_attend(qa, kca, vca, bta_i, sla_i, sc)
    elif (new is not None and not quantized
          and bta.shape[1] * bs > BLOCKWISE_FROM):
        out = _blockwise_attend(qa, kca, vca, bta_i, sla_i, causal, sc)
    else:
        out = _gather_attend(qa, kca, vca, bta_i, sla_i, ksa, vsa, causal,
                             sc)
    if quantized:
        return out, kca, vca, ksa, vsa
    return out, kca, vca


def block_multihead_attention(q, key_cache, value_cache, block_tables,
                              seq_lens, new_k=None, new_v=None, causal=True,
                              scale=None, k_scale=None, v_scale=None,
                              name=None):
    """Attend over paged KV history (+ optionally append this step's KV).

    Args:
      q: (B, T, H, D) queries for the T newest positions of each sequence
         (T=1 decode; T>1 chunked prefill / speculative verify).
      key_cache / value_cache: (num_blocks, block_size, KVH, D). Float
         pages, or int8 pages when ``k_scale``/``v_scale`` are given.
      block_tables: (B, max_blocks_per_seq) int32 physical block ids;
         entries beyond a sequence's allocation may be any valid id (they
         are masked by seq_lens).
      seq_lens: (B,) int32 sequence lengths INCLUDING the new T tokens.
      new_k / new_v: (B, T, KVH, D) — written into the caches at positions
         [len-T, len) before attending. Omit for read-only attention.
      causal: within the T new positions, query t sees history up to and
         including its own slot.
      k_scale / v_scale: (num_blocks, block_size, KVH) float32 sidecar
         scales for int8 caches. New KV is quantized on write; the
         per-sequence gather dequantizes.

    Returns (out (B, T, H, D), key_cache, value_cache) — plus the updated
    (k_scale, v_scale) appended when int8 caches are used. Caches update
    functionally (donate them in a jitted serving step for in-place
    reuse).
    """
    q, kc, vc = _t(q), _t(key_cache), _t(value_cache)
    bt, sl = _t(block_tables), _t(seq_lens)
    tensors = [q, kc, vc, bt, sl]
    has_new = new_k is not None
    if has_new:
        new_k, new_v = _t(new_k), _t(new_v)
        tensors += [new_k, new_v]
    quantized = k_scale is not None
    if quantized:
        if v_scale is None:
            raise ValueError("int8 KV cache needs both k_scale and v_scale")
        ks_t, vs_t = _t(k_scale), _t(v_scale)
        tensors += [ks_t, vs_t]

    # the jitted step keeps a trace per value of ``use_kernel``
    use_kernel = attention_path(
        q._data.shape, q._data.dtype, kc._data.shape, kc._data.dtype,
        has_new, quantized) == "kernel"
    if not use_kernel:
        _log_path("composite")  # the kernel path logs where it is lowered

    def f(qa, kca, vca, bta, sla, *rest):
        # the scope of both implementations in a trace: cache write, then
        # the kernel, or page gather, scores, softmax, values
        with jax.named_scope("paged_attention"):
            return _write_and_attend(
                qa, kca, vca, bta, sla, tuple(rest[:2]) if has_new else None,
                tuple(rest[-2:]) if quantized else None, causal=bool(causal),
                scale=None if scale is None else float(scale),
                use_kernel=use_kernel)

    # int8 caches/scales are not differentiable surfaces (round/clip);
    # the float path keeps its original cache lineage for trainers that
    # backprop through read-only paged attention.
    mask = ([True] + [not quantized] * 2 + [False, False]
            + [True, True] * has_new + [False, False] * quantized)
    return dispatch.call("block_multihead_attention", f, tensors,
                         differentiable_mask=mask)


@functools.partial(jax.jit,
                   static_argnames=("value_dim", "scale", "use_kernel"))
def _latent_write_and_attend(qa, pool, bta, sla, new, *, value_dim, scale,
                             use_kernel):
    """One ``latent_paged_attention`` call on arrays: write the chunk's
    rows, then attend with the pool as keys and values."""
    B, T, H, D = qa.shape
    nb, bs, _ = pool.shape
    sla_i, bta_i = sla.astype(jnp.int32), bta.astype(jnp.int32)
    blk, off = _new_rows_slots(bta_i, sla_i, T, nb, bs)
    pool = pool.at[blk, off].set(new.astype(pool.dtype), mode="drop")
    if use_kernel:
        out = _decode_attend(qa, pool, None, bta_i, sla_i, scale, value_dim)
    elif bta.shape[1] * bs > BLOCKWISE_FROM:
        out = _blockwise_attend(qa, pool, None, bta_i, sla_i, True, scale,
                                value_dim)
    else:
        out = _gather_attend(qa, pool, None, bta_i, sla_i, None, None, True,
                             scale, value_dim)
    return out, pool


def latent_paged_attention(q, pages, block_tables, seq_lens, new_rows,
                           value_dim, scale, name=None):
    """Write-and-attend over latent (MLA) pages in the absorbed form:
    multi-query attention with ONE K/V head whose keys are the cached rows
    and whose values are their first ``value_dim`` columns.

    Args:
      q: (B, T, H, D) absorbed queries ``[W_uk^T q_nope | q_rope]`` for the
         T newest positions of each sequence.
      pages: (num_blocks, block_size, D) rows ``[c | k_r]`` (zeros past
         them where the row is padded to whole lane tiles; ``q`` carries
         zeros there too).
      block_tables / seq_lens: as ``block_multihead_attention``.
      new_rows: (B, T, D), written at positions [len-T, len) before
         attending; query t sees the history up to and including its own
         row.
      value_dim: columns of a row that are its value (``kv_lora_rank``).
      scale: the softmax scale (of the UN-absorbed head: ``qk_head_dim **
         -0.5``; it does not follow from D).

    Returns (out (B, T, H, value_dim), pages). The pool updates functionally
    (donate it in a jitted serving step)."""
    q, pool = _t(q), _t(pages)
    tensors = [q, pool, _t(block_tables), _t(seq_lens), _t(new_rows)]
    use_kernel = attention_path(
        q._data.shape, q._data.dtype, pool._data.shape, pool._data.dtype,
        value_dim=int(value_dim)) == "kernel"
    if not use_kernel:
        _log_path("composite")  # the kernel path logs where it is lowered

    def f(qa, pa, bta, sla, new):
        with jax.named_scope("paged_attention"):
            return _latent_write_and_attend(
                qa, pa, bta, sla, new, value_dim=int(value_dim),
                scale=float(scale), use_kernel=use_kernel)

    return dispatch.call("latent_paged_attention", f, tensors,
                         differentiable_mask=[True, True, False, False,
                                              True])


@functools.partial(jax.jit, static_argnames=("window", "scale"))
def _ring_write_and_attend(qa, kra, vra, sla, nk, nv, *, window, scale):
    """One ``window_ring_attention`` call on arrays: write the chunk's K/V
    into the rows their positions map to, then attend over the ring under
    the window mask."""
    B, T, H, D = qa.shape
    _b, R, KVH, _ = kra.shape
    if H % KVH:
        raise ValueError(f"H={H} not a multiple of KVH={KVH}")
    if R < window + T - 1:
        raise ValueError(
            f"a ring of {R} rows cannot hold a window of {window} beside a "
            f"chunk of {T}: the chunk's last row would overwrite a row its "
            f"first query still sees")
    sla_i = sla.astype(jnp.int32)
    pos = sla_i[:, None] - T + jnp.arange(T)[None, :]         # (B, T)
    # position p lives in row p % R; a negative position (left padding,
    # the seq = 0 sentinel) writes nothing: row R is out of range
    row = jnp.where(pos >= 0, pos % R, R)
    lane = jnp.arange(B)[:, None]
    kra = kra.at[lane, row].set(nk.astype(kra.dtype), mode="drop")
    vra = vra.at[lane, row].set(nv.astype(vra.dtype), mode="drop")
    # the position each row holds once the chunk is written: the newest
    # p <= last with p % R == r; negative: nothing of this sequence yet
    last = sla_i[:, None] - 1
    held = last - (last - jnp.arange(R)[None, :]) % R          # (B, R)
    back = pos[:, :, None] - held[:, None, :]                  # (B, T, R)
    seen = ((held[:, None, :] >= 0) & (pos[:, :, None] >= 0)
            & (back >= 0) & (back < window))[:, :, None, None, :]
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    qg = qa.reshape(B, T, KVH, H // KVH, D)
    s = _scores("btkgd,brkd->btkgr", qg, kra) * sc
    s = jnp.where(seen, s, -1e30)
    p = jnp.where(seen, jax.nn.softmax(s, axis=-1), 0.0)
    o = _weighted_values("btkgr,brkd->btkgd", p, vra)
    return o.reshape(B, T, H, D).astype(qa.dtype), kra, vra


def window_ring_attention(q, key_rows, value_rows, seq_lens, new_k, new_v,
                          window, scale=None, name=None):
    """Sliding-window attention over a per-sequence ring of K/V rows.

    Args:
      q: (B, T, H, D) queries for the T newest positions of each sequence.
      key_rows / value_rows: (B, R, KVH, D), ``R >= window + T - 1``: the
         rows sequence b holds; position p lives in row ``p % R``.
      seq_lens: (B,) int32 sequence lengths INCLUDING the new T tokens. A
         row of the chunk at a negative position (left padding; every row
         of a ``seq_len <= 0`` lane) writes nothing and yields zeros.
      new_k / new_v: (B, T, KVH, D), written before attending.
      window: query i sees key j iff ``0 <= i - j < window``.

    A row holds something of this sequence only if the sequence wrote it:
    the position a row is read as follows from ``seq_lens`` alone, so what
    an earlier occupant of the rows left behind is never seen and the rows
    need no clearing between sequences.

    Returns (out (B, T, H, D), key_rows, value_rows); the rows update
    functionally (donate them in a jitted serving step)."""
    tensors = [_t(q), _t(key_rows), _t(value_rows), _t(seq_lens),
               _t(new_k), _t(new_v)]

    def f(qa, kra, vra, sla, nk, nv):
        # under the paged layers' scope as well: the share of a serving
        # program that is attention over cached K/V reads one scope
        with jax.named_scope("paged_attention"):
            return _ring_write_and_attend(
                qa, kra, vra, sla, nk, nv, window=int(window),
                scale=None if scale is None else float(scale))

    return dispatch.call("window_ring_attention", f, tensors,
                         differentiable_mask=[True, True, True, False,
                                              True, True])
