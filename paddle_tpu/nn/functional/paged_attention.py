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

Both sit under the named scope ``paged_attention``. ``log_paths`` collects
which one each call inside it was lowered to.

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

__all__ = ["block_multihead_attention"]


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def attention_path(q_shape, q_dtype, cache_shape, cache_dtype,
                   has_new=True, quantized=False) -> str:
    """``"kernel"`` or ``"composite"``: which implementation a call of
    these shapes and dtypes is traced to on this backend (the module
    docstring has the rule)."""
    if not has_new or quantized or not flags.get_flag("use_pallas_kernels"):
        return "composite"
    # Pallas is imported where a call first needs it, not with the package
    from ...ops.pallas import paged_attention as kernel
    if not (kernel.INTERPRET or jax.default_backend() == "tpu"):
        return "composite"
    ok = kernel.supports(q_shape, q_dtype, cache_shape, cache_dtype)
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


def _gather_attend(qa, kca, vca, bta, sla, ksa, vsa, causal, sc):
    """The composite: per sequence, gather the blocks of its table into a
    contiguous view and attend in float32 under a length mask."""
    B, T, H, D = qa.shape
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
            v = vca[blocks].reshape(s_max, KVH, D)
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
        return o.reshape(T, H, D).astype(qb.dtype)

    return jax.vmap(per_seq)(bta, sla, qa)


def _composite_decode_attend(qa, kca, vca, bta, sla, sc):
    # T = 1: the query's own slot is the last one its length admits, so the
    # length mask is the causal mask
    return _gather_attend(qa, kca, vca, bta, sla, None, None, True, sc)


# The kernel path is a primitive of its own so that the choice the trace
# cannot make is made by its lowering rule, which sees the devices.
_decode_attend_p = Primitive("paged_decode_attend")
_decode_attend_p.def_abstract_eval(
    lambda q, *_pools_table_lens, scale: jax.core.ShapedArray(q.shape,
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


def _lower_decode_attend(ctx, qa, kca, vca, bta, sla, *, scale):
    if _compiler_partitions(ctx.module_context.axis_context):
        _log_path("composite")
        attend = functools.partial(_composite_decode_attend, sc=scale)
    else:
        _log_path("kernel")
        # Pallas is imported where a call first needs it, not with the package
        from ...ops.pallas.paged_attention import paged_decode_attention
        attend = functools.partial(paged_decode_attention, scale=scale)
    return mlir.lower_fun(attend, multiple_results=False)(
        ctx, qa, kca, vca, bta, sla)


mlir.register_lowering(_decode_attend_p, _lower_decode_attend)


@functools.partial(jax.custom_jvp, nondiff_argnums=(5,))
def _decode_attend(qa, kca, vca, bta, sla, sc):
    """The T = 1 write-path attention of float pages: the kernel where the
    program is compiled whole, the composite where it is partitioned or
    differentiated."""
    return _decode_attend_p.bind(qa, kca, vca, bta, sla, scale=sc)


@_decode_attend.defjvp
def _decode_attend_jvp(sc, primals, tangents):
    *_, bta, sla = primals
    return jax.jvp(
        lambda q, k, v: _composite_decode_attend(q, k, v, bta, sla, sc),
        primals[:3], tangents[:3])


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
        # flat slot of new token t of seq b: pos = len - T + t. Rows
        # with seq_len < T (padded batch rows) would yield negative
        # positions that WRAP into live blocks — drop those writes.
        pos = sla_i[:, None] - T + jnp.arange(T)[None, :]     # (B, T)
        ok = pos >= 0
        blk = jnp.take_along_axis(bta_i, jnp.maximum(pos, 0) // bs,
                                  axis=1)                     # (B, T)
        blk = jnp.where(ok, blk, nb)  # out-of-range -> mode="drop"
        off = jnp.maximum(pos, 0) % bs
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
