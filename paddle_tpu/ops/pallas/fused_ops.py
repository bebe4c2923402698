"""Fused Pallas TPU kernels behind the graph-fusion pass.

Siblings to :mod:`.flash_attention`, covering the reference's hot fused
kernels (reference: paddle/phi/kernels/fusion/ — fused_layernorm,
fused_bias_act, fused_rope; 71 entries in fused_ops.yaml). Each kernel
is the *measured* alternative the per-shape autotuner
(:mod:`.autotune`) weighs against the XLA-fused jnp composite — the
composite is always the numerics reference and the portable fallback.

Kernels:

* ``fused_residual_norm`` — residual add + LayerNorm / RMSNorm over the
  last dim in one pass, emitting both the normalized value AND the sum,
  so the residual stream never round-trips HBM between the add and the
  norm.
* ``fused_matmul`` — ``act(norm(x) @ W + b)``: a row-panel matmul whose
  prologue normalizes the activation rows in-register (full K resident
  per tile) and whose epilogue applies bias + GELU/SiLU/ReLU before the
  single output store. One HBM round-trip where the unfused chain makes
  three or four.
* ``fused_matmul_rope`` — QKV-style projection with the rotary
  embedding applied in the epilogue: out tiles are rotated per head
  before the store (positions recovered from the row index), so the
  projected tensor lands in HBM already roped.

All kernels run under the Pallas interpreter (``INTERPRET = True``) so
CPU tests execute the real kernel bodies. Shape gates (`pallas_ok_*`)
keep the kernels on aligned shapes — anything else takes the composite.
Kernel bodies stay inside what Mosaic lowers on a TPU: 2-D tiles, integer
iota, no ``erf``/``erfc`` primitive, lane rotation by ``pltpu.roll``
rather than sub-128-lane slices; ``chip_smoke.py`` compiles every kernel
and tile candidate on the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import import_pallas

pl, pltpu = import_pallas()

#: run kernels through the Pallas interpreter (CPU testing of kernel code)
INTERPRET = False

# Tile candidates for the measured autotuner (ops/pallas/autotune.py) —
# small grids on purpose: each candidate costs one Mosaic compile at
# first sight of a (shape-class, chip) key; winners persist to disk.
NORM_ROW_CANDIDATES = [256, 512, 1024]
MATMUL_TILE_CANDIDATES = [(256, 256), (512, 256), (256, 512), (128, 512),
                          (512, 512)]

DEFAULT_NORM_ROWS = 512
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256

#: VMEM budget a kernel's resident blocks must fit — under Mosaic's
#: 16 MiB scoped-VMEM default with room for its own temporaries
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _fit_rows(block_rows: int, rows: int, d: int, itemsize: int,
              n_blocks: int, n_temps: int) -> int:
    """Row tile for the row-wise kernels: ``block_rows`` clamped to the
    array and to what VMEM holds. Each of the ``n_blocks`` (rows, d)
    operand/result blocks is double-buffered by the pipeline, and the
    body keeps ``n_temps`` block-sized f32 temporaries live (Mosaic
    spills them to scoped VMEM). Measured on v5e: four bf16 (1024, 1024)
    blocks alone are 16 MiB; the exact-GELU body held 24 MiB of
    temporaries over a (256, 4096) tile."""
    cap = _VMEM_BUDGET_BYTES // (d * (2 * n_blocks * itemsize + 4 * n_temps))
    while block_rows > max(cap, 8):
        block_rows //= 2        # halve: the tile keeps dividing the rows
    return max(8, min(block_rows, max(rows, 8)))


def _act_apply(y, act: str):
    """Epilogue activation on the fp32 accumulator (closed vocabulary —
    the fusion pass only rewrites activations listed here). The ONE
    implementation: nn.functional.fused's composites delegate here, so
    kernel and numerics reference share the same vocabulary; the public
    name list is nn.functional.fused.ACTIVATIONS."""
    if act == "gelu":
        return jax.nn.gelu(y, approximate=False)
    if act == "gelu_tanh":
        return jax.nn.gelu(y, approximate=True)
    if act == "silu":
        return jax.nn.silu(y)
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act in ("", "none", None):
        return y
    raise ValueError(f"unknown fused activation {act!r}")


def _erf(x):
    """erf for kernel bodies — Mosaic lowers neither ``erf`` nor ``erfc``.
    Abramowitz & Stegun 7.1.26, |error| <= 1.5e-7 (below f32 rounding of
    the activations it feeds)."""
    a = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * a)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    return jnp.sign(x) * (1.0 - poly * jnp.exp(-a * a))


def _act_kernel(y, act: str):
    """:func:`_act_apply` for kernel bodies: same vocabulary, with exact
    GELU spelled through :func:`_erf`."""
    if act == "gelu":
        return 0.5 * y * (1.0 + _erf(y * 0.7071067811865476))
    return _act_apply(y, act)


def _normalize_rows(x32, w32, b32, kind: str, eps: float):
    """Row-wise LN/RMS in fp32: x32 (R, D), w32/b32 (1, D)."""
    if kind == "rms_norm":
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + eps)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        centered = x32 - mean
        var = jnp.mean(centered * centered, axis=-1, keepdims=True)
        y = centered * jax.lax.rsqrt(var + eps)
    return y * w32 + b32


# --------------------------------------------------------------------------
# fused (residual+)norm
# --------------------------------------------------------------------------
def _norm_kernel(x_ref, res_ref, w_ref, b_ref, y_ref, sum_ref, *, kind,
                 eps):
    x32 = (x_ref[...].astype(jnp.float32)
           + res_ref[...].astype(jnp.float32))
    sum_ref[...] = x32.astype(sum_ref.dtype)
    w32 = w_ref[...].astype(jnp.float32)
    b32 = b_ref[...].astype(jnp.float32)
    y_ref[...] = _normalize_rows(x32, w32, b32, kind, eps).astype(
        y_ref.dtype)


def pallas_ok_norm(rows: int, d: int) -> bool:
    """Aligned shapes only: the norm statistics are exact only when the
    feature dim is fully resident (no padding lanes)."""
    return d % 128 == 0 and rows >= 8 and d * 8 * 4 <= _VMEM_BUDGET_BYTES


def _pad_rows(x, block_r):
    pad = (-x.shape[0]) % block_r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


def fused_residual_norm(x2d, res2d, weight, bias, *, kind="layer_norm",
                        eps=1e-5, block_rows=None):
    """One pass: ``s = x + res; y = norm(s) * w + b`` → ``(y, s)``."""
    r, d = x2d.shape
    block_rows = _fit_rows(int(block_rows or DEFAULT_NORM_ROWS), r, d,
                           x2d.dtype.itemsize, n_blocks=4, n_temps=1)
    xp = _pad_rows(x2d, block_rows)
    sp = _pad_rows(res2d, block_rows)
    rp = xp.shape[0]
    w2 = weight.reshape(1, d)
    b2 = bias.reshape(1, d)
    kernel = functools.partial(_norm_kernel, kind=kind, eps=eps)
    y, s = pl.pallas_call(
        lambda x_ref, res_ref, w_ref, b_ref, y_ref, sum_ref: kernel(
            x_ref, res_ref, w_ref, b_ref, y_ref, sum_ref),
        out_shape=[jax.ShapeDtypeStruct((rp, d), x2d.dtype),
                   jax.ShapeDtypeStruct((rp, d), x2d.dtype)],
        grid=(rp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        interpret=INTERPRET,
        name="fused_residual_norm",
    )(xp, sp, w2, b2)
    return y[:r], s[:r]


# --------------------------------------------------------------------------
# fused bias+act (elementwise epilogue as its own kernel, for graphs whose
# matmul is out of pallas reach — e.g. parallel layers adding bias
# separately after a sharded matmul)
# --------------------------------------------------------------------------
def _bias_act_kernel(x_ref, b_ref, y_ref, *, act):
    y = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = _act_kernel(y, act).astype(y_ref.dtype)


def fused_bias_act(x2d, bias, *, act="gelu", block_rows=None):
    """``act(x + b)`` over (R, D) with b (D,), one VPU pass."""
    r, d = x2d.shape
    block_rows = _fit_rows(int(block_rows or DEFAULT_NORM_ROWS), r, d,
                           x2d.dtype.itemsize, n_blocks=2, n_temps=8)
    xp = _pad_rows(x2d, block_rows)
    rp = xp.shape[0]
    return pl.pallas_call(
        functools.partial(_bias_act_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((rp, d), x2d.dtype),
        grid=(rp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        interpret=INTERPRET,
        name="fused_bias_act",
    )(xp, bias.reshape(1, d))[:r]


# --------------------------------------------------------------------------
# fused (norm→)matmul(→bias→act)
# --------------------------------------------------------------------------
def _matmul_kernel(x_ref, w_ref, b_ref, nw_ref, nb_ref, o_ref, *,
                   norm_kind, act, eps):
    x32 = x_ref[...].astype(jnp.float32)          # (bm, K)
    if norm_kind:
        x32 = _normalize_rows(x32, nw_ref[...].astype(jnp.float32),
                              nb_ref[...].astype(jnp.float32),
                              norm_kind, eps)
    # MXU consumes the input dtype (bf16 stays bf16); accumulate fp32
    acc = jax.lax.dot_general(
        x32.astype(x_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (bm, bn)
    acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[...] = _act_kernel(acc, act).astype(o_ref.dtype)


def pallas_ok_matmul(m: int, k: int, n: int, block_m: int,
                     block_n: int) -> bool:
    """The row-panel kernel keeps full K resident per tile: gate on lane
    alignment and the VMEM footprint of (x panel + w panel + acc)."""
    if k % 128 != 0 or n % block_n != 0:
        return False
    need = 4 * (block_m * k + k * block_n + block_m * block_n)
    return need <= _VMEM_BUDGET_BYTES


def fused_matmul(x2d, w, bias=None, norm_weight=None, norm_bias=None, *,
                 norm_kind="", act="", eps=1e-5, block_m=None,
                 block_n=None):
    """``act(norm(x) @ W + b)`` over x (M, K), W (K, N) in one kernel."""
    m, k = x2d.shape
    n = w.shape[1]
    block_m = int(block_m or DEFAULT_BLOCK_M)
    block_n = int(block_n or DEFAULT_BLOCK_N)
    block_m = max(8, min(block_m, max(m, 8)))
    block_n = min(block_n, n)
    xp = _pad_rows(x2d, block_m)
    mp = xp.shape[0]
    b2 = (bias if bias is not None
          else jnp.zeros((n,), x2d.dtype)).reshape(1, n)
    nw2 = (norm_weight if norm_weight is not None
           else jnp.ones((k,), x2d.dtype)).reshape(1, k)
    nb2 = (norm_bias if norm_bias is not None
           else jnp.zeros((k,), x2d.dtype)).reshape(1, k)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, norm_kind=norm_kind, act=act,
                          eps=eps),
        out_shape=jax.ShapeDtypeStruct((mp, n), x2d.dtype),
        grid=(mp // block_m, n // block_n),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        interpret=INTERPRET,
        name="fused_matmul",
    )(xp, w, b2, nw2, nb2)
    return out[:m]


# --------------------------------------------------------------------------
# fused matmul → rope epilogue (QKV projection that lands already-roped)
# --------------------------------------------------------------------------
def _matmul_rope_kernel(x_ref, w_ref, b_ref, o_ref, *, seq, head_dim,
                        theta, pos_offset, block_m, block_n):
    i = pl.program_id(0)
    x = x_ref[...]                                 # (bm, K)
    acc = jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bm, bn)
    acc = acc + b_ref[...].astype(jnp.float32)
    # Everything stays a 2-D (bm, bn) tile: a tile holds whole heads, so
    # column c sits at channel c % head_dim of its head and pairs with
    # the channel half a head away — fetched by rotating the lanes, not
    # by slicing 32/64-lane halves (which Mosaic does not tile).
    half = head_dim // 2
    # rows are the flattened (batch, seq) axis: position = row % seq
    rows = i * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, 1), 0)
    pos = (rows % seq + pos_offset).astype(jnp.float32)
    chan = jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1) % head_dim
    freqs = jnp.exp((chan % half).astype(jnp.float32)
                    * (-math.log(theta) / half))   # theta ** -(j / half)
    angle = pos * freqs                            # (bm, bn)
    # x1 lanes (first half of a head) rotate with -x2, x2 lanes with +x1;
    # the (1, bn) lane mask is applied as f32 arithmetic so it broadcasts
    # over the rows like any other operand
    first = (chan < half).astype(jnp.float32)
    ahead = pltpu.roll(acc, block_n - half, 1)     # acc[c + half]
    behind = pltpu.roll(acc, half, 1)              # acc[c - half]
    partner = behind - first * (ahead + behind)    # -x2 | +x1
    o_ref[...] = (acc * jnp.cos(angle)
                  + partner * jnp.sin(angle)).astype(o_ref.dtype)


def pallas_ok_matmul_rope(m: int, k: int, n: int, head_dim: int,
                          block_m: int, block_n: int) -> bool:
    """Rope rotation pairs channels within one head: each out tile must
    cover whole heads, and the head dim must split into even halves."""
    return (pallas_ok_matmul(m, k, n, block_m, block_n)
            and head_dim % 2 == 0 and block_n % head_dim == 0)


def fused_matmul_rope(x2d, w, bias=None, *, seq, head_dim,
                      theta=10000.0, pos_offset=0, block_m=None,
                      block_n=None):
    """``rope(reshape(x @ W + b))`` over x (B*S, K): the epilogue
    rotates each head's channel pairs before the single store."""
    m, k = x2d.shape
    n = w.shape[1]
    block_m = int(block_m or DEFAULT_BLOCK_M)
    block_n = int(block_n or DEFAULT_BLOCK_N)
    block_m = max(8, min(block_m, max(m, 8)))
    block_n = min(block_n, n)
    if block_n % head_dim:
        block_n = (block_n // head_dim or 1) * head_dim
    # positions are recovered as row % seq — padded rows would alias
    # position 0..pad, which is harmless (their outputs are sliced off)
    xp = _pad_rows(x2d, block_m)
    mp = xp.shape[0]
    b2 = (bias if bias is not None
          else jnp.zeros((n,), x2d.dtype)).reshape(1, n)
    out = pl.pallas_call(
        functools.partial(_matmul_rope_kernel, seq=int(seq),
                          head_dim=int(head_dim), theta=float(theta),
                          pos_offset=int(pos_offset), block_m=block_m,
                          block_n=block_n),
        out_shape=jax.ShapeDtypeStruct((mp, n), x2d.dtype),
        grid=(mp // block_m, n // block_n),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        interpret=INTERPRET,
        name="fused_matmul_rope",
    )(xp, w, b2)
    return out[:m]
