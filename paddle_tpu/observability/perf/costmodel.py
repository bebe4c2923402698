"""Analytical per-op cost model — FLOPs and HBM bytes per op class.

The attribution layer's source of *modeled* truth (reference analogue: the
per-op cost analysis phi kernels are tuned against; XLA lineage:
``Compiled.cost_analysis()``). Each op class gets a closed-form
FLOPs/bytes formula — matmul, conv, attention, elementwise, reduction,
norm, collectives — attached to the op registry via the ``OpDef.cost_fn``
field so dispatch, the profiler, and tools/perf_report.py all read the
same numbers. ``xla_cost`` extracts the same quantities from a compiled
program so tests can cross-check the model against XLA's own analysis.

Conventions:

* ``flops`` counts multiply-add as 2 (XLA's convention for dot/conv).
* ``bytes_read``/``bytes_written`` are the op's *minimal* HBM traffic —
  each input read once, each output written once. Fused producers and
  cached re-reads make real traffic differ; the roofline report treats
  these as the achievable floor (what a perfectly-fused kernel moves).
* A cost_fn signature is ``fn(input_shapes, input_dtypes, attrs,
  output_shapes) -> OpCost``; shapes are tuples of ints, dtypes numpy
  dtypes (bf16 included), attrs the op's semantic attr dict.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence

import numpy as np

__all__ = ["OpCost", "cost_of", "attach_cost_models", "xla_cost",
           "collective_cost", "einsum_cost", "dtype_bytes",
           "COST_MODELS"]


def dtype_bytes(dtype) -> int:
    """Element size in bytes; bfloat16 (ml_dtypes) is 2."""
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        # jax bf16 scalar type object
        return int(np.dtype(getattr(dtype, "dtype", "float32")).itemsize)


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


@dataclass
class OpCost:
    """Modeled cost of one op execution."""

    flops: float = 0.0
    bytes_read: float = 0.0
    bytes_written: float = 0.0
    notes: str = ""

    @property
    def bytes(self) -> float:
        return self.bytes_read + self.bytes_written

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per HBM byte — the roofline x-axis."""
        return self.flops / self.bytes if self.bytes else 0.0

    def __add__(self, other: "OpCost") -> "OpCost":
        return OpCost(self.flops + other.flops,
                      self.bytes_read + other.bytes_read,
                      self.bytes_written + other.bytes_written,
                      self.notes or other.notes)

    def to_dict(self) -> dict:
        return {"flops": self.flops, "bytes_read": self.bytes_read,
                "bytes_written": self.bytes_written,
                "bytes": self.bytes,
                "arithmetic_intensity": round(self.arithmetic_intensity,
                                              4)}


def _io_bytes(input_shapes, input_dtypes, output_shapes,
              out_dtype=None) -> tuple:
    """Default traffic model: every input read once, every output written
    once."""
    read = 0.0
    for i, s in enumerate(input_shapes):
        db = dtype_bytes(input_dtypes[i]) if i < len(input_dtypes) else 4
        read += _numel(s) * db
    if out_dtype is None:
        out_dtype = input_dtypes[0] if input_dtypes else np.float32
    written = sum(_numel(s) * dtype_bytes(out_dtype)
                  for s in output_shapes)
    return read, written


# --------------------------------------------------------------------------
# Op-class formulas
# --------------------------------------------------------------------------
def matmul_cost(input_shapes, input_dtypes, attrs, output_shapes) -> OpCost:
    """(…, m, k) @ (…, k, n): 2·m·k·n MACs per batch element. Handles
    transpose_x/y attrs and broadcast batching (bmm/addmm/linear ride the
    same formula; a bias add contributes m·n flops)."""
    a, b = tuple(input_shapes[0]), tuple(input_shapes[1])
    attrs = attrs or {}
    if attrs.get("transpose_x") or attrs.get("transpose_X"):
        a = a[:-2] + (a[-1], a[-2])
    if attrs.get("transpose_y") or attrs.get("transpose_Y"):
        b = b[:-2] + (b[-1], b[-2])
    if len(a) == 1:
        a = (1, a[0])
    if len(b) == 1:
        b = (b[0], 1)
    m, k = int(a[-2]), int(a[-1])
    n = int(b[-1])
    batch = 1
    for d in (output_shapes[0][:-2] if output_shapes
              else np.broadcast_shapes(a[:-2], b[:-2])):
        batch *= int(d)
    flops = 2.0 * batch * m * k * n
    if len(input_shapes) > 2:          # bias (linear/addmm)
        flops += batch * m * n
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(flops, read, written, "matmul")


def conv2d_cost(input_shapes, input_dtypes, attrs, output_shapes) -> OpCost:
    """NCHW x (Cout, Cin/g, kh, kw): 2·N·Cout·Hout·Wout·(Cin/g)·kh·kw."""
    x, w = tuple(input_shapes[0]), tuple(input_shapes[1])
    attrs = attrs or {}
    groups = int(attrs.get("groups", 1) or 1)
    cout, cin_g = int(w[0]), int(w[1])
    kh = int(w[2]) if len(w) > 2 else 1
    kw = int(w[3]) if len(w) > 3 else 1
    if output_shapes:
        out = tuple(output_shapes[0])
        n = int(out[0])
        spatial = _numel(out[2:])
    else:
        n = int(x[0])
        stride = attrs.get("stride", 1)
        if isinstance(stride, (tuple, list)):
            stride = stride[0]
        stride = int(stride or 1)
        spatial = max(_numel(x[2:]) // (stride * stride), 1)
    flops = 2.0 * n * cout * spatial * cin_g * kh * kw
    if len(input_shapes) > 2:
        flops += n * cout * spatial      # bias
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(flops, read, written, f"conv groups={groups}")


def attention_cost(input_shapes, input_dtypes, attrs,
                   output_shapes) -> OpCost:
    """Scaled-dot-product / flash attention over (B, S, H, D) QKV (the
    paddle layout this framework dispatches): QKᵀ and PV are each
    2·B·H·S·S_kv·D flops, plus the softmax's ~5·B·H·S·S_kv elementwise
    flops. Bytes follow the FLASH traffic model — QKV in, O out, no S×S
    matrix round-trip (the fused kernel keeps scores in VMEM); the
    unfused XLA path's extra traffic shows up as distance from this
    floor."""
    q = tuple(input_shapes[0])
    k = tuple(input_shapes[1]) if len(input_shapes) > 1 else q
    if len(q) == 4:                       # (B, S, H, D)
        b, s_q, h, d = (int(x) for x in q)
        s_kv = int(k[1])
    else:                                 # (B, S, D) single head
        b, s_q, d = (int(x) for x in q)
        h, s_kv = 1, int(k[1])
    mm = 4.0 * b * h * s_q * s_kv * d
    soft = 5.0 * b * h * s_q * s_kv
    read, written = _io_bytes(input_shapes[:3], input_dtypes,
                              output_shapes)
    return OpCost(mm + soft, read, written, "attention(flash traffic)")


def elementwise_cost(flops_per_elt: float = 1.0) -> Callable:
    def fn(input_shapes, input_dtypes, attrs, output_shapes) -> OpCost:
        n = _numel(output_shapes[0]) if output_shapes else (
            max((_numel(s) for s in input_shapes), default=0))
        read, written = _io_bytes(input_shapes, input_dtypes,
                                  output_shapes)
        return OpCost(flops_per_elt * n, read, written, "elementwise")
    return fn


def reduction_cost(input_shapes, input_dtypes, attrs,
                   output_shapes) -> OpCost:
    n = max((_numel(s) for s in input_shapes), default=0)
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(float(n), read, written, "reduction")


def norm_cost(input_shapes, input_dtypes, attrs, output_shapes) -> OpCost:
    """layer/rms/batch/group/instance norm: mean+var (2 passes) +
    normalize+affine ≈ 8 flops/element over the activation."""
    n = _numel(input_shapes[0]) if input_shapes else 0
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(8.0 * n, read, written, "norm")


def softmax_cost(input_shapes, input_dtypes, attrs,
                 output_shapes) -> OpCost:
    n = _numel(input_shapes[0]) if input_shapes else 0
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(5.0 * n, read, written, "softmax")  # max,sub,exp,sum,div


def gather_cost(input_shapes, input_dtypes, attrs, output_shapes) -> OpCost:
    """embedding/gather: no flops, traffic = gathered rows + indices."""
    read = 0.0
    if len(input_shapes) > 1:
        read += _numel(input_shapes[1]) * 8          # indices (i64)
    out_b = sum(_numel(s) * dtype_bytes(
        input_dtypes[0] if input_dtypes else np.float32)
        for s in output_shapes)
    return OpCost(0.0, read + out_b, out_b, "gather")


def embedding_bag_cost(input_shapes, input_dtypes, attrs,
                       output_shapes) -> OpCost:
    """Pooled gather (ids(…, L) x table(V, H) -> (…, H)): every id
    reads one H-row, the pool adds them (1 flop per gathered element),
    but only ONE pooled row is written per bag — the traffic asymmetry
    that makes dedup-before-exchange pay on skewed batches."""
    ids_n = _numel(input_shapes[0]) if input_shapes else 0
    table = tuple(input_shapes[1]) if len(input_shapes) > 1 else ()
    h = int(table[-1]) if table else 1
    item = dtype_bytes(input_dtypes[1]) if len(input_dtypes) > 1 else 4
    read = ids_n * 8 + ids_n * h * item      # indices (i64) + rows
    out_b = sum(_numel(s) * item for s in output_shapes)
    return OpCost(float(ids_n * h), read, out_b, "embedding_bag")


def scatter_add_cost(input_shapes, input_dtypes, attrs,
                     output_shapes) -> OpCost:
    """Row accumulate (dest(V, …) += updates at index): dest read +
    written once, updates and indices read once, one add per updated
    element (the sharded-embedding backward's table-grad op)."""
    upd_n = _numel(input_shapes[2]) if len(input_shapes) > 2 else 0
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(float(upd_n), read, written, "scatter_add")


def cross_entropy_cost(input_shapes, input_dtypes, attrs,
                       output_shapes) -> OpCost:
    n = _numel(input_shapes[0]) if input_shapes else 0
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(6.0 * n, read, written, "softmax+nll")


def fused_linear_cross_entropy_cost(input_shapes, input_dtypes, attrs,
                                    output_shapes) -> OpCost:
    """Head and loss in one op, as a training step pays for it: THREE
    products of rows x hidden x vocabulary (logits, the rows' gradient, the
    table's: the op's forward takes the gradients while a chunk's logits
    are at hand) and ~6 flops a logit of softmax + nll. The logits never
    leave the op: its traffic is the rows, the table and the labels."""
    x, table = tuple(input_shapes[0]), tuple(input_shapes[1])
    hidden = int(x[-1])
    rows = _numel(x) // max(hidden, 1)
    vocab = _numel(table) // max(hidden, 1)
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(3 * 2.0 * rows * hidden * vocab + 6.0 * rows * vocab,
                  read, written, "3 x matmul + softmax+nll")


def fused_residual_norm_cost(input_shapes, input_dtypes, attrs,
                             output_shapes) -> OpCost:
    """residual add (1) + norm (~8) flops/element; traffic = x +
    residual in, normed + sum out (the fusion's whole point: no
    intermediate round-trip)."""
    n = _numel(input_shapes[0]) if input_shapes else 0
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(9.0 * n, read, written, "fused residual+norm")


def fused_norm_linear_cost(input_shapes, input_dtypes, attrs,
                           output_shapes) -> OpCost:
    """norm prologue (~8/elt of x) + GEMM + bias/act epilogue (~5/elt
    of out); traffic = x + W (+vectors) in, ONE output out."""
    mm = matmul_cost(input_shapes[:2] if len(input_shapes) >= 2
                     else input_shapes, input_dtypes, {}, output_shapes)
    n_in = _numel(input_shapes[0]) if input_shapes else 0
    n_out = _numel(output_shapes[0]) if output_shapes else 0
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(mm.flops + 8.0 * n_in + 5.0 * n_out, read, written,
                  "fused norm+linear+act")


def fused_rope_proj_cost(input_shapes, input_dtypes, attrs,
                         output_shapes) -> OpCost:
    """GEMM + rotary epilogue (~6 flops/output element, incl. the
    sin/cos transcendentals)."""
    mm = matmul_cost(input_shapes[:2] if len(input_shapes) >= 2
                     else input_shapes, input_dtypes, {}, output_shapes)
    n_out = _numel(output_shapes[0]) if output_shapes else 0
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(mm.flops + 6.0 * n_out, read, written,
                  "fused rope projection")


def einsum_cost(input_shapes, input_dtypes, attrs, output_shapes) -> OpCost:
    """General einsum from the recorded ``equation`` attr: FLOPs =
    2 x the product of every distinct label's extent (each output
    element is a MAC chain over the contracted extents). Without an
    equation (legacy traces) the contraction structure is unknown —
    fall back to the matmul formula when shapes allow, else
    elementwise-over-largest-operand."""
    eq = (attrs or {}).get("equation")
    if isinstance(eq, str) and "." not in eq:
        lhs = eq.replace(" ", "").split("->", 1)[0]
        terms = lhs.split(",")
        if len(terms) == len(input_shapes) and all(
                len(t) == len(s) for t, s in zip(terms, input_shapes)):
            extent: Dict[str, int] = {}
            for t, s in zip(terms, input_shapes):
                for c, d in zip(t, s):
                    extent[c] = int(d)
            vol = 1.0
            for d in extent.values():
                vol *= d
            read, written = _io_bytes(input_shapes, input_dtypes,
                                      output_shapes)
            return OpCost(2.0 * vol, read, written, f"einsum {eq}")
    if len(input_shapes) >= 2 and all(len(s) >= 2
                                      for s in input_shapes[:2]):
        return matmul_cost(input_shapes, input_dtypes, {}, output_shapes)
    n = max((_numel(s) for s in input_shapes), default=0)
    read, written = _io_bytes(input_shapes, input_dtypes, output_shapes)
    return OpCost(2.0 * n, read, written, "einsum (no equation)")


def collective_cost(primitive: str, nbytes: float,
                    n_devices: int) -> OpCost:
    """Wire bytes of one collective under the standard ring algorithms
    (all_reduce moves 2·(n−1)/n·B, all_gather/reduce_scatter (n−1)/n·B,
    all_to_all (n−1)/n·B, broadcast/p2p B)."""
    n = max(int(n_devices), 1)
    p = primitive.lower()
    if n == 1:
        wire = 0.0
    elif "all_reduce" in p or "allreduce" in p:
        wire = 2.0 * (n - 1) / n * nbytes
    elif ("all_gather" in p or "allgather" in p
          or "reduce_scatter" in p or "all_to_all" in p
          or "alltoall" in p):
        wire = (n - 1) / n * nbytes
    else:                                # broadcast / send / recv / reduce
        wire = float(nbytes)
    return OpCost(0.0, wire, 0.0, f"{primitive} wire bytes n={n}")


# --------------------------------------------------------------------------
# Registry attachment
# --------------------------------------------------------------------------
#: op name -> cost_fn. The closed vocabulary the tests pin; categories not
#: named here fall back via _CATEGORY_MODELS.
COST_MODELS: Dict[str, Callable] = {}


def _fill_models():
    mm = matmul_cost
    for name in ("matmul", "mm", "bmm", "addmm", "linear", "fc",
                 "matmul_v2"):
        COST_MODELS[name] = mm
    for name in ("conv2d", "conv1d", "conv3d", "conv2d_transpose",
                 "depthwise_conv2d"):
        COST_MODELS[name] = conv2d_cost
    for name in ("flash_attention", "scaled_dot_product_attention",
                 "block_multihead_attention"):
        COST_MODELS[name] = attention_cost
    for name in ("layer_norm", "rms_norm", "batch_norm", "group_norm",
                 "instance_norm", "fused_layer_norm", "fused_rms_norm"):
        COST_MODELS[name] = norm_cost
    COST_MODELS["softmax"] = softmax_cost
    COST_MODELS["log_softmax"] = softmax_cost
    for name in ("cross_entropy", "softmax_with_cross_entropy",
                 "bce_with_logits"):
        COST_MODELS[name] = cross_entropy_cost
    COST_MODELS["fused_linear_cross_entropy"] = \
        fused_linear_cross_entropy_cost
    for name in ("embedding", "gather", "gather_nd", "index_select",
                 "take_along_axis"):
        COST_MODELS[name] = gather_cost
    COST_MODELS["embedding_bag"] = embedding_bag_cost
    COST_MODELS["scatter_add"] = scatter_add_cost
    for name in ("sum", "mean", "max", "min", "prod", "reduce_sum",
                 "logsumexp", "cumsum", "argmax", "argmin", "norm"):
        COST_MODELS[name] = reduction_cost
    ew1 = elementwise_cost(1.0)
    for name in ("add", "subtract", "multiply", "divide", "relu", "abs",
                 "scale", "clip", "where", "maximum", "minimum", "cast",
                 "add_n", "sqrt", "rsqrt", "square", "floor", "ceil",
                 "sign", "tril", "triu"):
        COST_MODELS[name] = ew1
    ew4 = elementwise_cost(4.0)          # transcendental-ish
    for name in ("exp", "log", "tanh", "sigmoid", "gelu", "silu", "swish",
                 "erf", "sin", "cos", "pow", "softplus", "log1p"):
        COST_MODELS[name] = ew4
    COST_MODELS["einsum"] = einsum_cost
    # dispatch-level ops with no registry entry (tensor protocol /
    # model-layer composites) — named here so the planner's scoring
    # walk prices them (tools/planner_audit.py enforces coverage)
    COST_MODELS["getitem"] = elementwise_cost(0.0)   # slice: traffic only
    COST_MODELS["rotary_embedding"] = elementwise_cost(6.0)
    # fused ops (compile/fusion rewrite targets) — round-12 attribution
    # must see through the rewrite (ISSUE 10)
    COST_MODELS["fused_bias_act"] = elementwise_cost(5.0)
    COST_MODELS["fused_residual_norm"] = fused_residual_norm_cost
    COST_MODELS["fused_norm_linear"] = fused_norm_linear_cost
    COST_MODELS["fused_rope_proj"] = fused_rope_proj_cost


_fill_models()

#: category fallback when an op has no named model
_CATEGORY_MODELS: Dict[str, Callable] = {
    "linalg": matmul_cost,
    "conv": conv2d_cost,
    "attention": attention_cost,
    "norm": norm_cost,
    "reduction": reduction_cost,
    "loss": cross_entropy_cost,
    "activation": elementwise_cost(4.0),
    "math": elementwise_cost(1.0),
    "manipulation": elementwise_cost(0.0),
    "creation": elementwise_cost(0.0),
    "indexing": gather_cost,
    "search": reduction_cost,
    # fused ops carry NAMED models (COST_MODELS above); this fallback
    # only covers future fused registrations that miss the audit gate
    "fusion": elementwise_cost(4.0),
}


def attach_cost_models() -> int:
    """Attach the per-op-class formulas to the live op registry
    (``OpDef.cost_fn``). Idempotent; a cost_fn already set by a
    register(..., cost_fn=) site wins. Returns the number of ops that
    now carry a model."""
    from ...ops import registry as reg

    n = 0
    for name, od in reg.OPS.items():
        if od.cost_fn is None:
            fn = COST_MODELS.get(name) or _CATEGORY_MODELS.get(od.category)
            if fn is not None:
                od.cost_fn = fn
        if od.cost_fn is not None:
            n += 1
    return n


def cost_of(op_name: str, input_shapes: Sequence, input_dtypes=(),
            attrs: Optional[dict] = None,
            output_shapes: Sequence = ()) -> Optional[OpCost]:
    """Modeled cost of one op execution, or None when neither the
    registry nor the name/category tables know the op."""
    # precedence: registry cost_fn (a register(..., cost_fn=) override
    # must beat the generic tables — the documented extension contract)
    # > per-name class formula > category fallback
    fn = None
    category = None
    try:
        from ...ops import registry as reg
        od = reg.OPS.get(op_name)
        if od is not None:
            fn = od.cost_fn
            category = od.category
    except Exception:
        fn = None
    if fn is None:
        fn = COST_MODELS.get(op_name)
    if fn is None and category is not None:
        fn = _CATEGORY_MODELS.get(category)
    if fn is None:
        return None
    try:
        return fn(list(map(tuple, input_shapes)), list(input_dtypes),
                  dict(attrs or {}), list(map(tuple, output_shapes)))
    except Exception:
        return None


# --------------------------------------------------------------------------
# XLA cross-check
# --------------------------------------------------------------------------
def xla_cost(compiled) -> Optional[dict]:
    """FLOPs / bytes-accessed of a ``jax.stages.Compiled`` (or anything
    with ``cost_analysis()``), summed across partitions. Returns
    ``{"flops", "bytes_accessed", "transcendentals"}`` or None when the
    backend exposes no analysis."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if ca is None:
        return None
    if isinstance(ca, dict):
        ca = [ca]
    if not ca:
        return None
    out = {"flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}
    for entry in ca:
        out["flops"] += float(entry.get("flops", 0.0) or 0.0)
        out["bytes_accessed"] += float(
            entry.get("bytes accessed", 0.0) or 0.0)
        out["transcendentals"] += float(
            entry.get("transcendentals", 0.0) or 0.0)
    return out


def relative_error(modeled: float, measured: float) -> float:
    """|modeled − measured| / max(measured, 1) — the cross-check metric
    the tests assert tolerance on."""
    return abs(modeled - measured) / max(abs(measured), 1.0)


def roofline_bound(cost: OpCost, peak_flops: float,
                   peak_bw: float) -> dict:
    """Where the op sits on the roofline: attainable FLOP/s at its
    arithmetic intensity, and whether the bound is compute or HBM
    bandwidth."""
    ai = cost.arithmetic_intensity
    attainable = min(peak_flops, peak_bw * ai) if ai > 0 else 0.0
    ridge = peak_flops / peak_bw if peak_bw else math.inf
    return {"arithmetic_intensity": ai,
            "attainable_flops": attainable,
            "bound": "compute" if ai >= ridge else "bandwidth",
            "ridge_intensity": ridge}
