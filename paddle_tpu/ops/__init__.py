"""Op layer: user-facing tensor functions + Tensor method attachment.

The reference monkey-patches ~400 methods onto its eager Tensor
(python/paddle/tensor/__init__.py); this module does the same for ours.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core import dispatch
from ..core.dtype import convert_dtype
from ..core.tensor import Tensor, as_tensor
from . import creation, linalg, manipulation, math, reduction, search
from .registry import OPS, op_names, ops_by_category

from .math import *        # noqa: F401,F403
from .creation import *    # noqa: F401,F403
from .manipulation import *  # noqa: F401,F403
from .reduction import *   # noqa: F401,F403
from .linalg import *      # noqa: F401,F403
from .search import *      # noqa: F401,F403
from . import inplace, tail  # noqa: E402  (need the base ops registered)
from .inplace import *     # noqa: F401,F403
from .tail import *        # noqa: F401,F403


# ---------------------------------------------------------------------------
# Tensor indexing
# ---------------------------------------------------------------------------
def _norm_index(idx):
    """Convert Tensors in an index expression into raw arrays."""
    def conv(i):
        if isinstance(i, Tensor):
            return i._data
        if isinstance(i, (list, np.ndarray)):
            return jnp.asarray(i)
        return i
    if isinstance(idx, tuple):
        return tuple(conv(i) for i in idx)
    return conv(idx)


def _static_region(idx, shape):
    """Per-dim ``(start, stop)`` hull of a static int/slice index
    expression, or None when any component is data-dependent (tensor /
    array / mask indices) or unhandled. Dims past the indexed prefix
    are full extent. Consumed by the verifier's TPU75x alias pass
    (static.liveness): a provably-disjoint write/read pair is safe, so
    the hull must never under-approximate — unknown means None."""
    import builtins                    # `slice` is shadowed by the op
    items = idx if isinstance(idx, tuple) else (idx,)
    region = []
    for k, it in enumerate(items):
        if k >= len(shape):
            return None
        n = int(shape[k])
        if isinstance(it, bool) or it is None or it is Ellipsis:
            return None
        if isinstance(it, (int, np.integer)):
            s = int(it) + (n if int(it) < 0 else 0)
            if not 0 <= s < n:
                return None
            region.append((s, s + 1))
        elif isinstance(it, builtins.slice):
            # NOTE: builtins only in here — `any`/`max`/`slice` are all
            # shadowed by the star-imported op surface
            for x in (it.start, it.stop, it.step):
                if x is not None and not isinstance(x, (int, np.integer)):
                    return None
            s, e, st = it.indices(n)
            if st < 0:                 # hull of a reversed slice
                s, e = e + 1, s + 1
            region.append((s, builtins.max(s, e)))
        else:
            return None
    for k in range(len(items), len(shape)):
        region.append((0, int(shape[k])))
    return tuple(region)


def _getitem(self, idx):
    """Tensor indexing protocol (``t[idx]``): ints/slices/ellipsis/
    tensor indices lower to jax advanced indexing as ONE ``getitem``
    op; boolean masks take the data-dependent host path (reference
    masked_select semantics)."""
    if isinstance(idx, Tensor) and idx.dtype == np.dtype(bool):
        # boolean mask -> dynamic shape -> host path (parity with reference
        # masked_select semantics): the result length is only known after
        # reading the mask, so this site is a host boundary by contract,
        # not an accidental sync
        mask = np.asarray(idx._data).astype(bool)  # tpulint: disable=TPU104 — data-dependent output shape
        data = np.asarray(self._data)  # tpulint: disable=TPU104 — same masked_select host boundary
        return Tensor(jnp.asarray(data[mask]))
    nidx = _norm_index(idx)
    attrs = {}
    reg = _static_region(idx, self.shape)
    if reg is not None:
        attrs["read_region"] = reg
    return dispatch.call("getitem", lambda a, **_attrs: a[nidx], [self],
                         attrs=attrs)


# registry entry for the dispatched name: the tensor-protocol indexing
# pseudo-op already carried a named spmd rule; the program verifier's
# TPU700 contract pass surfaced the missing OpDef
from .registry import register as _register_op  # noqa: E402

_register_op("getitem", category="indexing")(_getitem)


def _setitem(self, idx, value):
    """In-place region write ``t[idx] = value`` (``.at[idx].set`` under
    functional XLA semantics, payload swapped back into ``t``). Records
    a ``write_region`` attr when the index hull is static so the
    verifier's TPU75x alias pass can prove disjoint rewrites safe."""
    nidx = _norm_index(idx)
    vt = value if isinstance(value, Tensor) else as_tensor(value)
    attrs = {}
    reg = _static_region(idx, self.shape)
    if reg is not None:
        # static write hull: lets the TPU75x alias pass prove a
        # disjoint region rewrite safe (no attr = data-dependent)
        attrs["write_region"] = reg
    def f(a, v, **_attrs):
        return a.at[nidx].set(v.astype(a.dtype))
    out = dispatch.call("setitem", f, [self, vt], attrs=attrs)
    self._swap_payload(out._data)
    self.grad_node, self.output_index = out.grad_node, out.output_index
    self.stop_gradient = out.stop_gradient if not self.stop_gradient else self.stop_gradient
    return self


# registry entry mirrors getitem's: the indexing pseudo-op needs an
# OpDef for the verifier's TPU700 contract pass (found when the TPU75x
# alias pass first put recorded setitem programs through the ladder)
_register_op("setitem", category="indexing")(_setitem)


def _astype(self, dtype):
    return math.cast(self, dtype)


def _clone(self):
    return creation.clone(self)


def _item(self, *args):
    return Tensor.item(self, *args)


_BINARY_OPERATORS = {
    "__add__": math.add, "__radd__": lambda a, b: math.add(b, a),
    "__sub__": math.subtract, "__rsub__": lambda a, b: math.subtract(b, a),
    "__mul__": math.multiply, "__rmul__": lambda a, b: math.multiply(b, a),
    "__truediv__": math.divide, "__rtruediv__": lambda a, b: math.divide(b, a),
    "__floordiv__": math.floor_divide,
    "__rfloordiv__": lambda a, b: math.floor_divide(b, a),
    "__mod__": math.mod, "__rmod__": lambda a, b: math.mod(b, a),
    "__pow__": math.pow, "__rpow__": lambda a, b: math.pow(b, a),
    "__matmul__": linalg.matmul, "__rmatmul__": lambda a, b: linalg.matmul(b, a),
    "__eq__": math.equal, "__ne__": math.not_equal,
    "__lt__": math.less_than, "__le__": math.less_equal,
    "__gt__": math.greater_than, "__ge__": math.greater_equal,
    "__and__": math.bitwise_and, "__or__": math.bitwise_or,
    "__xor__": math.bitwise_xor,
}


def _attach_methods():
    for name, fn in _BINARY_OPERATORS.items():
        setattr(Tensor, name, (lambda f: lambda self, other: f(self, other))(fn))
    Tensor.__neg__ = lambda self: math.neg(self)
    Tensor.__abs__ = lambda self: math.abs(self)
    Tensor.__invert__ = lambda self: math.logical_not(self)
    Tensor.__getitem__ = _getitem
    Tensor.__setitem__ = _setitem
    Tensor.__hash__ = object.__hash__  # __eq__ override would kill hashing

    methods = {
        # math
        "add": math.add, "subtract": math.subtract, "multiply": math.multiply,
        "divide": math.divide, "floor_divide": math.floor_divide, "mod": math.mod,
        "remainder": math.mod, "pow": math.pow, "maximum": math.maximum,
        "minimum": math.minimum, "exp": math.exp, "log": math.log, "log2": math.log2,
        "log10": math.log10, "log1p": math.log1p, "sqrt": math.sqrt, "rsqrt": math.rsqrt,
        "square": math.square, "abs": math.abs, "neg": math.neg, "sign": math.sign,
        "floor": math.floor, "ceil": math.ceil, "round": math.round, "trunc": math.trunc,
        "reciprocal": math.reciprocal, "sin": math.sin, "cos": math.cos, "tan": math.tan,
        "asin": math.asin, "acos": math.acos, "atan": math.atan, "sinh": math.sinh,
        "cosh": math.cosh, "tanh": math.tanh, "erf": math.erf, "sigmoid": math.sigmoid,
        "scale": math.scale, "clip": math.clip, "lerp": math.lerp, "cast": math.cast,
        "astype": _astype, "isnan": math.isnan, "isinf": math.isinf,
        "isfinite": math.isfinite, "equal": math.equal, "not_equal": math.not_equal,
        "less_than": math.less_than, "less_equal": math.less_equal,
        "greater_than": math.greater_than, "greater_equal": math.greater_equal,
        "logical_and": math.logical_and, "logical_or": math.logical_or,
        "logical_not": math.logical_not, "logical_xor": math.logical_xor,
        "isclose": math.isclose, "allclose": math.allclose, "equal_all": math.equal_all,
        "nan_to_num": math.nan_to_num,
        # reduction
        "sum": reduction.sum, "mean": reduction.mean, "max": reduction.max,
        "min": reduction.min, "prod": reduction.prod, "any": reduction.any,
        "all": reduction.all, "std": reduction.std, "var": reduction.var,
        "logsumexp": reduction.logsumexp, "median": reduction.median,
        "cumsum": reduction.cumsum, "cumprod": reduction.cumprod,
        "amax": reduction.amax, "amin": reduction.amin,
        "count_nonzero": reduction.count_nonzero,
        # manipulation
        "reshape": manipulation.reshape, "reshape_": manipulation.reshape_,
        "flatten": manipulation.flatten, "squeeze": manipulation.squeeze,
        "squeeze_": manipulation.squeeze_, "unsqueeze": manipulation.unsqueeze,
        "unsqueeze_": manipulation.unsqueeze_, "transpose": manipulation.transpose,
        "tile": manipulation.tile, "expand": manipulation.expand,
        "expand_as": manipulation.expand_as, "broadcast_to": manipulation.broadcast_to,
        "flip": manipulation.flip, "roll": manipulation.roll,
        "gather": manipulation.gather, "gather_nd": manipulation.gather_nd,
        "scatter": manipulation.scatter, "scatter_nd_add": manipulation.scatter_nd_add,
        "index_select": manipulation.index_select, "masked_select": search.masked_select
        if hasattr(search, "masked_select") else manipulation.masked_select,
        "masked_fill": manipulation.masked_fill, "split": manipulation.split,
        "chunk": manipulation.chunk, "unbind": manipulation.unbind,
        "pad": manipulation.pad, "take_along_axis": manipulation.take_along_axis,
        "put_along_axis": manipulation.put_along_axis, "repeat_interleave":
        manipulation.repeat_interleave, "diagonal": manipulation.diagonal,
        "numel_t": manipulation.numel, "moveaxis": manipulation.moveaxis,
        "unfold": manipulation.unfold, "view": manipulation.view,
        "view_as": manipulation.view_as,
        # linalg
        "matmul": linalg.matmul, "mm": linalg.mm, "bmm": linalg.bmm, "dot": linalg.dot,
        "norm": linalg.norm, "dist": linalg.dist, "t": linalg.t, "trace": linalg.trace,
        "inner": linalg.inner, "outer": linalg.outer, "cross": linalg.cross,
        "cholesky": linalg.cholesky, "inverse": linalg.inverse,
        "matrix_power": linalg.matrix_power,
        # search
        "argmax": search.argmax, "argmin": search.argmin, "argsort": search.argsort,
        "sort": search.sort, "topk": search.topk, "where": search.where,
        "nonzero": search.nonzero, "unique": search.unique, "kthvalue": search.kthvalue,
        "bucketize": search.bucketize,
        # creation-ish
        "clone": _clone, "fill_": lambda self, v: self.set_value(
            jnp.full(tuple(self.shape), v, dtype=self._data.dtype)),
        "zero_": lambda self: self.set_value(jnp.zeros(tuple(self.shape),
                                                       dtype=self._data.dtype)),
    }
    for name, fn in methods.items():
        setattr(Tensor, name, fn)

    # in-place arithmetic sugar (paddle add_/subtract_/scale_)
    def _make_inplace(f):
        def inplace(self, *a, **k):
            out = f(self, *a, **k)
            self._swap_payload(out._data)
            self.grad_node, self.output_index = out.grad_node, out.output_index
            if not out.stop_gradient:
                self.stop_gradient = False
            return self
        return inplace

    for nm, f in [("add_", math.add), ("subtract_", math.subtract),
                  ("multiply_", math.multiply), ("divide_", math.divide),
                  ("scale_", math.scale), ("clip_", math.clip),
                  ("exp_", math.exp), ("sqrt_", math.sqrt), ("rsqrt_", math.rsqrt),
                  ("floor_", math.floor), ("ceil_", math.ceil),
                  ("reciprocal_", math.reciprocal), ("round_", math.round),
                  ("tanh_", math.tanh)]:
        setattr(Tensor, nm, _make_inplace(f))


_attach_methods()


# ---------------------------------------------------------------------------
# Registry: every public op function is registered (ops/registry.py is the
# source of truth the parity audit runs against — tools/op_parity_audit.py)
# ---------------------------------------------------------------------------
def _register_all():
    from .registry import register_module
    # control-flow ops self-register via @register decorators (their
    # reference yaml names: conditional_block / while); imported here so
    # the registry is complete at paddle_tpu import time
    from . import control_flow  # noqa: F401
    register_module(math, "math")
    register_module(creation, "creation")
    register_module(manipulation, "manipulation")
    register_module(reduction, "reduction")
    register_module(linalg, "linalg")
    register_module(search, "search")
    from ..nn import functional as _F
    from ..nn.functional import (activation as _act, common as _common,
                                 conv as _conv, loss as _loss, norm as _norm,
                                 pooling as _pool)
    # explicit skips: these names are deliberately ALSO defined at the
    # nn.functional level (paddle has both paddle.sigmoid and
    # paddle.nn.functional.sigmoid); the ops-level registration above is
    # the OpDef of record — tpulint TPU304 rejects silent shadowing
    for mod, cat, skip in ((_act, "activation", ("sigmoid", "tanh")),
                           (_common, "nn_common",
                            ("one_hot", "pad", "unfold")),
                           (_conv, "conv", ()), (_loss, "loss", ()),
                           (_norm, "norm", ()), (_pool, "pooling", ())):
        register_module(mod, cat, skip=skip)
    from ..nn.functional import flash_attention as _fa
    register_module(_fa, "attention")
    # fused ops self-register via @register decorators (category
    # "fusion" with cost/spmd coverage gated by tools/fusion_audit.py)
    from ..nn.functional import fused as _fused  # noqa: F401
    from ..nn.functional import vision as _vis
    register_module(_vis, "vision")
    from ..nn.functional import paged_attention as _paged
    register_module(_paged, "attention")
    from ..nn.functional import (delta_rule as _delta, experts as _experts,
                                 ssm as _ssm)
    register_module(_ssm, "nn_common")
    register_module(_experts, "nn_common")
    # the three ops; the packed state's helpers are plain functions
    register_module(_delta, "nn_common",
                    skip=("heads_packed", "pack_state", "unpack_state"))
    from ..vision import ops as _vops
    register_module(_vops, "vision")
    from .. import geometric as _geo
    register_module(_geo, "geometric")
    from .. import signal as _sig
    register_module(_sig, "signal")
    from .. import quantization as _quant
    register_module(_quant, "quantization")

    # rotary_embedding dispatches from models/llama.py (imported on
    # demand, so it cannot self-register at paddle_tpu import time);
    # the OpDef lives here as a lazy forwarder — the program verifier's
    # TPU700 contract pass surfaced the missing entry
    from .registry import register as _reg

    def rotary_embedding(x, theta=10000.0, pos_offset=0):
        """Apply RoPE to [B, S, H, D] activations (reference fused_rope
        op): (even, odd) channel pairs rotated by position-dependent
        angles at base ``theta``; ``pos_offset`` may be a python int
        (recorded as a semantic attr, fusable into the projection), a
        traced scalar, or a per-batch vector."""
        from ..models.llama import rotary_embedding as _impl
        return _impl(x, theta=theta, pos_offset=pos_offset)

    _reg("rotary_embedding", category="attention")(rotary_embedding)


_register_all()
