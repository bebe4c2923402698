"""Registry-driven OpTest sweep (VERDICT r3 #3).

The reference runs OpTest against essentially every op
(test/legacy_test/op_test.py:418 forward-vs-numpy, :3026 check_grad,
:1084 tolerances). Here the sweep is driven by ``ops/registry.py``: every
registered op must either carry a RECIPE (inputs/attrs (+ optional numpy
reference)) and pass

  1. execution + finite outputs,
  2. forward vs an independent NumPy reference (when one exists),
  3. eager == jit parity (the dispatch / compiled-lowering-cache paths),
  4. analytic-vs-finite-difference gradients (differentiable float ops),

or appear in SKIP with a written reason (dedicated suite / unsweepable
signature). ``test_registry_fully_classified`` pins that partition, so a
newly registered op FAILS the suite until it is classified.

The sweep runs under ``jax.default_matmul_precision('highest')`` — this
backend's default f32 matmul is reduced-precision, which would drown the
finite-difference checks in contraction noise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.registry import OPS

RNG = np.random.RandomState(0)


def sym(*shape):
    return RNG.uniform(-0.9, 0.9, shape).astype(np.float32)


def pos(*shape):
    return RNG.uniform(0.2, 0.9, shape).astype(np.float32)


def unit(*shape):
    return RNG.uniform(0.05, 0.95, shape).astype(np.float32)


def gt1(*shape):
    return RNG.uniform(1.1, 2.0, shape).astype(np.float32)


def ints(hi, *shape):
    return RNG.randint(0, hi, shape).astype(np.int64)


def boolean(*shape):
    return RNG.rand(*shape) > 0.5


def pd(*shape):
    a = RNG.randn(*shape).astype(np.float32)
    return (a @ a.T + shape[0] * np.eye(shape[0])).astype(np.float32)


def spaced(*shape):
    """Well-separated values (gap >> the FD delta) for max-style ops:
    near-ties would let the finite-difference perturbation flip an
    argmax and break the gradient check spuriously."""
    n = int(np.prod(shape))
    vals = np.linspace(-1.0, 1.0, n).astype(np.float32)
    return np.random.RandomState(1234 + n).permutation(vals).reshape(shape)


R = {}


def rec(name, inputs, attrs=None, ref=None, grad=True, grad_idx=None,
        rtol=1e-4, atol=1e-5, jit=True, grad_tol=5e-3):
    R[name] = dict(inputs=inputs, attrs=attrs or {}, ref=ref, grad=grad,
                   grad_idx=grad_idx, rtol=rtol, atol=atol, jit=jit,
                   grad_tol=grad_tol)


def np_ref(name):
    for mod in (np, np.linalg):
        f = getattr(mod, name, None)
        if f is not None:
            return f
    return None


# ---------------------------------------------------------------- math unary
for n in ("abs sign neg floor ceil round trunc exp expm1 sin cos tan "
          "sinh cosh tanh erf square reciprocal sigmoid frac "
          "asinh atan sqrt rsqrt").split():
    dom = pos if n in ("sqrt", "rsqrt", "reciprocal") else sym
    refs = {"neg": np.negative, "square": lambda x: x * x,
            "reciprocal": lambda x: 1.0 / x, "frac": lambda x: x - np.trunc(x),
            "sigmoid": lambda x: 1 / (1 + np.exp(-x)),
            "rsqrt": lambda x: 1 / np.sqrt(x), "erf": None}
    rec(n, [dom(3, 4)], ref=refs.get(n, np_ref(n)),
        grad=n not in ("sign", "floor", "ceil", "round", "trunc"))
for n in "log log2 log10 log1p digamma lgamma gammaln i0 i0e i1 i1e".split():
    rec(n, [pos(3, 4)], ref=np_ref(n), grad=True)
for n in "acos asin atanh erfinv logit".split():
    dom = unit if n in ("erfinv", "logit") else (lambda *s: sym(*s) * 0.8)
    rec(n, [dom(3, 4)], ref=np_ref(n))
rec("acosh", [gt1(3, 4)], ref=np.arccosh)
rec("asin", [sym(3, 4) * 0.8], ref=np.arcsin)
rec("acos", [sym(3, 4) * 0.8], ref=np.arccos)
rec("atanh", [sym(3, 4) * 0.8], ref=np.arctanh)
rec("stanh", [sym(3, 4)])
rec("angle", [sym(3, 4)], ref=np.angle, grad=False)
rec("conj", [sym(3, 4)], ref=np.conj, grad=False)
rec("real", [sym(3, 4)], ref=np.real, grad=False)
rec("imag", [(sym(3, 4) + 1j * sym(3, 4)).astype(np.complex64)],
    ref=np.imag, grad=False)
rec("nan_to_num", [np.array([[1.0, np.nan], [np.inf, -np.inf]], np.float32)],
    ref=np.nan_to_num, grad=False)
rec("polygamma", [pos(3, 4)], attrs={"n": 1}, grad=False)
rec("sign", [sym(3, 4)], ref=np.sign, grad=False)
rec("logit", [unit(3, 4)], grad=True)
rec("heaviside", [sym(3, 4), sym(3, 4)], ref=np.heaviside, grad=False)
rec("clip", [sym(3, 4)], attrs={"min": -0.5, "max": 0.5},
    ref=lambda x, **kw: np.clip(x, -0.5, 0.5))
rec("scale", [sym(3, 4)], attrs={"scale": 2.5, "bias": 1.0},
    ref=lambda x, **kw: 2.5 * x + 1.0)
rec("increment", [sym(1)], grad=False)
rec("cast", [sym(3, 4)], attrs={"dtype": "float32"}, grad=False)

# --------------------------------------------------------------- math binary
for n in ("add subtract multiply maximum minimum fmax fmin hypot "
          "copysign logaddexp atan2").split():
    rec(n, [sym(3, 4), sym(3, 4)], ref=np_ref(n) or {
        "atan2": np.arctan2}.get(n))
rec("atan2", [sym(3, 4), pos(3, 4)], ref=np.arctan2)
rec("divide", [sym(3, 4), pos(3, 4)], ref=np.divide)
rec("pow", [pos(3, 4), sym(3, 4)], ref=np.power)
rec("mod", [pos(3, 4), pos(3, 4)], ref=np.mod, grad=False)
rec("floor_mod", [pos(3, 4), pos(3, 4)], ref=np.mod, grad=False)
rec("remainder", [pos(3, 4), pos(3, 4)], ref=np.remainder, grad=False)
rec("floor_divide", [pos(3, 4) * 10, pos(3, 4)], ref=np.floor_divide,
    grad=False)
rec("nextafter", [sym(3, 4), sym(3, 4)], ref=np.nextafter, grad=False)
rec("ldexp", [sym(3, 4), ints(4, 3, 4)], ref=np.ldexp, grad=False)
rec("gcd", [ints(20, 3, 4), ints(20, 3, 4)], ref=np.gcd, grad=False)
rec("lcm", [ints(10, 3, 4) + 1, ints(10, 3, 4) + 1], ref=np.lcm,
    grad=False)
rec("lerp", [sym(3, 4), sym(3, 4), unit(3, 4)],
    ref=lambda x, y, w: x + w * (y - x))
rec("gammainc", [pos(3, 4) * 3, pos(3, 4) * 3], grad=False)
rec("gammaincc", [pos(3, 4) * 3, pos(3, 4) * 3], grad=False)
rec("diff", [sym(3, 5)], ref=np.diff, grad=True)
rec("trapezoid", [sym(3, 5)], ref=np.trapezoid if hasattr(np, "trapezoid")
    else np.trapz, grad=True)
rec("logical_and", [boolean(3, 4), boolean(3, 4)], ref=np.logical_and,
    grad=False)
rec("logical_or", [boolean(3, 4), boolean(3, 4)], ref=np.logical_or,
    grad=False)
rec("logical_xor", [boolean(3, 4), boolean(3, 4)], ref=np.logical_xor,
    grad=False)
rec("logical_not", [boolean(3, 4)], ref=np.logical_not, grad=False)
for n in "bitwise_and bitwise_or bitwise_xor".split():
    rec(n, [ints(16, 3, 4).astype(np.int32), ints(16, 3, 4).astype(np.int32)],
        ref=np_ref(n), grad=False)
rec("bitwise_not", [ints(16, 3, 4).astype(np.int32)], ref=np.bitwise_not,
    grad=False)
rec("bitwise_left_shift", [ints(8, 3, 4).astype(np.int32),
                           ints(4, 3, 4).astype(np.int32)],
    ref=np.left_shift, grad=False)
rec("bitwise_right_shift", [ints(64, 3, 4).astype(np.int32),
                            ints(4, 3, 4).astype(np.int32)],
    ref=np.right_shift, grad=False)
for n in ("equal not_equal greater_equal less_equal greater_than "
          "less_than greater less").split():
    npn = {"greater_than": np.greater, "less_than": np.less,
           "greater": np.greater, "less": np.less}.get(n, np_ref(n))
    rec(n, [ints(3, 3, 4).astype(np.float32),
            ints(3, 3, 4).astype(np.float32)], ref=npn, grad=False)
for n in "isfinite isinf isnan".split():
    rec(n, [np.array([[1.0, np.nan], [np.inf, 0.5]], np.float32)],
        ref=np_ref(n), grad=False)
rec("isclose", [sym(3, 4), sym(3, 4)], ref=np.isclose, grad=False)
rec("allclose", [sym(3, 4), sym(3, 4)], ref=np.allclose, grad=False)
rec("equal_all", [ints(3, 3, 4), ints(3, 3, 4)],
    ref=lambda a, b: np.array_equal(a, b), grad=False)
rec("multiplex", [[sym(4, 3), sym(4, 3)],
                  np.array([[0], [1], [0], [1]], np.int32)], grad=False,
    jit=False)
rec("fill_diagonal", [sym(4, 4)], attrs={"value": 0.0}, grad=False)
rec("fill_diagonal_tensor", [sym(4, 4), sym(4)], grad=False)
rec("copysign", [sym(3, 4), sym(3, 4)], ref=np.copysign, grad=False)
rec("renorm", [sym(3, 4)], attrs={"p": 2.0, "axis": 0, "max_norm": 1.0})
rec("reduce_as", [sym(3, 4), sym(1, 4)],
    ref=lambda x, t: x.sum(0, keepdims=True), grad_idx=[0])

# ---------------------------------------------------------------- reduction
for n in "max min amax amin mean sum prod".split():
    rec(n, [sym(3, 4)], ref=np_ref(n) or getattr(np, n, None))
rec("std", [sym(3, 4)], ref=lambda x: np.std(x, ddof=1), rtol=1e-3)
rec("var", [sym(3, 4)], ref=lambda x: np.var(x, ddof=1), rtol=1e-3)
rec("nanmean", [sym(3, 4)], ref=np.nanmean)
rec("nansum", [sym(3, 4)], ref=np.nansum)
rec("median", [sym(3, 5)], ref=np.median, grad=False)
rec("nanmedian", [sym(3, 5)], ref=np.nanmedian, grad=False)
rec("quantile", [sym(3, 5)], attrs={"q": 0.5},
    ref=lambda x, **kw: np.quantile(x, 0.5), grad=False)
rec("nanquantile", [sym(3, 5)], attrs={"q": 0.5},
    ref=lambda x, **kw: np.nanquantile(x, 0.5), grad=False)
rec("logsumexp", [sym(3, 4)],
    ref=lambda x: np.log(np.exp(x).sum()))
rec("logcumsumexp", [sym(3, 4)], attrs={"axis": 1},
    ref=lambda x, **kw: np.log(np.cumsum(np.exp(x), 1)))
rec("cumsum", [sym(3, 4)], attrs={"axis": 1},
    ref=lambda x, **kw: np.cumsum(x, 1))
rec("cumprod", [pos(3, 4)], attrs={"dim": 1},
    ref=lambda x, **kw: np.cumprod(x, 1))
rec("cummax", [sym(3, 4)], attrs={"axis": 1}, grad=False)
rec("cummin", [sym(3, 4)], attrs={"axis": 1}, grad=False)
rec("count_nonzero", [ints(2, 3, 4).astype(np.float32)],
    ref=np.count_nonzero, grad=False)
rec("mode", [sym(3, 5)], grad=False)
rec("all", [boolean(3, 4)], ref=np.all, grad=False)
rec("any", [boolean(3, 4)], ref=np.any, grad=False)

# --------------------------------------------------------------- activation
for n in ("relu relu6 elu celu selu silu swish mish softplus softsign "
          "hardtanh hardshrink softshrink tanhshrink hardsigmoid "
          "hardswish leaky_relu log_sigmoid thresholded_relu").split():
    rec(n, [sym(3, 4)])
rec("gelu", [sym(3, 4)])
rec("softmax", [sym(3, 4)],
    ref=lambda x: np.exp(x) / np.exp(x).sum(-1, keepdims=True))
rec("log_softmax", [sym(3, 4)],
    ref=lambda x: x - np.log(np.exp(x).sum(-1, keepdims=True)))
rec("glu", [sym(3, 4)])
rec("maxout", [sym(2, 4, 3, 3)], attrs={"groups": 2})
rec("prelu", [sym(3, 4), np.asarray([0.25], np.float32)])
rec("swiglu", [sym(3, 4), sym(3, 4)])
rec("gumbel_softmax", [sym(3, 4)], grad=False, ref=None, jit=False)
rec("rrelu", [sym(3, 4)], attrs={"training": False}, grad=False)

# ------------------------------------------------------------- manipulation
rec("reshape", [sym(3, 4)], attrs={"shape": [4, 3]},
    ref=lambda x, **kw: x.reshape(4, 3))
rec("transpose", [sym(3, 4)], attrs={"perm": [1, 0]},
    ref=lambda x, **kw: x.T)
rec("squeeze", [sym(3, 1, 4)], ref=np.squeeze)
rec("unsqueeze", [sym(3, 4)], attrs={"axis": 1},
    ref=lambda x, **kw: x[:, None])
rec("flatten", [sym(2, 3, 4)], ref=lambda x: x.reshape(2 * 3 * 4))
rec("flip", [sym(3, 4)], attrs={"axis": 0},
    ref=lambda x, **kw: np.flip(x, 0))
rec("reverse", [sym(3, 4)], attrs={"axis": 0},
    ref=lambda x, **kw: np.flip(x, 0))
rec("roll", [sym(3, 4)], attrs={"shifts": 1},
    ref=lambda x, **kw: np.roll(x, 1))
rec("rot90", [sym(3, 4)], ref=np.rot90)
rec("tile", [sym(3, 4)], attrs={"repeat_times": [2, 1]},
    ref=lambda x, **kw: np.tile(x, (2, 1)))
rec("broadcast_to", [sym(1, 4)], attrs={"shape": [3, 4]},
    ref=lambda x, **kw: np.broadcast_to(x, (3, 4)))
rec("expand", [sym(1, 4)], attrs={"shape": [3, 4]},
    ref=lambda x, **kw: np.broadcast_to(x, (3, 4)))
rec("expand_as", [sym(1, 4), sym(3, 4)],
    ref=lambda x, y: np.broadcast_to(x, (3, 4)), grad_idx=[0])
rec("concat", [[sym(2, 3), sym(2, 3)]], jit=False, grad=False,
    ref=lambda xs: np.concatenate(xs))
rec("stack", [[sym(2, 3), sym(2, 3)]], jit=False, grad=False,
    ref=lambda xs: np.stack(xs))
rec("split", [sym(4, 3)], attrs={"num_or_sections": 2}, grad=False)
rec("chunk", [sym(4, 3)], attrs={"chunks": 2}, grad=False)
rec("unbind", [sym(3, 4)], grad=False)
rec("unstack", [sym(3, 4)], grad=False)
rec("pad", [sym(3, 4)], attrs={"pad": [1, 1, 1, 1]})
rec("swapaxes", [sym(3, 4)], attrs={"axis0": 0, "axis1": 1},
    ref=lambda x, **kw: np.swapaxes(x, 0, 1))
rec("moveaxis", [sym(3, 4)], attrs={"source": 0, "destination": 1},
    ref=lambda x, **kw: np.moveaxis(x, 0, 1))
rec("diagonal", [sym(4, 4)], ref=np.diagonal)
rec("diag_embed", [sym(3, 4)], grad=False)
rec("kron", [sym(2, 2), sym(3, 3)], ref=np.kron, grad_tol=2e-2)
rec("take", [sym(3, 4), ints(12, 5)], ref=np.take, grad_idx=[0])
rec("take_along_axis", [sym(3, 4), ints(3, 3, 4), 0], jit=False,
    grad=False)
rec("repeat_interleave", [sym(3, 4)], attrs={"repeats": 2, "axis": 1},
    ref=lambda x, **kw: np.repeat(x, 2, 1))
rec("masked_fill", [sym(3, 4), boolean(3, 4), -1.0], jit=False,
    grad=False)
rec("numel", [sym(3, 4)], ref=lambda x: np.asarray(x.size), grad=False)
rec("atleast_1d", [np.float32(3.0)], grad=False)
rec("atleast_2d", [sym(4)], grad=False)
rec("atleast_3d", [sym(3, 4)], grad=False)
rec("as_complex", [sym(3, 4, 2)], grad=False)
rec("as_real", [(sym(3, 4) + 1j * sym(3, 4)).astype(np.complex64)],
    grad=False)
rec("crop", [sym(4, 5)], attrs={"shape": [2, 3], "offsets": [1, 1]},
    ref=lambda x, **kw: x[1:3, 1:4])
rec("slice", [sym(4, 5)], attrs={"axes": [0], "starts": [1], "ends": [3]},
    ref=lambda x, **kw: x[1:3])
rec("strided_slice", [sym(6, 5)],
    attrs={"axes": [0], "starts": [0], "ends": [6], "strides": [2]},
    ref=lambda x, **kw: x[0:6:2])
rec("index_add", [sym(4, 3), np.asarray([0, 2], np.int64), 0, sym(2, 3)],
    grad=False, jit=False)
rec("index_sample", [sym(3, 5), ints(5, 3, 2)], grad_idx=[0])
rec("index_put", [sym(3, 4), (ints(3, 2), ints(4, 2)), sym(2)],
    jit=False, grad=False)
rec("put_along_axis", [sym(3, 4), ints(3, 3, 4), sym(3, 4)],
    attrs={"axis": 0}, grad=False, jit=False)
rec("select_scatter", [sym(3, 4), sym(4)],
    attrs={"axis": 0, "index": 1}, grad_idx=[0, 1])
rec("slice_scatter", [sym(4, 4), sym(2, 4)],
    attrs={"axes": [0], "starts": [0], "ends": [2], "strides": [1]},
    grad_idx=[0, 1])
rec("tensor_split", [sym(4, 3)], attrs={"num_or_indices": 2}, grad=False)
rec("tensordot", [sym(3, 4), sym(4, 5)], attrs={"axes": 1},
    ref=lambda x, y, **kw: np.tensordot(x, y, 1), grad_tol=2e-2)
rec("broadcast_tensors", [[sym(1, 4), sym(3, 1)]], jit=False, grad=False)
rec("unfold", [sym(1, 1, 6, 6)], attrs={"kernel_sizes": 2}, grad=False)
rec("shard_index", [ints(20, 5, 1)],
    attrs={"index_num": 20, "nshards": 2, "shard_id": 0}, grad=False)
rec("view", [sym(3, 4)], attrs={"shape_or_dtype": [4, 3]},
    ref=lambda x, **kw: x.reshape(4, 3), grad=False)
rec("view_as", [sym(3, 4), sym(4, 3)], grad=False)
rec("meshgrid", [[sym(3), sym(4)]], jit=False, grad=False)

# ------------------------------------------------------------------ linalg
rec("matmul", [sym(3, 4), sym(4, 5)], ref=np.matmul, grad_tol=2e-2)
rec("mm", [sym(3, 4), sym(4, 5)], ref=np.matmul, grad_tol=2e-2)
rec("bmm", [sym(2, 3, 4), sym(2, 4, 5)], ref=np.matmul, grad_tol=2e-2)
rec("dot", [sym(4), sym(4)], ref=np.dot, grad_tol=2e-2)
rec("inner", [sym(3, 4), sym(5, 4)], ref=np.inner, grad_tol=2e-2)
rec("outer", [sym(3), sym(4)], ref=np.outer, grad_tol=2e-2)
rec("mv", [sym(3, 4), sym(4)], ref=np.matmul, grad_tol=2e-2)
rec("addmm", [sym(3, 5), sym(3, 4), sym(4, 5)], grad_tol=2e-2)
rec("t", [sym(3, 4)], ref=np.transpose)
rec("matrix_transpose", [sym(2, 3, 4)],
    ref=lambda x: np.swapaxes(x, -1, -2))
rec("trace", [sym(4, 4)], ref=np.trace)
rec("norm", [sym(3, 4)], ref=lambda x: np.linalg.norm(x), rtol=1e-3)
rec("p_norm", [sym(3, 4)], attrs={"p": 2},
    ref=lambda x, **kw: np.linalg.norm(x.reshape(-1)), rtol=1e-3)
rec("dist", [sym(3, 4), sym(3, 4)],
    ref=lambda x, y: np.linalg.norm((x - y).reshape(-1)))
rec("det", [pd(3)], ref=np.linalg.det, rtol=1e-3, grad_tol=2e-2)
rec("slogdet", [pd(3)], grad=False)
rec("inverse", [pd(3)], ref=np.linalg.inv, rtol=1e-3, grad_tol=5e-2)
rec("solve", [pd(3), sym(3, 2)], ref=np.linalg.solve, rtol=1e-3,
    grad_tol=5e-2)
rec("cholesky", [pd(3)], ref=np.linalg.cholesky, rtol=1e-3, grad=False)
rec("cholesky_solve", [sym(3, 1), np.linalg.cholesky(pd(3))], grad=False)
rec("triangular_solve", [np.tril(pd(3)).astype(np.float32), sym(3, 2)],
    attrs={"upper": False}, grad=False)
rec("eigvalsh", [pd(3)], ref=np.linalg.eigvalsh, rtol=1e-3, grad=False)
rec("eigh", [pd(3)], grad=False)
rec("eig", [pd(3)], grad=False)     # pure_callback: jits since round 15
rec("eigvals", [pd(3)], grad=False)
rec("svd", [sym(4, 3)], grad=False)
rec("qr", [sym(4, 3)], grad=False)
rec("lu", [pd(3)], grad=False)
rec("lstsq", [sym(4, 3), sym(4, 2)], grad=False)
rec("pinv", [sym(4, 3)], ref=np.linalg.pinv, rtol=1e-2, atol=1e-3,
    grad=False)
rec("matrix_power", [pd(3)], attrs={"n": 2},
    ref=lambda x, **kw: np.linalg.matrix_power(x, 2), rtol=1e-3,
    grad=False)
rec("matrix_rank", [pd(3)], ref=np.linalg.matrix_rank, grad=False)
rec("rank", [sym(3, 4)], ref=lambda x: np.asarray(x.ndim), grad=False)
rec("cross", [sym(4, 3), sym(4, 3)], ref=np.cross)  # paddle picks the
# first len-3 axis; (4,3) makes that the last axis, matching np
rec("cdist", [sym(3, 4), sym(5, 4)], grad=False)
rec("cov", [sym(3, 6)], ref=np.cov, rtol=1e-3, grad=False)
rec("corrcoef", [sym(3, 6)], ref=np.corrcoef, rtol=1e-3, grad=False)
rec("bincount", [ints(5, 10)], ref=np.bincount, grad=False, jit=False)
rec("histogram", [sym(10)], grad=False)  # in-graph since round 15
rec("vander", [sym(4)], grad=False)
rec("einsum", ["ij,jk->ik", sym(3, 4), sym(4, 5)], jit=False, grad=False)
rec("multi_dot", [[sym(3, 4), sym(4, 5)]], jit=False, grad=False)
rec("householder_product", [sym(4, 3), sym(3)], grad=False)

# -------------------------------------------------------------------- loss
rec("mse_loss", [sym(4, 3), sym(4, 3)],
    ref=lambda x, y: ((x - y) ** 2).mean())
rec("l1_loss", [sym(4, 3), sym(4, 3)],
    ref=lambda x, y: np.abs(x - y).mean(), grad_idx=[0])
rec("smooth_l1_loss", [sym(4, 3), sym(4, 3)], grad_idx=[0])
rec("huber_loss", [sym(4, 3), sym(4, 3)], grad_idx=[0])
rec("log_loss", [unit(4, 1), boolean(4, 1).astype(np.float32)],
    grad_idx=[0])
rec("square_error_cost", [sym(4, 3), sym(4, 3)],
    ref=lambda x, y: (x - y) ** 2)
rec("binary_cross_entropy", [unit(4, 3), boolean(4, 3).astype(np.float32)],
    grad_idx=[0])
rec("binary_cross_entropy_with_logits",
    [sym(4, 3), boolean(4, 3).astype(np.float32)], grad_idx=[0])
rec("kl_div", [np.log(unit(4, 3)), unit(4, 3)], grad_idx=[0])
rec("nll_loss", [np.log(unit(4, 5)), ints(5, 4)], grad_idx=[0])
rec("cross_entropy", [sym(4, 5), ints(5, 4)], grad_idx=[0])
rec("softmax_with_cross_entropy", [sym(4, 5), ints(5, 4, 1)],
    grad=False)
rec("sigmoid_focal_loss", [sym(4, 3), boolean(4, 3).astype(np.float32)],
    grad_idx=[0])
rec("margin_ranking_loss", [sym(4), sym(4),
                            np.sign(sym(4)).astype(np.float32)],
    grad_idx=[0, 1])
rec("hinge_embedding_loss", [sym(4, 3),
                             np.where(boolean(4, 3), 1, -1).astype(
                                 np.float32)], grad_idx=[0])
rec("cosine_embedding_loss", [sym(4, 3), sym(4, 3),
                              np.where(boolean(4), 1, -1).astype(
                                  np.float32)], grad_idx=[0, 1])
rec("triplet_margin_loss", [sym(4, 3), sym(4, 3), sym(4, 3)],
    grad_idx=[0])
rec("fused_linear_cross_entropy", [sym(6, 4), sym(4, 8), ints(8, 6)],
    grad_idx=[0, 1], grad_tol=2e-2)

# ------------------------------------------------------- fused (compile/fusion)
_sig = lambda v: 1.0 / (1.0 + np.exp(-v))
rec("fused_bias_act", [sym(6, 8), sym(8)], attrs={"activation": "silu"},
    ref=lambda x, b, **kw: (x + b) * _sig(x + b), grad_tol=2e-2)
rec("fused_residual_norm", [sym(6, 8), sym(6, 8), pos(8), sym(8)],
    grad_idx=[0, 1], grad_tol=2e-2)
rec("fused_norm_linear", [sym(6, 8), sym(8, 5)],
    attrs={"norm_type": "rms_norm", "epsilon": 1e-5},
    ref=lambda x, w, **kw: (
        x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)) @ w,
    rtol=1e-3, grad_tol=2e-2)
rec("fused_rope_proj", [sym(2, 4, 8), sym(8, 8)],
    attrs={"num_heads": 2}, grad_tol=2e-2)

# --------------------------------------------------------------- nn_common
rec("linear", [sym(3, 4), sym(4, 5)], ref=np.matmul, grad_tol=2e-2)
rec("embedding", [ints(6, 3), sym(6, 4)], grad_idx=[1])
rec("embedding_bag", [ints(6, 3, 2), sym(6, 4)], grad_idx=[1],
    ref=lambda i, w, **kw: w[i].sum(-2))
rec("dropout", [sym(3, 4)], attrs={"p": 0.0}, ref=lambda x, **kw: x)
rec("alpha_dropout", [sym(3, 4)], attrs={"p": 0.0},
    ref=lambda x, **kw: x)
rec("dropout2d", [sym(2, 3, 4, 4)], attrs={"p": 0.0},
    ref=lambda x, **kw: x)
rec("dropout3d", [sym(2, 3, 4, 4, 4)], attrs={"p": 0.0},
    ref=lambda x, **kw: x)
rec("cosine_similarity", [sym(3, 4), sym(3, 4)])
rec("label_smooth", [unit(3, 4)],
    ref=lambda x: x * 0.9 + 0.1 / 4)
rec("sequence_mask", [ints(5, 4) + 1], attrs={"maxlen": 6}, grad=False)
rec("pixel_shuffle", [sym(1, 8, 3, 3)], attrs={"upscale_factor": 2})
rec("pixel_unshuffle", [sym(1, 2, 4, 4)], attrs={"downscale_factor": 2})
rec("channel_shuffle", [sym(1, 4, 3, 3)], attrs={"groups": 2})
rec("zeropad2d", [sym(1, 2, 3, 3)], attrs={"padding": [1, 1, 1, 1]})
rec("bilinear", [sym(3, 4), sym(3, 5), sym(2, 4, 5)], grad_idx=[0, 1])
rec("interpolate", [sym(1, 2, 4, 4)], attrs={"scale_factor": 2},
    grad=False)
rec("upsample", [sym(1, 2, 4, 4)], attrs={"scale_factor": 2},
    grad=False)
rec("fold", [sym(1, 4, 4)],
    attrs={"output_sizes": [3, 3], "kernel_sizes": 2}, grad=False)

# --------------------------------------------------------------------- norm
rec("layer_norm", [sym(3, 4)], attrs={"normalized_shape": [4]},
    rtol=1e-3)
rec("rms_norm", [sym(3, 4), np.ones(4, np.float32)], jit=False,
    grad_idx=[0], rtol=1e-3)
rec("normalize", [sym(3, 4)], rtol=1e-3)
rec("group_norm", [sym(2, 4, 3, 3)], attrs={"num_groups": 2}, rtol=1e-3)
rec("instance_norm", [sym(2, 3, 4, 4)], rtol=1e-3)
rec("batch_norm", [sym(4, 3), np.zeros(3, np.float32),
                   np.ones(3, np.float32)],
    attrs={"training": True}, grad_idx=[0], rtol=1e-3, jit=False)
rec("local_response_norm", [sym(2, 4, 5, 5)], attrs={"size": 3},
    rtol=1e-3, grad=False)

# ------------------------------------------------------------------ pooling
for nd, shape in (("1d", (1, 2, 8)), ("2d", (1, 2, 6, 6)),
                  ("3d", (1, 2, 4, 4, 4))):
    rec(f"avg_pool{nd}", [sym(*shape)], attrs={"kernel_size": 2})
    rec(f"max_pool{nd}", [spaced(*shape)], attrs={"kernel_size": 2})
    rec(f"adaptive_avg_pool{nd}", [sym(*shape)], attrs={"output_size": 2})
    rec(f"adaptive_max_pool{nd}", [spaced(*shape)],
        attrs={"output_size": 2})
rec("lp_pool1d", [sym(1, 2, 8)],
    attrs={"norm_type": 2, "kernel_size": 2}, grad=False)
rec("lp_pool2d", [sym(1, 2, 6, 6)],
    attrs={"norm_type": 2, "kernel_size": 2}, grad=False)

# --------------------------------------------------------------------- conv
rec("conv1d", [sym(1, 2, 8), sym(3, 2, 3)], grad_tol=2e-2)
rec("conv2d", [sym(1, 2, 6, 6), sym(3, 2, 3, 3)], grad_tol=2e-2)
rec("conv3d", [sym(1, 2, 4, 4, 4), sym(2, 2, 2, 2, 2)], grad_tol=2e-2)
rec("conv1d_transpose", [sym(1, 2, 6), sym(2, 3, 3)], grad_tol=2e-2)
rec("conv2d_transpose", [sym(1, 2, 5, 5), sym(2, 3, 3, 3)],
    grad_tol=2e-2)
rec("conv3d_transpose", [sym(1, 2, 3, 3, 3), sym(2, 2, 2, 2, 2)],
    grad_tol=2e-2)

# ----------------------------------------------------------------- indexing
rec("gather", [sym(4, 3), ints(4, 5)], ref=lambda x, i: x[i],
    grad_idx=[0])
rec("gather_nd", [sym(4, 3), ints(3, 2, 1)], grad_idx=[0])
rec("index_select", [sym(4, 3), ints(4, 2)], attrs={"axis": 0},
    grad_idx=[0])
rec("scatter", [sym(4, 3), ints(4, 2), sym(2, 3)], grad_idx=[0, 2],
    jit=False)
rec("scatter_nd_add", [sym(4, 3), ints(4, 2, 1), sym(2, 3)],
    grad_idx=[0, 2], jit=False)


def _scatter_add_ref(x, i, u, **kw):
    out = np.copy(x)
    np.add.at(out, i, u)
    return out


rec("scatter_add", [sym(4, 3), ints(4, 5), sym(5, 3)], grad_idx=[0, 2],
    ref=_scatter_add_ref)
rec("masked_select", [sym(3, 4), boolean(3, 4)], grad=False, jit=False)

# ------------------------------------------------------------------- search
rec("where", [boolean(3, 4), sym(3, 4), sym(3, 4)], ref=np.where,
    grad_idx=[1, 2])
rec("sort", [sym(3, 5)], ref=np.sort, grad=False)
rec("argsort", [sym(3, 5)], ref=np.argsort, grad=False)
rec("argmax", [sym(3, 5)], ref=np.argmax, grad=False)
rec("argmin", [sym(3, 5)], ref=np.argmin, grad=False)
rec("topk", [sym(3, 5)], attrs={"k": 2}, grad=False)
rec("top_k", [sym(3, 5)], attrs={"k": 2}, grad=False)
rec("kthvalue", [sym(3, 5)], attrs={"k": 2}, grad=False)
rec("nonzero", [ints(2, 3, 4).astype(np.float32)], grad=False,
    jit=False)
rec("unique", [ints(4, 10).astype(np.float32)], grad=False, jit=False)
rec("unique_consecutive", [np.sort(ints(4, 10)).astype(np.float32)],
    grad=False, jit=False)
rec("searchsorted", [np.sort(sym(6)), sym(4)], ref=np.searchsorted,
    grad=False)
rec("bucketize", [sym(4), np.sort(sym(6))],
    ref=lambda x, b: np.searchsorted(b, x), grad=False)
rec("isin", [ints(5, 6).astype(np.float32),
             ints(5, 3).astype(np.float32)], ref=np.isin, grad=False)
rec("masked_scatter", [sym(3, 4), boolean(3, 4), sym(12)], grad=False,
    jit=False)
rec("index_of_max", [sym(3, 5)], grad=False)
rec("gather_tree", [ints(3, 5, 2, 3), ints(3, 5, 2, 3)], grad=False)

# -------------------------------------------------------------- creation
rec("tril", [sym(4, 4)], ref=np.tril)
rec("triu", [sym(4, 4)], ref=np.triu)
rec("diag", [sym(4)], ref=np.diag, grad=False)
rec("diagflat", [sym(4)], ref=np.diagflat, grad=False)
rec("assign", [sym(3, 4)], ref=lambda x: x, grad=False)
rec("clone", [sym(3, 4)], ref=lambda x: x)
rec("ones_like", [sym(3, 4)], ref=np.ones_like, grad=False)
rec("zeros_like", [sym(3, 4)], ref=np.zeros_like, grad=False)
rec("full_like", [sym(3, 4)], attrs={"fill_value": 2.5},
    ref=lambda x, **kw: np.full_like(x, 2.5), grad=False)
rec("empty_like", [sym(3, 4)], grad=False)
rec("one_hot", [ints(4, 5)], attrs={"num_classes": 4}, grad=False)
rec("complex", [sym(3, 4), sym(3, 4)], grad=False)
rec("polar", [pos(3, 4), sym(3, 4)], grad=False)
rec("to_tensor", [sym(3, 4)], ref=lambda x: x, grad=False)

# ---------------------------------------------------------------- signal
rec("frame", [sym(1, 16)], attrs={"frame_length": 4, "hop_length": 2},
    grad=False)
rec("overlap_add", [sym(1, 4, 7)], attrs={"hop_length": 2}, grad=False)


# --------------------------------------------------------- op-surface tail
rec("rad2deg", [sym(3, 4)], ref=np.rad2deg)
rec("deg2rad", [sym(3, 4)], ref=np.deg2rad)
rec("sinc", [sym(3, 4)], ref=np.sinc)
rec("sgn", [sym(3, 4)], ref=np.sign)
rec("signbit", [sym(3, 4)], ref=np.signbit, grad=False)
rec("isneginf", [np.array([[1.0, -np.inf], [np.inf, 0.0]], np.float32)],
    ref=np.isneginf, grad=False)
rec("isposinf", [np.array([[1.0, -np.inf], [np.inf, 0.0]], np.float32)],
    ref=np.isposinf, grad=False)
rec("isreal", [sym(3, 4)], ref=np.isreal, grad=False)
rec("multigammaln", [gt1(3, 4) + 2.0], attrs={"p": 2}, grad=True)
rec("cumulative_trapezoid", [sym(3, 6)],
    ref=lambda a: np.cumsum((a[..., 1:] + a[..., :-1]) * 0.5, axis=-1))
rec("pdist", [sym(5, 3)],
    ref=lambda a: np.sqrt(
        ((a[:, None, :] - a[None, :, :]) ** 2).sum(-1))[
            np.triu_indices(5, k=1)],
    grad_tol=3e-2)  # sqrt'(d) amplifies FD error at small distances
rec("block_diag", [[sym(2, 2), sym(3, 1)]],
    ref=None, grad=False, jit=False)
rec("hsplit", [sym(4, 6)], attrs={"num_or_indices": 2}, grad=False)
rec("vsplit", [sym(4, 6)], attrs={"num_or_indices": 2}, grad=False)
rec("dsplit", [sym(2, 2, 4)], attrs={"num_or_indices": 2}, grad=False)
rec("unflatten", [sym(6, 2)], attrs={"axis": 0, "shape": [2, 3]},
    ref=lambda a: a.reshape(2, 3, 2))
rec("index_fill", [sym(4, 3), np.array([0, 2], np.int64)],
    attrs={"axis": 0, "value": 7.0}, grad=False)
rec("diagonal_scatter", [sym(4, 4), sym(4)],
    ref=lambda a, b: (a * (1 - np.eye(4, dtype=a.dtype))
                      + np.diag(b).astype(a.dtype)))
rec("scatter_nd", [np.array([[1], [3]], np.int64), sym(2)],
    attrs={"shape": [6]}, grad=False)
rec("add_n", [[sym(3, 4), sym(3, 4), sym(3, 4)]],
    ref=lambda xs: xs[0] + xs[1] + xs[2], grad=False, jit=False)
# list-input ops: the harness hands ref the list itself (concat idiom)
for _sname, _sref in (("hstack", np.hstack), ("vstack", np.vstack),
                      ("dstack", np.dstack),
                      ("column_stack", np.column_stack),
                      ("row_stack", np.vstack)):
    rec(_sname, [[sym(2, 3), sym(2, 3)]], ref=_sref, grad=False,
        jit=False)


# ---------------------------------------------------------------- skips
from paddle_tpu.ops.inplace import INPLACE_OF  # noqa: E402

SKIP = {
    # higher-order control-flow ops: their operands are callables plus
    # whatever Tensors the branches close over — there is no sweepable
    # (inputs, attrs) recipe; eager/compiled/gradient behavior has a
    # dedicated suite
    **{n: "higher-order control-flow op (callable operands); covered by "
          "tests/test_control_flow.py"
       for n in ("conditional_block", "while_loop", "case",
                 "switch_case")},
    # in-place variants: payload-swap wrappers over the swept base ops
    **{n: f"in-place alias of {b} (payload swap; base op swept)"
       for n, b in INPLACE_OF.items()},
    "where_": "hand-written in-place where (adopts into x, not the "
              "condition — see ADVICE r4); semantics in test_advice_fixes",
    **{n: "random in-place fill; seeded behavior in test_api_tail.py"
       for n in ("normal_", "bernoulli_", "log_normal_", "cauchy_",
                 "geometric_")},
    # linalg tail: numerically verified against numpy/scipy in
    # tests/test_submodule_tail.py (decompositions need scipy refs)
    **{n: "covered by tests/test_submodule_tail.py (scipy/numpy refs)"
       for n in ("inv cholesky_inverse matrix_exp vector_norm "
                 "matrix_norm cond svd_lowrank ormqr").split()},
    # dispatched names the program verifier's TPU700 pass surfaced as
    # unregistered (round 20): now carry OpDefs; dedicated coverage
    "scaled_dot_product_attention":
        "pallas/XLA fused attention; eager/compiled/grad parity in "
        "tests/test_flash_attention.py and the model suites",
    "rotary_embedding":
        "RoPE with python-int/traced/per-batch offset contract; covered "
        "by the llama suites + fusion rope_proj tests",
    "getitem":
        "tensor indexing protocol (t[idx]); exercised pervasively via "
        "__getitem__ across the whole suite",
    "setitem":
        "in-place indexing protocol (t[idx] = v, registered round 22 "
        "for the TPU75x alias pass); exercised via __setitem__ across "
        "the suite and region-attr semantics in test_program_verifier",
    # registered lazily on fleet.moe import, so they only appear in the
    # registry when an earlier test pulled in the MoE stack
    "moe_gate":
        "gating softmax + top-k capacity dispatch: data-dependent "
        "routing has no elementwise sweep contract; parity-tested in "
        "test_moe_sep and verified in the tpulint --programs "
        "moe_layer ladder rung",
    "moe_layer":
        "monolithic GShard dispatch/expert/combine op: grouped einsum "
        "over routed tokens has no elementwise sweep contract; "
        "parity-tested in test_moe_sep and verified in the tpulint "
        "--programs moe_layer ladder rung",
    # state-space and routed-expert serving ops (nn/functional/ssm.py,
    # experts.py): tuple-in / tuple-out ops over a carried state or a
    # routing table, no elementwise sweep contract; each is compared with
    # the plain reference (benchmark/reference/nemotron_h.py) in
    # tests/test_nemotron_h.py
    **{n: "compared with the plain Nemotron-H reference in "
          "tests/test_nemotron_h.py (carried state / routing table: no "
          "elementwise sweep contract)"
       for n in ("causal_conv1d", "ssd_chunk_scan", "ssd_state_update",
                 "gated_group_rms_norm", "sigmoid_topk_route",
                 "held_experts_relu2")},
    **{n: "compared with the token-by-token loop in "
          "tests/test_delta_rule.py, with a decay a head and a decay a key "
          "channel (carried matrix state: no elementwise sweep contract)"
       for n in ("gated_delta_chunk", "gated_delta_step", "gated_rms_norm")},
    "held_experts_swiglu":
        "routing table in, no elementwise sweep contract; compared with "
        "the plain K-EXAONE reference in tests/test_exaone_moe.py (and, "
        "with a ReLU gate, SmallThinker's in tests/test_smallthinker.py)",
    "softmax_topk_route":
        "routing table out (top-k indices and a softmax over the chosen "
        "logits), no elementwise sweep contract; compared with numpy and "
        "the plain SmallThinker reference in tests/test_smallthinker.py",
    "gated_short_conv":
        "a causal filter along the sequence axis, no elementwise sweep "
        "contract; compared with a token loop and with the plain LFM2-MoE "
        "reference in tests/test_lfm2_moe.py",
    # op-surface tail without a sweepable contract
    "histogramdd": "multi-output (hist, edges-list) contract; "
                   "numpy-parity tested in test_api_tail",
    "as_strided": "gather-based strided view; covered in test_api_tail",
    "combinations": "index enumeration; covered in test_api_tail",
    "frexp": "dual-output decomposition; covered in test_api_tail",
    "binomial": "random draws; covered in test_api_tail",
    "standard_gamma": "random draws; covered in test_api_tail",
    "log_normal": "random draws (factory); covered in test_api_tail",
    # creation ops without a tensor input (shape-driven factories) —
    # exercised throughout the suite and in tests/test_ops.py
    **{n: "factory op (no tensor input); covered across the suite"
       for n in ("arange empty eye full linspace logspace ones zeros "
                 "rand randn randint_like randperm standard_normal "
                 "tril_indices triu_indices normal multinomial "
                 "bernoulli poisson exponential_ gaussian randint "
                 "uniform").split()},
    # stateful / random semantics (seeded paths covered in test_ops.py /
    # test_distributions.py)
    "shuffle_batch": "random shuffle; seeded behavior in test_ops.py",
    "top_p_sampling": "random sampling; covered by test_serving.py",
    "class_center_sample": "random sampling; covered in test_opset_round2.py",
    # dedicated suites
    "block_multihead_attention": "covered by tests/test_paged_attention.py",
    "window_ring_attention": "covered by tests/test_paged_attention.py",
    "latent_paged_attention": "covered by tests/test_paged_attention.py",
    "ctc_loss": "covered by tests/test_ops_round2b.py (CTC numerics)",
    "ctc_align": "covered by tests/test_ops_round2b.py",
    "rnnt_loss": "covered by tests/test_text_onnx.py / round2b",
    "edit_distance": "covered by tests/test_ops_round2b.py",
    "hsigmoid_loss": "tree-code signature; covered by round2b tests",
    "stft": "complex windowed transform; covered by test_ops_round2b.py",
    "istft": "complex windowed transform; covered by test_ops_round2b.py",
    **{n: "covered by tests/test_vision_ops.py"
       for n in ("affine_grid bipartite_match box_clip box_coder "
                 "correlation decode_jpeg deform_conv2d "
                 "distribute_fpn_proposals generate_proposals "
                 "grid_sample matrix_nms multiclass_nms nms prior_box "
                 "psroi_pool read_file roi_align roi_pool "
                 "temporal_shift yolo_box yolo_loss").split()},
    **{n: "covered by tests/test_sparse_ops.py geometric section"
       for n in ("reindex_graph reindex_heter_graph sample_neighbors "
                 "segment_max segment_mean segment_min segment_sum "
                 "send_u_recv send_ue_recv send_uv "
                 "weighted_sample_neighbors").split()},
    **{n: "covered by tests/test_coverage_round2b.py quantization"
       for n in ("apply_per_channel_scale fake_quant llm_int8_linear "
                 "weight_dequantize weight_only_linear "
                 "weight_quantize").split()},
    # in-place aliases of swept ops
    "reshape_": "in-place alias of reshape",
    "squeeze_": "in-place alias of squeeze",
    "unsqueeze_": "in-place alias of unsqueeze",
    # pooling variants with auxiliary-index plumbing
    "max_unpool1d": "needs indices from return_mask pool; test_nn.py",
    "max_unpool2d": "needs indices from return_mask pool; test_nn.py",
    "max_unpool3d": "needs indices from return_mask pool; test_nn.py",
    "fractional_max_pool2d": "random regions; covered in test_nn.py",
    "fractional_max_pool3d": "random regions; covered in test_nn.py",
    "adaptive_max_pool3d": "covered in test_nn.py (mask variant)",
    "lu_unpack": "consumes lu() pivots tuple; covered with lu in "
                 "test_ops.py",
    "pca_lowrank": "randomized algorithm; property-tested in test_ops.py",
}


def _to_tensor(v):
    if isinstance(v, paddle.Tensor):
        return v
    if isinstance(v, (list, tuple)) and all(
            isinstance(x, np.ndarray) for x in v):
        return [paddle.to_tensor(x) for x in v]
    if isinstance(v, np.ndarray):
        return paddle.to_tensor(v)
    return v


def _leaves(out):
    if isinstance(out, paddle.Tensor):
        return [out]
    if isinstance(out, (list, tuple)):
        res = []
        for o in out:
            res.extend(_leaves(o))
        return res
    return []


#: the registry as it stands at import (collection) time — ops that
#: OTHER tests register at runtime (custom-op suites exercising the
#: registration API) are not part of the framework surface this sweep
#: pins, and their presence must not depend on test execution order
_BASELINE_OPS = set(OPS)

ALL_SWEPT = sorted(set(R) & set(OPS))


def test_registry_fully_classified():
    """Every registered op is either swept or skip-listed with a reason —
    an unclassified new op fails the suite. Ops registered at RUNTIME by
    other tests (custom-op tests register from test modules) are out of
    scope — only the framework's own surface is pinned."""
    framework = {n for n in _BASELINE_OPS
                 if getattr(OPS.get(n), "lowering", None) is not None
                 and getattr(OPS[n].lowering, "__module__",
                             "").startswith("paddle_tpu")}
    unclassified = sorted(framework - set(R) - set(SKIP))
    assert not unclassified, (
        f"{len(unclassified)} registry ops lack a sweep recipe or a "
        f"skip reason: {unclassified}")
    # and no recipe/skip entry names a non-existent op (a typo would
    # silently test nothing)
    phantom = sorted((set(R) | set(SKIP)) - set(OPS))
    assert not phantom, f"recipes/skips for unknown ops: {phantom}"
    # and the partition is meaningful: the large majority is swept
    assert len(ALL_SWEPT) >= 300, (len(ALL_SWEPT), len(OPS))


@pytest.mark.parametrize("name", ALL_SWEPT)
def test_op(name):
    spec = R[name]
    d = OPS[name]
    fn = d.lowering
    with jax.default_matmul_precision("highest"):
        tensors = [_to_tensor(np.copy(v) if isinstance(v, np.ndarray)
                              else v) for v in spec["inputs"]]
        out = fn(*tensors, **spec["attrs"])
        leaves = _leaves(out)
        assert leaves, f"{name} returned no tensors"
        for o in leaves:
            a = o.numpy()
            if np.issubdtype(a.dtype, np.floating):
                assert np.isfinite(a).all(), f"{name}: non-finite output"

        if spec["ref"] is not None:
            ref = spec["ref"](*[np.copy(v) if isinstance(v, np.ndarray)
                                else v for v in spec["inputs"]])
            refs = ref if isinstance(ref, (list, tuple)) else [ref]
            for o, r in zip(leaves, refs):
                np.testing.assert_allclose(
                    o.numpy().astype(np.float64),
                    np.asarray(r).astype(np.float64),
                    rtol=spec["rtol"], atol=spec["atol"],
                    err_msg=f"{name}: forward mismatch vs NumPy")

        # eager == jit parity (array-only signatures)
        if spec["jit"] and all(isinstance(v, np.ndarray)
                               for v in spec["inputs"]):
            def jfn(*arrays):
                o = fn(*[paddle.Tensor(a) for a in arrays],
                       **spec["attrs"])
                return [t._data for t in _leaves(o)]

            jout = jax.jit(jfn)(*[jnp.asarray(v) for v in spec["inputs"]])
            for o, jo in zip(leaves, jout):
                np.testing.assert_allclose(
                    np.asarray(o.numpy(), np.float64),
                    np.asarray(jo, np.float64), rtol=1e-5, atol=1e-6,
                    err_msg=f"{name}: eager/jit divergence")

        # finite-difference gradient check
        if spec["grad"] and d.differentiable:
            from op_test import check_grad
            float_idx = [i for i, v in enumerate(spec["inputs"])
                         if isinstance(v, np.ndarray)
                         and np.issubdtype(v.dtype, np.floating)]
            idxs = spec["grad_idx"] if spec["grad_idx"] is not None \
                else float_idx
            if idxs and all(isinstance(v, np.ndarray)
                            for v in spec["inputs"]):
                check_grad(fn, [np.copy(v) for v in spec["inputs"]],
                           attrs=spec["attrs"], grad_input_idx=idxs,
                           max_relative_error=spec["grad_tol"])


# ---------------------------------------------------------------- bf16 pass
# Per-dtype sweep (reference op_test.py:1084,1492 — per-dtype tolerance
# defaults; bf16 atol 1e-2, grad 0.03): every float recipe re-runs with
# bf16 inputs and must stay within bf16 tolerances of its own f32 result.
# bf16 is THE dtype this framework trains in, so its coverage is pinned
# like the fp32 partition: eligible = all-float32-ndarray-input recipes;
# an op that cannot run bf16 needs a written reason in BF16_SKIP.

BF16_SKIP = {
    # LAPACK-style decompositions / solvers: f32/f64-only algorithms
    # (also f32/f64-only in the reference's MKL/cuSOLVER backends)
    **{n: "LAPACK-backed linalg; f32/f64 only (reference parity)"
       for n in ("cholesky cholesky_solve eig eigh eigvals eigvalsh "
                 "svd qr lu matrix_power det slogdet inverse "
                 "lstsq solve triangular_solve matrix_rank "
                 "corrcoef cov pinv householder_product").split()},
    **{n: "constructs complex64 outputs; complex has no bf16 analog"
       for n in ("complex", "as_complex", "polar")},
    "erfinv": "XLA bf16 erfinv lowering unsupported; f32 upcast is the "
              "documented usage",
    "i0": "Bessel series needs f32 accumulation; reference CPU kernel "
          "is f32/f64 only",
    "i0e": "as i0", "i1": "as i0", "i1e": "as i0",
    "polygamma": "series expansion; f32/f64 only in reference too",
    "digamma": "as polygamma", "lgamma": "as polygamma",
    "gammaln": "as polygamma",
    "logit": "log(p/(1-p)) near saturation overflows bf16's 8-bit "
             "mantissa beyond any fixed tolerance",
    "histogram": "bin boundary assignment flips under bf16 rounding",
    "histogramdd": "as histogram", "bincount": "integer-driven",
    "searchsorted": "boundary comparisons flip under bf16 rounding",
    "bucketize": "as searchsorted",
    "isclose": "tolerance semantics are dtype-relative; bf16 run is "
               "a different contract, covered by its own unit test",
    "allclose": "as isclose",
}


def _bf16_eligible(name):
    spec = R[name]
    ins = spec["inputs"]
    return (spec["jit"] and ins
            and all(isinstance(v, np.ndarray) for v in ins)
            and all(v.dtype == np.float32 for v in ins))


BF16_SWEPT = sorted(n for n in ALL_SWEPT
                    if _bf16_eligible(n) and n not in BF16_SKIP)


def test_bf16_partition_pinned():
    """The bf16-covered count is pinned the way the fp32 one is: a new
    float op must either sweep in bf16 or carry a written reason."""
    assert len(BF16_SWEPT) >= 150, len(BF16_SWEPT)
    phantom = sorted(set(BF16_SKIP) - set(OPS))
    assert not phantom, f"BF16_SKIP names unknown ops: {phantom}"


@pytest.mark.parametrize("name", BF16_SWEPT)
def test_op_bf16(name):
    """bf16 run vs the op's own f32 result, at reference bf16
    tolerances. Outputs that are integral/bool (argmax, counts) must be
    EXACT; float outputs get rtol/atol 3e-2 over the f32 baseline plus
    the input-rounding error bf16 casting introduces."""
    spec = R[name]
    fn = OPS[name].lowering
    with jax.default_matmul_precision("highest"):
        f32_in = [np.copy(v) for v in spec["inputs"]]
        # the f32 BASELINE uses the bf16-rounded values, so the compare
        # isolates the op's own bf16 arithmetic from input rounding
        rounded = [np.asarray(jnp.asarray(v, jnp.bfloat16)
                              .astype(jnp.float32)) for v in f32_in]
        ref = _leaves(fn(*[_to_tensor(v) for v in rounded],
                         **spec["attrs"]))
        got = _leaves(fn(*[paddle.Tensor(jnp.asarray(v, jnp.bfloat16))
                           for v in f32_in], **spec["attrs"]))
        assert len(ref) == len(got)
        def is_float(dt):
            # ml_dtypes' bfloat16/float8 are NOT np.floating subtypes
            return (np.issubdtype(dt, np.floating)
                    or jnp.issubdtype(dt, jnp.floating))

        for r, g in zip(ref, got):
            ga = g.numpy()
            ra = r.numpy()
            if is_float(ra.dtype):
                assert is_float(ga.dtype), \
                    f"{name}: float output became {ga.dtype}"
                np.testing.assert_allclose(
                    ga.astype(np.float64), ra.astype(np.float64),
                    rtol=3e-2, atol=3e-2,
                    err_msg=f"{name}: bf16 output diverged")
            else:
                np.testing.assert_array_equal(
                    ga, ra, err_msg=f"{name}: integral output changed "
                                    f"under bf16")
