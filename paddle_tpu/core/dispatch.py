"""Eager op dispatcher.

TPU-native replacement for the reference's generated per-op ``*_ad_func``
layer (reference: paddle/fluid/eager/auto_code_generator/generator/
eager_gen.py:301 — AMP cast -> type promotion -> autograd-meta -> GradNode ->
PHI kernel call -> NaN check). Here one generic ``call`` does that pipeline
for every op: the "kernel" is a jax-level lowering (XLA fuses + schedules, so
there is no KernelKey/backend selection), and the GradNode is the jax.vjp
closure of the lowering. Payloads may be tracers, so the same dispatcher body
is what program capture traces through.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from . import flags
from .tensor import Tensor
from ..observability import metrics as _metrics
from ..observability import trace as _trace

_perf_counter = time.perf_counter  # patchable seam for overhead tests

# Filled in lazily to break the core<->autograd import cycle (the autograd
# package re-exports dispatch's grad-mode contexts).
GradNode = None
AccumulationNode = None
_sot = None  # bound on first eager dispatch (core<->jit import cycle)


def _bind_engine():
    global GradNode, AccumulationNode
    if GradNode is None:
        from ..autograd.engine import AccumulationNode as _A, GradNode as _G
        GradNode, AccumulationNode = _G, _A

_state = threading.local()


def _tls():
    if not hasattr(_state, "grad_enabled"):
        _state.grad_enabled = True
        _state.amp_level = "O0"
        _state.amp_dtype = dtypes.bfloat16
        _state.amp_custom_white = set()
        _state.amp_custom_black = set()
        _state.branch_trace = None
        _state.quiet = False
    return _state


# ---------------------------------------------------------------------------
# Branch tracing (control-flow ops). While a branch trace is installed,
# ``call`` does not execute ops at all: it hands them to the trace, which
# evaluates shapes abstractly and records which external Tensors the branch
# reads (ops/control_flow.py builds lax.cond/while_loop/switch lowerings
# from that). Saved/restored as a stack so nested control flow works.
# ---------------------------------------------------------------------------
def enter_branch_trace(bt):
    s = _tls()
    prev = s.branch_trace
    s.branch_trace = bt
    return prev


def exit_branch_trace(prev):
    _tls().branch_trace = prev


def in_branch_trace() -> bool:
    return _tls().branch_trace is not None


class quiet_scope:
    """Suppress dispatch side channels (profiler taps, Program recorder,
    export tracers, nan/benchmark sweeps) for ops dispatched inside a
    control-flow lowering: the enclosing construct is recorded as ONE op,
    so its internals must not leak tracer-held tensors into recorders."""

    def __enter__(self):
        s = _tls()
        self._prev = s.quiet
        s.quiet = True
        return self

    def __exit__(self, *exc):
        _tls().quiet = self._prev
        return False


def grad_enabled() -> bool:
    return _tls().grad_enabled


def set_grad_enabled(mode: bool) -> bool:
    s = _tls()
    prev = s.grad_enabled
    s.grad_enabled = mode
    return prev


class no_grad:
    """Context manager + decorator (paddle.no_grad)."""

    def __enter__(self):
        self._prev = set_grad_enabled(False)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with no_grad():
                return fn(*a, **k)
        return wrapper


class enable_grad:
    def __enter__(self):
        self._prev = set_grad_enabled(True)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


class set_grad_enabled_ctx:
    def __init__(self, mode: bool):
        self._mode = mode

    def __enter__(self):
        self._prev = set_grad_enabled(self._mode)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


# ---------------------------------------------------------------------------
# AMP op lists — capability parity with reference python/paddle/amp/amp_lists.py
# (bf16-first: on TPU the MXU natively consumes bf16).
# ---------------------------------------------------------------------------
AMP_WHITE_OPS = {
    "matmul", "mm", "bmm", "conv2d", "conv1d", "conv3d", "conv2d_transpose",
    "einsum", "linear", "addmm", "flash_attention", "scaled_dot_product_attention",
    # head + loss, chunk by chunk: three products with the vocabulary a
    # differentiated call (logits, the rows' gradient, the table's) take the
    # amp dtype's operands; max / logsumexp / picked logit / loss are f32
    # and the table's gradient sums in f32 over the chunks whatever the
    # input dtype; nothing of (rows, vocabulary) is kept
    "fused_linear_cross_entropy",
    # GEMM-bearing fused ops (compile/fusion): the norm prologue /
    # rope epilogue compute in f32 internally regardless of input dtype
    "fused_norm_linear", "fused_rope_proj",
}
AMP_BLACK_OPS = {
    "exp", "log", "log2", "log10", "log1p", "pow", "square", "sqrt", "rsqrt",
    "softmax", "log_softmax", "cross_entropy", "softmax_with_cross_entropy",
    "layer_norm", "rms_norm", "batch_norm", "group_norm", "instance_norm",
    "mean", "sum", "cumsum", "sigmoid_cross_entropy", "reduce_sum",
    "norm", "cos_sim", "erfinv", "acos", "asin", "atan2",
}


def amp_state():
    s = _tls()
    return s.amp_level, s.amp_dtype


def set_amp_state(level: str, dtype=None, custom_white=None, custom_black=None):
    s = _tls()
    prev = (s.amp_level, s.amp_dtype, s.amp_custom_white, s.amp_custom_black)
    s.amp_level = level
    if dtype is not None:
        s.amp_dtype = dtypes.convert_dtype(dtype)
    s.amp_custom_white = set(custom_white or ())
    s.amp_custom_black = set(custom_black or ())
    return prev


def restore_amp_state(prev):
    s = _tls()
    s.amp_level, s.amp_dtype, s.amp_custom_white, s.amp_custom_black = prev


def _amp_cast_inputs(op_name: str, arrays: List):
    """O1: cast white-list op inputs to amp dtype, black-list to fp32.
    O2 casting happens at the parameter level (amp.decorate)."""
    s = _tls()
    if s.amp_level not in ("O1", "O2"):
        return arrays
    name = op_name.lower()
    white = (name in AMP_WHITE_OPS or name in s.amp_custom_white)
    black = (name in AMP_BLACK_OPS or name in s.amp_custom_black)
    if white and not black:
        target = s.amp_dtype
    elif black:
        target = dtypes.float32
    else:
        return arrays
    out = []
    for a in arrays:
        d = np.dtype(a.dtype)
        if d in (dtypes.float16, dtypes.bfloat16, dtypes.float32) and d != target:
            a = a.astype(target)
        out.append(a)
    return out


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------
# Hot-path flag mirror: dispatch reads these per op, so they are kept in
# sync by flag observers instead of registry lookups per call.
_hot_flags = {"check_nan_inf": flags.get_flag("check_nan_inf"),
              "benchmark": flags.get_flag("benchmark"),
              "eager_jit_cache": flags.get_flag("eager_jit_cache"),
              "enable_metrics": flags.get_flag("enable_metrics"),
              "perf_op_cost": flags.get_flag("perf_op_cost")}
flags.on_change("check_nan_inf",
                lambda v: _hot_flags.__setitem__("check_nan_inf", v))
flags.on_change("benchmark",
                lambda v: _hot_flags.__setitem__("benchmark", v))
flags.on_change("eager_jit_cache",
                lambda v: _hot_flags.__setitem__("eager_jit_cache", v))
flags.on_change("enable_metrics",
                lambda v: _hot_flags.__setitem__("enable_metrics", v))
flags.on_change("perf_op_cost",
                lambda v: _hot_flags.__setitem__("perf_op_cost", v))

# Dispatch telemetry instruments (collection is gated per event by
# FLAGS_enable_metrics; declaring them here is one-time import cost).
_m_op_latency = _metrics.histogram(
    "paddle_tpu_dispatch_op_latency_seconds",
    "Host wall time per eager op dispatch (lowering + tape + side "
    "channels).", labelnames=("op",))
_m_eager_jit = _metrics.counter(
    "paddle_tpu_eager_jit_cache_total",
    "Eager compiled-lowering cache events: hit = compiled fast path, "
    "miss = first sight of a key, warmup = eager run below the jit "
    "threshold, compile = jitted entry installed, uncacheable = closure "
    "not exactly keyable, bypass = known-uncacheable key.",
    labelnames=("event",))
_m_hook_overhead = _metrics.histogram(
    "paddle_tpu_dispatch_hook_seconds",
    "Host time spent inside op/recorder/export hooks per dispatch.")
_m_op_flops = _metrics.counter(
    "paddle_tpu_perf_op_flops_total",
    "Modeled FLOPs dispatched per op (analytical cost model; "
    "FLAGS_perf_op_cost).", labelnames=("op",))
_m_op_bytes = _metrics.counter(
    "paddle_tpu_perf_op_bytes_total",
    "Modeled minimal HBM bytes moved per op (analytical cost model; "
    "FLAGS_perf_op_cost).", labelnames=("op",))

_costmodel = None  # bound on first perf_op_cost dispatch (lazy: the perf
# package imports the op registry, which must finish loading first)


def _accumulate_op_cost(op_name, arrays, attrs, out_list):
    """Fold the modeled per-op FLOPs/bytes into the perf counters —
    FLAGS_perf_op_cost sites only (one cost_fn call per dispatch)."""
    global _costmodel
    try:
        if _costmodel is None:
            from ..observability.perf import costmodel as _cm
            _costmodel = _cm
        c = _costmodel.cost_of(
            op_name,
            [tuple(getattr(a, "shape", ())) for a in arrays],
            [getattr(a, "dtype", None) for a in arrays], attrs,
            [tuple(getattr(o, "shape", ())) for o in out_list])
        if c is not None:
            _m_op_flops.inc(c.flops, op=op_name)
            _m_op_bytes.inc(c.bytes, op=op_name)
    except Exception:
        pass

_op_hooks: List[Callable] = []  # profiler / debugging taps
_recorder_tls = threading.local()  # program capture is per-thread: a
# guard on thread A must not record ops dispatched by thread B


def _recorder_hooks() -> List[Callable]:
    hooks = getattr(_recorder_tls, "hooks", None)
    if hooks is None:
        hooks = _recorder_tls.hooks = []
    return hooks


def register_recorder_hook(fn):
    _recorder_hooks().append(fn)


def unregister_recorder_hook(fn):
    hooks = _recorder_hooks()
    if fn in hooks:
        hooks.remove(fn)


_export_hooks: List[Callable] = []  # ONNX/interchange tracers: receive
# (op_name, tensor_inputs, out_tensors, export_attrs) — the SEMANTIC op
# parameters (stride/padding/...) that the jax lowering closures over


def register_export_hook(fn):
    _export_hooks.append(fn)


def unregister_export_hook(fn):
    try:
        _export_hooks.remove(fn)
    except ValueError:
        pass


def register_op_hook(fn):
    """Register a per-op tap called as ``fn(op_name, inputs, outputs,
    attrs, duration_s)``. Legacy 4-positional hooks are adapted so older
    taps keep working without seeing the latency argument."""
    import inspect
    target = fn
    try:
        params = inspect.signature(fn).parameters.values()
        positional = [p for p in params
                      if p.kind in (p.POSITIONAL_ONLY,
                                    p.POSITIONAL_OR_KEYWORD)]
        has_var = any(p.kind == p.VAR_POSITIONAL for p in params)
        if not has_var and len(positional) == 4:
            def target(op, ins, outs, attrs, dur, __fn=fn):
                return __fn(op, ins, outs, attrs)
            # stack, not a single slot: double register + double
            # unregister of the same legacy hook must stay symmetric
            _hook_adapters.setdefault(fn, []).append(target)
    except (TypeError, ValueError):
        pass
    _op_hooks.append(target)
    return fn


_hook_adapters: Dict[Callable, List[Callable]] = {}


def unregister_op_hook(fn):
    adapters = _hook_adapters.get(fn)
    target = fn
    if adapters:
        target = adapters.pop()
        if not adapters:
            del _hook_adapters[fn]
    try:
        _op_hooks.remove(target)
    except ValueError:
        pass


def _check_nan_inf(op_name, outs):
    for o in outs:
        if not isinstance(o, (jax.Array, np.ndarray)):
            continue  # SOT LazyArray / tracer: checked when materialized
        d = np.dtype(o.dtype)
        if np.issubdtype(d, np.floating) or d == dtypes.bfloat16:
            bad = bool(jnp.any(~jnp.isfinite(o)))  # tpulint: disable=TPU103 — FLAGS_check_nan_inf debugging sweep: the per-op host sync IS the feature (default off)
            if bad:
                level = flags.get_flag("check_nan_inf_level")
                msg = f"NaN or Inf found in output of op '{op_name}'"
                if level == 0:
                    raise FloatingPointError(msg)
                print(f"[paddle_tpu][nan_inf] {msg}")


# ---------------------------------------------------------------------------
# Eager compiled-lowering cache: steady-state eager ops run as cached
# jax.jit programs instead of unamortized JAX eager dispatch (reference
# bar: the generated C++ ad_func path, eager_gen.py:301, is µs-level).
# A lowering is cacheable only when its closure is fully described by
# primitives — anything value-opaque (arrays, objects) falls back to
# plain eager so a stale compile can never be served.
# ---------------------------------------------------------------------------
_EAGER_JIT_MAX = 1024
#: eager executions of a key before the compiled lowering is installed —
#: steady-state loops amortize one compile, while code that touches an
#: op only a handful of times never pays XLA compilation for it
_JIT_AFTER = 3
_eager_jit_cache: Dict = {}   # (op, closure key) -> count | jitted | False

_PRIM_TYPES = (int, float, bool, str, bytes, complex, type(None))


def _const_key(v, depth: int):
    """Hashable key fully describing a closed-over constant, or None if
    the value cannot be exactly keyed (= uncacheable)."""
    if isinstance(v, _PRIM_TYPES):
        # type-qualified: 2, 2.0 and True hash/compare equal in python,
        # but bake into DIFFERENT compiled programs (dtype promotion)
        return (type(v).__name__, v)
    if isinstance(v, (np.integer, np.floating, np.bool_)):
        return ("nps", type(v).__name__, v.item())
    if isinstance(v, np.dtype):
        return ("dt", str(v))
    if isinstance(v, (tuple, list)):
        out = []
        for x in v:
            k = _const_key(x, depth - 1) if depth > 0 else None
            if k is None and x is not None:
                return None
            out.append(k)
        return ("seq", tuple(out))
    if isinstance(v, dict):
        if depth <= 0:
            return None
        try:
            items = sorted(v.items())
        except TypeError:
            return None
        out = []
        for key, x in items:
            k = _const_key(x, depth - 1)
            if k is None and x is not None:
                return None
            out.append((key, k))
        return ("map", tuple(out))
    if callable(v):
        return _closure_cache_key(v, depth - 1)
    return None


def _closure_cache_key(f, depth: int = 3):
    """Key of a lowering = code identity + every closure/default value;
    None when any captured value is not exactly keyable."""
    if depth < 0:
        return None
    import functools
    if isinstance(f, functools.partial):
        sub = _closure_cache_key(f.func, depth - 1)
        ar = _const_key(tuple(f.args), depth - 1)
        kw = _const_key(f.keywords or {}, depth - 1)
        if sub is None or ar is None or kw is None:
            return None
        return ("partial", sub, ar, kw)
    if isinstance(f, np.ufunc) or type(f).__module__.startswith(
            ("jax.", "numpy")):
        # stateless callable objects (np/jnp ufuncs, jitted wrappers):
        # identity-keyed; the key tuple holds a strong ref so the id
        # cannot be recycled
        return ("uf", f)
    if getattr(f, "__self__", None) is not None:
        # bound method: behavior can depend on mutable receiver state the
        # closure walk cannot see — never cache
        return None
    code = getattr(f, "__code__", None)
    if code is None:
        return None
    parts: List = [code.co_filename, code.co_firstlineno, code.co_name]
    for cell in getattr(f, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:
            return None
        k = _const_key(v, depth - 1)
        if k is None and v is not None:
            return None
        parts.append(k)
    for d in getattr(f, "__defaults__", None) or ():
        k = _const_key(d, depth - 1)
        if k is None and d is not None:
            return None
        parts.append(k)
    # keyword-only defaults carry real state: the AMP wrapper binds the
    # true lowering as __inner=... — missing these would key every
    # AMP-wrapped op of a name to one compiled program
    kwd = getattr(f, "__kwdefaults__", None) or {}
    for kname in sorted(kwd):
        k = _const_key(kwd[kname], depth - 1)
        if k is None and kwd[kname] is not None:
            return None
        parts.append((kname, k))
    return tuple(parts)


def _all_jax_arrays(outs) -> bool:
    seq = outs if isinstance(outs, (tuple, list)) else [outs]
    return all(isinstance(o, jax.Array) for o in seq)


def _jit_cached_call(op_name: str, f: Callable, arrays):
    """Execute an eager lowering through the compiled cache. First sight
    of a key runs eagerly (verifying the outputs are pure jax arrays) and
    installs the jitted entry; later calls hit jax.jit's C++ fast path —
    jit's own aval cache handles shape/dtype polymorphism under one
    entry."""
    metered = _hot_flags["enable_metrics"]
    key0 = _closure_cache_key(f)
    if key0 is None:
        if metered:
            _m_eager_jit.inc(event="uncacheable")
        return f(*arrays)
    key = (op_name, key0)
    ent = _eager_jit_cache.get(key)
    if ent is False:
        if metered:
            _m_eager_jit.inc(event="bypass")
        return f(*arrays)
    if ent is None or isinstance(ent, int):
        outs = f(*arrays)
        if ent is None:
            if metered:
                _m_eager_jit.inc(event="miss")
            if len(_eager_jit_cache) >= _EAGER_JIT_MAX:
                _eager_jit_cache.pop(next(iter(_eager_jit_cache)))
            _eager_jit_cache[key] = (1 if _all_jax_arrays(outs)
                                     else False)
        elif ent + 1 >= _JIT_AFTER:
            if metered:
                _m_eager_jit.inc(event="compile")
            _eager_jit_cache[key] = jax.jit(f)
        else:
            if metered:
                _m_eager_jit.inc(event="warmup")
            _eager_jit_cache[key] = ent + 1
        return outs
    if metered:
        _m_eager_jit.inc(event="hit")
    return ent(*arrays)


def _lazy_vjp(f, arrays):
    """Deferred vjp: linearize only when the tape backward actually runs
    (the primal recomputes inside jax.vjp then — remat-style, so forward
    dispatch never pays for a backward that may never happen)."""
    state = {}

    def vjp_fn(cts):
        if "vjp" not in state:
            # SOT LazyArray payloads must be concretized explicitly —
            # jax no longer honors __jax_array__ during abstractification
            concrete = [a.__jax_array__() if hasattr(a, "__jax_array__")
                        else a for a in arrays]
            _, state["vjp"] = jax.vjp(f, *concrete)
        return state["vjp"](cts)

    return vjp_fn


def call(op_name: str, fn: Callable, tensor_inputs: Sequence[Tensor],
         attrs: Optional[dict] = None, multi_output: bool = False,
         differentiable_mask: Optional[Sequence[bool]] = None,
         export_attrs: Optional[dict] = None):
    """Run one op: ``fn(*arrays, **attrs)`` over the payloads of
    ``tensor_inputs``, recording a GradNode when grad is enabled and any
    input requires grad. Returns Tensor or list of Tensors.
    ``export_attrs`` carries the op's semantic parameters for interchange
    tracers (ONNX export) — it never affects execution."""
    global _sot
    attrs = attrs or {}
    s = _tls()
    if s.branch_trace is not None:
        # control-flow branch discovery: nothing executes — the trace
        # records the op abstractly (shapes via jax.eval_shape) and logs
        # which external Tensors the branch reads
        return s.branch_trace.run_op(op_name, fn, tensor_inputs, attrs)
    if GradNode is None:
        _bind_engine()

    # Telemetry gate: one list truthiness + two dict lookups when every
    # channel is off — the disabled path never reads the clock.
    timed = (bool(_op_hooks) or _hot_flags["enable_metrics"]
             or _trace._active["on"]) and not s.quiet
    t0 = _perf_counter() if timed else 0.0

    arrays = [t._data for t in tensor_inputs]
    if _sot is not None and not _sot.active():
        # payloads that escaped an earlier SOT capture concretize here
        # (jax no longer coerces via __jax_array__ automatically)
        arrays = [a.concrete() if type(a) is _sot.LazyArray else a
                  for a in arrays]
    amp_cast = _amp_cast_inputs(op_name, arrays)
    if amp_cast is not arrays:
        # fold the AMP cast INTO the differentiated function so vjp
        # cotangents keep the ORIGINAL input/output dtypes — an out-of-band
        # cast would hand consumers mismatched-dtype cotangents
        inner, targets = fn, [a.dtype for a in amp_cast]

        def fn(*xs, __inner=inner, __targets=targets, **kw):
            cast = [x.astype(d) if hasattr(x, "astype") and x.dtype != d
                    else x for x, d in zip(xs, __targets)]
            return __inner(*cast, **kw)

    requires = [
        (not t.stop_gradient) and (differentiable_mask[i] if differentiable_mask else True)
        for i, t in enumerate(tensor_inputs)
    ]
    record = s.grad_enabled and any(requires)

    if attrs:
        f = lambda *xs: fn(*xs, **attrs)
    else:
        f = fn

    node = None
    traced = any(isinstance(a, jax.core.Tracer) for a in arrays)
    sot_rec = None
    if not traced:
        if _sot is None:
            from ..jit import sot as _sot_mod
            _sot = _sot_mod
        if _sot.active():
            sot_rec = _sot.record_or_none(op_name, f, arrays, attrs)
    if sot_rec is not None:
        # SOT lazy capture: the op joined the pending segment graph; its
        # outputs are LazyArrays that materialize at the next graph break.
        lazies, sot_multi = sot_rec
        outs = list(lazies) if sot_multi else lazies[0]
        vjp_fn = _lazy_vjp(f, arrays) if record else None
    else:
        if _sot is not None and any(type(a) is _sot.LazyArray
                                    for a in arrays):
            # implicit SOT break (shape inference refused the op): the
            # segment was flushed; run on the materialized values — jax
            # rejects LazyArray wrappers during abstractification
            arrays = [a.concrete() if type(a) is _sot.LazyArray else a
                      for a in arrays]
        # Eager linearization here would be wasted work whenever backward
        # never runs, and under an outer jax transform it also breaks
        # custom_vjp kernels (second-order AD). Compute the primal only;
        # if the tape IS walked, derive the vjp lazily then (the primal is
        # recomputed inside jax.vjp at that point — remat-style).
        if traced or not _hot_flags["eager_jit_cache"]:
            # under an outer trace, injecting nested jit boundaries would
            # fragment the caller's XLA fusion — run the lowering inline
            outs = f(*arrays)
        else:
            outs = _jit_cached_call(op_name, f, arrays)
        vjp_fn = _lazy_vjp(f, arrays) if record else None

    out_tuple = isinstance(outs, (tuple, list))
    single = not out_tuple
    out_list = [outs] if single else list(outs)

    if record:
        edges = []
        for t, req in zip(tensor_inputs, requires):
            if not req:
                edges.append((None, 0))
            elif t.grad_node is not None:
                edges.append((t.grad_node, t.output_index))
            else:
                if getattr(t, "_accum_node", None) is None:
                    t._accum_node = AccumulationNode(t)
                edges.append((t._accum_node, 0))
        node = GradNode(
            op_name, vjp_fn, edges,
            [(o.shape, np.dtype(o.dtype)) for o in out_list],
            requires, out_tuple=out_tuple,
            primal_fn=f, saved_inputs=list(tensor_inputs),
        )

    out_tensors = []
    for i, o in enumerate(out_list):
        t = Tensor(o, stop_gradient=not record)
        if node is not None:
            t.grad_node = node
            t.output_index = i
        out_tensors.append(t)

    if not s.quiet:
        if _hot_flags["check_nan_inf"]:
            _check_nan_inf(op_name, out_list)
        if _hot_flags["benchmark"]:
            for o in out_list:
                if isinstance(o, jax.Array):
                    jax.block_until_ready(o)
        dur = 0.0
        if timed:
            # a channel that flipped on mid-call reports from the NEXT op
            # (t0 predates the flip, so its span/metric would be garbage)
            dur = _perf_counter() - t0
            if _hot_flags["enable_metrics"]:
                _m_op_latency.observe(dur, op=op_name)
                if _hot_flags["perf_op_cost"]:
                    _accumulate_op_cost(op_name, arrays, attrs, out_list)
            if _trace._active["on"]:
                _trace.add_complete(op_name, "dispatch", t0, t0 + dur)
        rec_hooks = _recorder_hooks()
        th0 = _perf_counter() if (
            timed and _hot_flags["enable_metrics"]
            and (_op_hooks or rec_hooks or _export_hooks)) else 0.0
        for hook in _op_hooks:
            hook(op_name, tensor_inputs, out_tensors, attrs, dur)
        for hook in rec_hooks:
            # recorder taps (static.Program capture, spmd propagation)
            # additionally receive the attr-bound lowering so the op can
            # be replayed on new payloads, plus the semantic attrs the
            # sharding rules key on (axis/transpose/keepdim/...)
            hook(op_name, f, tensor_inputs, out_tensors, attrs)
        if _export_hooks:
            merged = dict(attrs)
            if export_attrs:
                merged.update(export_attrs)
            for hook in _export_hooks:
                hook(op_name, tensor_inputs, out_tensors, merged)
        if th0:
            _m_hook_overhead.observe(_perf_counter() - th0)

    if single:
        return out_tensors[0]
    return out_tensors


def wrap_hooks_into_tensor(t: Tensor, hook):
    """Attach a grad hook to a non-leaf tensor: store it on its producer node."""
    node = t.grad_node
    node.output_hooks.setdefault(t.output_index, []).append(hook)


def retain_grad_for(t: Tensor):
    if t.grad_node is not None:
        t.grad_node.retain_outputs[t.output_index] = t
