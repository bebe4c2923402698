"""paddle_tpu: a TPU-native deep learning framework.

Brand-new design with the capabilities of the PaddlePaddle reference
(define-by-run autograd, static capture, hybrid-parallel distributed
training), built on JAX/XLA/Pallas idioms: ops are jax lowerings fused by
XLA, the autograd tape records jax VJP closures, program capture jits whole
train steps, and parallelism is expressed over a jax.sharding.Mesh with
XLA collectives on ICI/DCN.
"""
from __future__ import annotations

import time as _time

_T_FIRST_LINE = _time.perf_counter()    # startup.import's "before" mark

import importlib

__version__ = "0.1.0"

from .core import dtype as _dtype_mod
from .core.dtype import (bfloat16, bool_ as bool8, complex64, complex128, float16,
                         float32, float64, int8, int16, int32, int64, uint8,
                         float8_e4m3fn, float8_e5m2, iinfo, finfo,
                         get_default_dtype, set_default_dtype)
from .core.tensor import Tensor, as_tensor, is_tensor

import numpy as _np

#: paddle.dtype / paddle.bool — our dtypes ARE numpy dtype instances, so
#: the dtype "class" is np.dtype (isinstance(paddle.float32, paddle.dtype)
#: holds, matching the reference contract)
dtype = _np.dtype
bool = bool8  # noqa: A001 - reference exports `paddle.bool`
from .core.dispatch import no_grad, enable_grad, set_grad_enabled_ctx as set_grad_enabled
from .core.generator import seed, get_rng_state, set_rng_state, Generator
from .core.flags import get_flags, set_flags, define_flag
from .core.place import (CPUPlace, CustomPlace, Place, TPUPlace, device_count,
                         get_device, is_compiled_with_tpu, set_device)
from .core import enforce

# Op surface (also attaches Tensor methods).
from .ops import *  # noqa: F401,F403
from .ops import creation as _creation
from .ops.creation import to_tensor
from .autograd import backward, grad, is_grad_enabled, PyLayer
from .batch import batch

CUDAPlace = TPUPlace  # source-compat alias: accelerator place
CUDAPinnedPlace = CPUPlace  # pinned host memory: host-side here


def shape(x):
    """Shape of ``x`` as an int32 tensor (reference paddle.shape)."""
    return to_tensor(_np.asarray(x.shape, _np.int32))


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor print formatting (reference set_printoptions); applies to
    the numpy formatter Tensor.__repr__ uses."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def disable_signal_handler():
    """No-op (reference disables C++ fault handlers; none here)."""


def check_shape(shape_val, op_name="", expected_element_type=(int,)):
    """Shape validation helper (reference base/data_feeder.py
    check_shape: a shape is a list/tuple of ints or an int tensor)."""
    if isinstance(shape_val, Tensor):
        return
    if not isinstance(shape_val, (list, tuple)):
        raise TypeError(
            f"{op_name}: shape must be list/tuple/Tensor, got "
            f"{type(shape_val)}")
    for item in shape_val:
        if not isinstance(item, expected_element_type + (Tensor,)):
            raise TypeError(
                f"{op_name}: shape element must be int/Tensor, got "
                f"{type(item)}")


def flops(net, input_size=None, custom_ops=None, print_detail=False,
          inputs=None):
    from .hapi.dynamic_flops import flops as _flops
    return _flops(net, input_size, custom_ops=custom_ops,
                  print_detail=print_detail, inputs=inputs)


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_distribute():
    return True


def in_dynamic_mode():
    from .jit.api import in_capture_mode
    return not in_capture_mode()


def disable_static(place=None):
    return None


def enable_static():
    return None


def save(obj, path, protocol=4, **kwargs):
    from .framework.io import save as _save
    return _save(obj, path, protocol=protocol, **kwargs)


def load(path, **kwargs):
    from .framework.io import load as _load
    return _load(path, **kwargs)


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes=dtypes, input=input)


_LAZY_MODULES = {
    "nn", "optimizer", "amp", "io", "jit", "distributed", "vision", "metric", "fault",
    "profiler", "observability", "autograd", "incubate", "framework", "device", "static", "hapi",
    "distribution", "linalg", "fft", "signal", "sparse", "text", "onnx", "quantization",
    "models", "utils", "inference", "native", "audio", "geometric",
    "strings", "hub", "regularizer", "version", "sysconfig",
}

#: top-level names resolved lazily from submodules (avoids importing
#: hapi/nn at package import)
_LAZY_ATTRS = {
    "Model": ("paddle_tpu.hapi.model", "Model"),
    "callbacks": ("paddle_tpu.hapi", "callbacks"),
    "LazyGuard": ("paddle_tpu.nn.lazy_init", "LazyGuard"),
    "ParamAttr": ("paddle_tpu.nn.parameter", "ParamAttr"),
    "create_parameter": ("paddle_tpu.nn.parameter", "create_parameter"),
    "DataParallel": ("paddle_tpu.distributed.parallel", "DataParallel"),
    "get_cuda_rng_state": ("paddle_tpu.framework.random",
                           "get_cuda_rng_state"),
    "set_cuda_rng_state": ("paddle_tpu.framework.random",
                           "set_cuda_rng_state"),
}


def __getattr__(name):
    if name in _LAZY_MODULES:
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name in _LAZY_ATTRS:
        mod_name, attr = _LAZY_ATTRS[name]
        value = getattr(importlib.import_module(mod_name), attr)
        globals()[name] = value
        return value
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


# the start-up record's first entry: OS process start to this line
from .observability import trace as _trace  # noqa: E402
_trace.note_import(_T_FIRST_LINE)
