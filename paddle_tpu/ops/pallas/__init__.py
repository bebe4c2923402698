"""Pallas TPU kernels for the hot ops.

TPU-native replacement for the reference's hand-written fused CUDA kernels
(reference: paddle/phi/kernels/fusion/gpu/ and third_party/flashattn). Only
the truly bandwidth/latency-critical ops get kernels here — everything else
is left to XLA fusion. ``paged_attention`` is the serving decode step's
attention over the pages a lane holds; ``serving`` holds the serving tier's
in-graph helpers (int8 KV page (de)quant, the speculative-decode
accept-prefix step) that the paged-attention op and engine verify program
compose.
"""
from __future__ import annotations

import sys
import threading

_import_lock = threading.Lock()


def import_pallas():
    """``(pl, pltpu)``: Pallas and its TPU backend, for every kernel module
    of this package.

    Importing Pallas also imports, where it can, the interpreter of its GPU
    backend and with it Mosaic GPU's dialects: 0.76 of the import's 1.26 s
    on a v5e host. A serving process imports Pallas when it traces its decode
    program, inside the seconds before its first token: with the plain import
    the hybrid serving cell's set-up grew by 1.5 s of 15.5 against a bound of
    a tenth, with this by 0.7 (PERF.md section 6, PR 27). No kernel here runs
    on a GPU, and ``pallas_call`` is written to do without that interpreter
    (it catches the ImportError, as on a jaxlib built without Mosaic GPU). So
    that one optional import is made to fail while Pallas loads, the
    documented way (``sys.modules[name] = None``), and the entry is taken out
    again. What it costs: in such a process ``pallas_call(interpret=
    mosaic_gpu.InterpretParams(...))`` is not available. A process that
    holds Pallas already gets it as it is.
    """
    optional = "jax._src.pallas.mosaic_gpu.interpret"
    with _import_lock:
        block = not any(name in sys.modules for name in
                        ("jax.experimental.pallas", optional))
        if block:
            sys.modules[optional] = None
        try:
            from jax.experimental import pallas
            from jax.experimental.pallas import tpu
        finally:
            if block:
                del sys.modules[optional]
    return pallas, tpu
