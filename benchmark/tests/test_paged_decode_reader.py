"""CPU tests of ``paged_decode_attn_roofline_pct`` on a small trace recorded
on the chip with the decode kernel in it
(``lib/testdata/program_serve_kernel.xplane.pb``: 0.06 s of the
``tiny_kernel`` preset through Router -> PagedEngine, the generator's tick
records beside it in ``program_serve_kernel.window.json``), and on traces
that hold nothing for it. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.layer_metrics import (  # noqa: E402
    paged_decode_attn_roofline_pct as reader)
from benchmark.lib import flops, harness, program_spans, trace_reduce  # noqa: E402
from benchmark.tests import tiny, tiny_hybrid, tiny_kernel  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
NAME = "paged_decode_attn_roofline_pct"


def ctx_of(monkeypatch, file, config, window=None):
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return {"kind": "serve", "trace": trace_reduce.reduce(path),
            "config": config, "device_kind": "TPU v5 lite",
            "window": window or {"ticks": [], "trace_tick0": None}}


def recorded_window():
    with open(os.path.join(DATA, "program_serve_kernel.window.json")) as f:
        return json.load(f)


def test_the_kernel_is_told_by_its_name():
    assert reader.is_kernel(
        "%paged_decode_attn.3 tpu_custom_call/5 (bf16[32,32,128])")
    assert reader.is_kernel("%paged_decode_attn tpu_custom_call/5 ()")
    assert not reader.is_kernel("%flash_fwd.3 tpu_custom_call/3 ()")
    assert not reader.is_kernel("%fusion.21 fusion")
    assert not reader.is_kernel("%paged_decode_attn_x.1 fusion")


def test_bytes_and_operations_against_hand_counts():
    mis = harness.load_json(ROOT, "benchmark", "configs",
                            "mistral-7b-v0.3-L16.json")
    # 16 layers x (K + V) x 8 heads x 128 x 2 bytes a token; a query row
    # in and an output row out: 32 heads x 128 x 2 bytes, 16 layers
    assert reader.step_bytes(mis, 0, 1000) == 1000 * 16 * 2 * 8 * 128 * 2
    assert reader.step_bytes(mis, 32, 0) == 16 * 2 * 32 * 32 * 128 * 2
    assert reader.step_bytes(mis, 32, 9600) == (
        9600 * flops.kv_bytes_per_token(mis) + 16 * 2 * 32 * 32 * 128 * 2)
    # QK^T and PV: 2 x 2 x 32 heads x 128 a cached token a layer
    assert reader.step_flops(mis, 9600) == 2 * 2 * 32 * 128 * 16 * 9600
    # bound by bytes at every length: 4 KiB against 16 Kflop a token a layer
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert (reader.step_bytes(mis, 32, 9600) / pk["hbm_bytes_per_s"]
            > reader.step_flops(mis, 9600) / pk["bf16_flops"])


def test_reader_on_the_recorded_kernel_trace(monkeypatch):
    ctx = ctx_of(monkeypatch, "program_serve_kernel.xplane.pb",
                 tiny_kernel.LLAMA, recorded_window())
    first = ctx["trace"]["devices"][sorted(ctx["trace"]["devices"])[0]]
    kernels = [n for n, _s, _e in first["ops"] if reader.is_kernel(n)]
    steps = [n for n, _s, _e in first["modules"]
             if n.startswith("jit_paged_decode_step(")]
    # once an attention layer a decode step
    assert kernels and len(kernels) == (
        tiny_kernel.LLAMA["num_hidden_layers"] * len(steps))
    got = harness.read_layer_metric(NAME, ctx)
    # a few hundred tokens a step are nowhere near the roofline; the share
    # bills live tokens only, so it is a share
    assert 0 < got < 100
    # the scope both paths sit under still reads
    assert harness.read_layer_metric("paged_attn_share_pct", ctx) > 0


@pytest.mark.parametrize("file,config", [
    ("program_serve.xplane.pb", tiny.LLAMA),             # the composite
    ("program_serve_hybrid.xplane.pb", tiny_hybrid.NEMOTRON),
    ("small_trace.xplane.pb", tiny.LLAMA)])              # no spans at all
def test_nothing_to_read_gives_none(monkeypatch, file, config):
    """The parent's program attends through the composite: its trace holds
    no ``paged_decode_attn`` and the metric is left out of the line."""
    win = recorded_window()
    ctx = ctx_of(monkeypatch, file, config, win)
    assert harness.read_layer_metric(NAME, ctx) is None
    for ctx in ({"kind": "serve", "trace": None, "config": tiny.LLAMA},
                {"kind": "fit", "trace": None, "config": {}},
                {"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}},
                 "config": tiny.LLAMA}):
        assert harness.read_layer_metric(NAME, ctx) is None
