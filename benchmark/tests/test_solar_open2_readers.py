"""CPU tests of the readers the Solar Open 2 serving cell brought
(``kda_step_roofline_pct``, ``kda_share_pct``, ``solar_tick_roofline_pct``)
on a small trace recorded on the chip
(``lib/testdata/program_serve_solar.xplane.pb``: 0.08 s of the tiny
``solar_open2`` ``KERNEL`` preset, linear heads of 128 x 128 and attention
heads of 128, through Router -> PagedEngine, both kernels in the programs
that hold a decode step, with the generator's tick records and the
configuration beside it in ``program_serve_solar.window.json``), and on
traces that hold nothing for them. Run by hand:

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

from benchmark.lib import harness, program_spans, trace_reduce  # noqa: E402
from benchmark.tests import tiny_solar_open2  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
NEW = ["kda_step_roofline_pct", "kda_share_pct", "solar_tick_roofline_pct"]
SHARED = ["prefill_chunk_device_ms", "mixed_step_device_ms",
          "idle_attributed_pct_serve", "moe_expert_load_max_over_mean"]


def recorded_window():
    with open(os.path.join(DATA, "program_serve_solar.window.json")) as f:
        return json.load(f)


def ctx_of(monkeypatch, file, window=None, config=None):
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return {"kind": "serve", "trace": trace_reduce.reduce(path),
            "config": config or tiny_solar_open2.KERNEL,
            "device_kind": "TPU v5 lite", "window": window or {
                "ticks": [], "trace_tick0": None}}


def test_readers_on_the_recorded_solar_open2_trace(monkeypatch):
    win = recorded_window()
    ctx = ctx_of(monkeypatch, "program_serve_solar.xplane.pb", window=win,
                 config=win["config"])
    rec = program_spans.recording(ctx)
    programs = {n.split("(")[0] for n, _s, _e in rec["modules"]}
    assert "jit_paged_mixed_step" in programs
    scopes = " ".join(rec["scopes"].values())
    for scope in ("attn.linear/attn.linear.proj",
                  "attn.linear/attn.linear.conv",
                  "attn.linear/attn.linear.rule",
                  "attn.linear/attn.linear.norm", "attn.full",
                  "moe/moe.router",
                  "moe/moe.experts", "moe/moe.shared", "lm_head"):
        assert f"/{scope}/" in scopes, scope
    # (``attn.full.gate`` names no operation of its own here: the compiler
    # fused the sigmoid and the product into a neighbour, and a fusion is
    # billed to the one ``op_name`` it keeps)
    # both kernels are in the traced programs, under their own names
    kernels = {program_spans.kernel_name(n) for n, _s, _e in rec["ops"]}
    assert {"delta_rule_step", "paged_decode_attn"} <= kernels
    got = {name: harness.read_layer_metric(name, ctx)
           for name in NEW + SHARED}
    # three KDA layers of four: a large part of the programs, not all
    assert 10 < got["kda_share_pct"] < 100
    # a tiny model is nowhere near its roofline; a share is still a share
    assert 0 < got["kda_step_roofline_pct"] < 100
    assert 0 < got["solar_tick_roofline_pct"] < 100
    assert got["mixed_step_device_ms"] > 0
    assert 0 < got["idle_attributed_pct_serve"] <= 100
    assert got["moe_expert_load_max_over_mean"] >= 1.0


def test_costs_are_billed_by_program():
    from benchmark.layer_metrics import solar_tick_roofline_pct as tick
    from benchmark.lib import flops_solar_open2 as fl
    cfg = dict(tiny_solar_open2.KERNEL)
    width = tick.chunk_width(cfg)
    assert width == 16          # the preset's budget, below the widest 256
    assert tick.chunk_width({"engine": {}}) == 256
    chunk, mixed, step = tick.PROGRAMS
    assert tick.call_cost(cfg, chunk, 3, 100) == (
        fl.prefill_chunk_flops(cfg, width, width),
        fl.prefill_chunk_bytes(cfg, width, width))
    assert tick.call_cost(cfg, step, 3, 100) == (
        fl.decode_step_flops(cfg, 3, 100), fl.decode_step_bytes(cfg, 3, 100))
    ops, nbytes = tick.call_cost(cfg, mixed, 3, 100)
    assert ops == fl.prefill_chunk_flops(cfg, width, width) \
        + fl.decode_step_flops(cfg, 3, 100)
    assert nbytes < fl.prefill_chunk_bytes(cfg, width, width) \
        + fl.decode_step_bytes(cfg, 3, 100)     # ONE stream of the weights
    state = 16 * 128 * 128 * 4
    assert fl.rule_state_bytes(cfg) == state
    assert fl.rule_decode_bytes(cfg, 3) == 3 * (2 * state + 5 * 2048 * 4)
    assert fl.counts(cfg) == {"full": 1, "kda": 3}


@pytest.mark.parametrize("file", ["program_serve.xplane.pb",
                                  "program_serve_olmo.xplane.pb",
                                  "small_trace.xplane.pb"])
def test_nothing_to_read_gives_none(monkeypatch, file):
    """Another architecture's trace under its own configuration; the same
    trace under THIS configuration (Olmo-Hybrid's holds ``attn.linear`` and
    ``delta_rule_step``, but its ticks are not this cell's: no window, so
    nothing to bill); a trace without the program's spans. None, never an
    exception: the parent is measured with these readers laid over it."""
    ctx = ctx_of(monkeypatch, file, config={"arch": "olmo_hybrid"})
    assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
        == dict.fromkeys(NEW)
    ctx = ctx_of(monkeypatch, file)
    assert harness.read_layer_metric("kda_step_roofline_pct", ctx) is None
    assert harness.read_layer_metric("solar_tick_roofline_pct", ctx) is None
    if file != "program_serve_olmo.xplane.pb":
        assert harness.read_layer_metric("kda_share_pct", ctx) is None
    for ctx in ({"kind": "serve", "trace": None, "config": {}},
                {"kind": "fit", "trace": None, "config": {}},
                {"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}},
                 "config": tiny_solar_open2.KERNEL}):
        assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
            == dict.fromkeys(NEW)
