"""CPU tests of the readers the Olmo-Hybrid serving cell brought
(``linear_attn_share_pct``, ``delta_rule_decode_roofline_pct``,
``mha_decode_attn_roofline_pct``, ``olmo_hybrid_decode_roofline_pct``) on a
small trace recorded on the chip
(``lib/testdata/program_serve_olmo.xplane.pb``: 0.08 s of the tiny
``olmo_hybrid`` preset at two attention heads of 128 through Router ->
PagedEngine, both kernels in the decode program, with the generator's tick
records and the configuration beside it in
``program_serve_olmo.window.json``), and on traces that hold nothing for
them. Run by hand:

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
from benchmark.tests import tiny_olmo_hybrid  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
NEW = ["linear_attn_share_pct", "delta_rule_decode_roofline_pct",
       "mha_decode_attn_roofline_pct", "olmo_hybrid_decode_roofline_pct"]
SHARED = ["decode_step_device_ms", "prefill_chunk_device_ms",
          "full_attn_share_pct", "idle_attributed_pct_serve"]


def recorded_window():
    with open(os.path.join(DATA, "program_serve_olmo.window.json")) as f:
        return json.load(f)


def ctx_of(monkeypatch, file, window=None, config=None):
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return {"kind": "serve", "trace": trace_reduce.reduce(path),
            "config": config or tiny_olmo_hybrid.CFG,
            "device_kind": "TPU v5 lite", "window": window or {
                "ticks": [], "trace_tick0": None}}


def test_readers_on_the_recorded_olmo_hybrid_trace(monkeypatch):
    win = recorded_window()
    ctx = ctx_of(monkeypatch, "program_serve_olmo.xplane.pb", window=win,
                 config=win["config"])
    rec = program_spans.recording(ctx)
    programs = {n.split("(")[0] for n, _s, _e in rec["modules"]}
    assert "jit_paged_decode_step" in programs
    scopes = " ".join(rec["scopes"].values())
    for scope in ("attn.linear/attn.linear.proj", "attn.linear/attn.linear.conv",
                  "attn.linear/attn.linear.rule",
                  "attn.linear/attn.linear.norm", "attn.full", "mlp",
                  "lm_head"):
        assert f"/{scope}/" in scopes, scope
    # both kernels are in the decode program, under their own names
    kernels = {program_spans.kernel_name(n) for n, _s, _e in rec["ops"]}
    assert {"delta_rule_step", "paged_decode_attn"} <= kernels
    got = {name: harness.read_layer_metric(name, ctx)
           for name in NEW + SHARED}
    # six linear layers of eight: most of a tiny decode step, not all
    assert 0 < got["full_attn_share_pct"] < got["linear_attn_share_pct"] < 100
    assert got["linear_attn_share_pct"] + got["full_attn_share_pct"] < 100
    # a tiny model is nowhere near its roofline; a share is still a share
    for name in ("delta_rule_decode_roofline_pct",
                 "mha_decode_attn_roofline_pct",
                 "olmo_hybrid_decode_roofline_pct"):
        assert 0 < got[name] < 100, name
    assert got["decode_step_device_ms"] > 0
    assert 0 < got["idle_attributed_pct_serve"] <= 100


def test_the_rule_is_billed_once_a_lane_a_layer():
    from benchmark.lib import flops_olmo_hybrid as fl
    cfg = tiny_olmo_hybrid.CFG
    state = 6 * 8 * 64 * 4
    assert fl.rule_state_bytes(cfg) == state
    assert fl.rule_decode_bytes(cfg, 3) == 3 * (
        2 * state + (2 * 48 + 2 * 384) * 4)
    assert fl.counts(cfg) == {"linear_attention": 6, "full_attention": 2}


@pytest.mark.parametrize("file", ["program_serve.xplane.pb",
                                  "program_serve_hybrid.xplane.pb",
                                  "small_trace.xplane.pb"])
def test_nothing_to_read_gives_none(monkeypatch, file):
    """Another architecture's trace has no ``attn.linear`` scope and its
    configuration is not this one's; the parent's program names no such
    scope even under this configuration; a trace without the program's
    spans gives nothing at all. None, never an exception: the parent is
    measured with these readers laid over it."""
    ctx = ctx_of(monkeypatch, file, config={"arch": "llama_like"})
    assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
        == dict.fromkeys(NEW)
    win = recorded_window()
    ctx = ctx_of(monkeypatch, file, window=win, config=win["config"])
    assert harness.read_layer_metric("linear_attn_share_pct", ctx) is None
    assert harness.read_layer_metric(
        "delta_rule_decode_roofline_pct", ctx) is None
    for ctx in ({"kind": "serve", "trace": None, "config": {}},
                {"kind": "fit", "trace": None, "config": {}},
                {"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}},
                 "config": tiny_olmo_hybrid.CFG}):
        assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
            == dict.fromkeys(NEW)
