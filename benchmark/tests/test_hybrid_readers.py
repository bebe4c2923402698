"""CPU tests of the readers the hybrid serving cell brought
(``hybrid_decode_roofline_pct``, ``moe_share_pct``, ``mamba_share_pct``,
``moe_expert_load_max_over_mean``) on a small trace recorded on the chip
(``lib/testdata/program_serve_hybrid.xplane.pb``: 0.06 s of the tiny
``nemotron_h`` preset through Router -> PagedEngine, with the generator's
tick records beside it in ``program_serve_hybrid.window.json``), and on
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
from benchmark.tests import tiny_hybrid  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
NEW = ["hybrid_decode_roofline_pct", "moe_share_pct", "mamba_share_pct",
       "moe_expert_load_max_over_mean"]
SHARED = ["decode_step_device_ms", "prefill_chunk_device_ms",
          "idle_attributed_pct_serve"]


def ctx_of(monkeypatch, file, window=None, config=None):
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return {"kind": "serve", "trace": trace_reduce.reduce(path),
            "config": config or tiny_hybrid.NEMOTRON,
            "device_kind": "TPU v5 lite", "window": window or {
                "ticks": [], "trace_tick0": None}}


def recorded_window():
    with open(os.path.join(DATA, "program_serve_hybrid.window.json")) as f:
        return json.load(f)


def test_readers_on_the_recorded_hybrid_trace(monkeypatch):
    win = recorded_window()
    ctx = ctx_of(monkeypatch, "program_serve_hybrid.xplane.pb", window=win)
    rec = program_spans.recording(ctx)
    programs = {n.split("(")[0] for n, _s, _e in rec["modules"]}
    assert {"jit_paged_prefill_chunk", "jit_paged_decode_step"} <= programs
    scopes = " ".join(rec["scopes"].values())
    for scope in ("mamba", "attn", "moe/moe.router", "moe/moe.experts",
                  "moe/moe.shared", "lm_head"):
        assert f"/{scope}/" in scopes, scope
    got = {name: harness.read_layer_metric(name, ctx)
           for name in NEW + SHARED}
    # the two kinds of mixer are most of a decode step, and not all of it
    assert 0 < got["moe_share_pct"] < 100
    assert 0 < got["mamba_share_pct"] < 100
    assert got["moe_share_pct"] + got["mamba_share_pct"] < 100
    # a tiny model is nowhere near its roofline; the share is still a share
    assert 0 < got["hybrid_decode_roofline_pct"] < 100
    assert got["moe_expert_load_max_over_mean"] >= 1.0
    assert got["decode_step_device_ms"] > 0
    assert got["prefill_chunk_device_ms"] > 0
    assert 0 < got["idle_attributed_pct_serve"] <= 100


def test_expert_load_is_the_worst_layers_max_over_mean():
    read = lambda load: harness.read_layer_metric(  # noqa: E731
        "moe_expert_load_max_over_mean", {"window": {"expert_load": load}})
    assert read([[5, 5, 5, 5], [1, 1, 1, 9]]) == pytest.approx(3.0)
    assert read([[2, 2], [0, 0]]) == pytest.approx(1.0)
    assert read([[0, 0]]) is None
    assert read(None) is None


@pytest.mark.parametrize("file", ["program_serve.xplane.pb",
                                  "small_trace.xplane.pb"])
def test_nothing_to_read_gives_none(monkeypatch, file):
    """A dense decoder's trace has no ``moe`` / ``mamba`` scope and its
    configuration is not a hybrid's; a trace without the program's spans
    (the parent of the PR that added them) gives nothing at all."""
    ctx = ctx_of(monkeypatch, file, config={"arch": "llama_like"})
    assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
        == dict.fromkeys(NEW)
    for ctx in ({"kind": "serve", "trace": None, "config": {}},
                {"kind": "fit", "trace": None, "config": {}},
                {"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}},
                 "config": tiny_hybrid.NEMOTRON}):
        assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
            == dict.fromkeys(NEW)
