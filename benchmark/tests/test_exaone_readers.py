"""CPU tests of the readers the K-EXAONE serving cell brought
(``exaone_decode_roofline_pct``, ``full_attn_share_pct``,
``window_attn_share_pct``, ``full_attn_decode_roofline_pct``) on a small
trace recorded on the chip (``lib/testdata/program_serve_exaone.xplane.pb``:
0.1 s of the ``tiny_exaone_moe.KERNEL`` preset through Router -> PagedEngine,
with the generator's tick records beside it in
``program_serve_exaone.window.json``), and on traces that hold nothing for
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

from benchmark.layer_metrics.paged_decode_attn_roofline_pct import (  # noqa: E402
    is_kernel)
from benchmark.lib import harness, program_spans, trace_reduce  # noqa: E402
from benchmark.tests import tiny_exaone_moe, tiny_hybrid  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
NEW = ["exaone_decode_roofline_pct", "full_attn_share_pct",
       "window_attn_share_pct", "full_attn_decode_roofline_pct"]
SHARED = ["decode_step_device_ms", "prefill_chunk_device_ms",
          "idle_attributed_pct_serve", "moe_share_pct",
          "paged_attn_share_pct", "moe_expert_load_max_over_mean"]


def ctx_of(monkeypatch, file, window=None, config=None):
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return {"kind": "serve", "trace": trace_reduce.reduce(path),
            "config": config or tiny_exaone_moe.KERNEL,
            "device_kind": "TPU v5 lite", "window": window or {
                "ticks": [], "trace_tick0": None}}


def recorded_window():
    with open(os.path.join(DATA, "program_serve_exaone.window.json")) as f:
        return json.load(f)


def test_readers_on_the_recorded_trace(monkeypatch):
    win = recorded_window()
    ctx = ctx_of(monkeypatch, "program_serve_exaone.xplane.pb", window=win)
    rec = program_spans.recording(ctx)
    programs = {n.split("(")[0] for n, _s, _e in rec["modules"]}
    assert {"jit_paged_prefill_chunk", "jit_paged_decode_step"} <= programs
    scopes = " ".join(rec["scopes"].values())
    for scope in ("attn.window", "attn.full", "mlp", "moe/moe.router",
                  "moe/moe.experts", "moe/moe.shared", "lm_head",
                  "attn.full/paged_attention",
                  "attn.window/paged_attention"):
        assert f"/{scope}/" in scopes, scope
    first = ctx["trace"]["devices"][sorted(ctx["trace"]["devices"])[0]]
    kernels = [n for n, _s, _e in first["ops"] if is_kernel(n)]
    steps = [n for n, _s, _e in first["modules"]
             if n.startswith("jit_paged_decode_step(")]
    # once a FULL layer a decode step: one of the five layers pages
    assert kernels and len(kernels) == len(steps)
    got = {name: harness.read_layer_metric(name, ctx)
           for name in NEW + SHARED}
    # the attention kinds and the expert layers are most of a decode step,
    # and not all of it
    shares = [got["full_attn_share_pct"], got["window_attn_share_pct"],
              got["moe_share_pct"]]
    assert all(0 < s < 100 for s in shares) and sum(shares) < 100
    # four window layers against one full layer over a few dozen tokens
    assert got["window_attn_share_pct"] > got["full_attn_share_pct"]
    # a tiny model is nowhere near its roofline; a share is still a share
    assert 0 < got["exaone_decode_roofline_pct"] < 100
    assert 0 < got["full_attn_decode_roofline_pct"] < 100
    assert 0 < got["paged_attn_share_pct"] < 100
    assert got["moe_expert_load_max_over_mean"] >= 1.0
    assert got["decode_step_device_ms"] > 0
    assert got["prefill_chunk_device_ms"] > 0
    assert 0 < got["idle_attributed_pct_serve"] <= 100


@pytest.mark.parametrize("file,config", [
    ("program_serve.xplane.pb", {"arch": "llama_like"}),
    ("program_serve_hybrid.xplane.pb", tiny_hybrid.NEMOTRON),
    ("small_trace.xplane.pb", {"arch": "llama_like"})])
def test_nothing_to_read_gives_none(monkeypatch, file, config):
    """Another architecture's trace has no ``attn.full`` / ``attn.window``
    scope and its configuration is not this one's; a trace without the
    program's spans gives nothing at all; and this architecture's readers
    find nothing in a parent's run, which has no such program."""
    ctx = ctx_of(monkeypatch, file, config=config)
    assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
        == dict.fromkeys(NEW)
    for ctx in ({"kind": "serve", "trace": None, "config": {}},
                {"kind": "fit", "trace": None, "config": {}},
                {"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}},
                 "config": tiny_exaone_moe.KERNEL}):
        assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
            == dict.fromkeys(NEW)


def test_the_hybrids_readers_find_nothing_of_theirs_here(monkeypatch):
    ctx = ctx_of(monkeypatch, "program_serve_exaone.xplane.pb",
                 window=recorded_window())
    for name in ("hybrid_decode_roofline_pct", "mamba_share_pct",
                 "paged_decode_attn_roofline_pct"):
        assert harness.read_layer_metric(name, ctx) is None, name
