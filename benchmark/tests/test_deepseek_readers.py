"""CPU tests of the readers the Kanana serving cell brought
(``mla_attn_share_pct``, ``mla_decode_roofline_pct``,
``mla_decode_attn_roofline_pct``) on a small trace recorded on the chip
(``lib/testdata/program_serve_deepseek.xplane.pb``: 0.07 s of the
``tiny_deepseek_v3.KERNEL`` preset through Router -> PagedEngine, with the
generator's tick records beside it in ``program_serve_deepseek.window.json``),
and on traces that hold nothing for them. Run by hand:

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
from benchmark.tests import (tiny_deepseek_v3, tiny_exaone_moe,  # noqa: E402
                             tiny_hybrid)

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
NEW = ["mla_attn_share_pct", "mla_decode_roofline_pct",
       "mla_decode_attn_roofline_pct"]
SHARED = ["decode_step_device_ms", "prefill_chunk_device_ms",
          "idle_attributed_pct_serve", "moe_share_pct",
          "paged_attn_share_pct", "moe_expert_load_max_over_mean"]


def ctx_of(monkeypatch, file, window=None, config=None):
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return {"kind": "serve", "trace": trace_reduce.reduce(path),
            "config": config or tiny_deepseek_v3.KERNEL,
            "device_kind": "TPU v5 lite", "window": window or {
                "ticks": [], "trace_tick0": None}}


def recorded_window():
    with open(os.path.join(DATA, "program_serve_deepseek.window.json")) as f:
        return json.load(f)


def test_readers_on_the_recorded_trace(monkeypatch):
    win = recorded_window()
    ctx = ctx_of(monkeypatch, "program_serve_deepseek.xplane.pb", window=win)
    rec = program_spans.recording(ctx)
    programs = {n.split("(")[0] for n, _s, _e in rec["modules"]}
    assert {"jit_paged_prefill_chunk", "jit_paged_decode_step"} <= programs
    scopes = " ".join(rec["scopes"].values())
    for scope in ("attn.mla", "attn.mla/attn.mla.proj",
                  "attn.mla/attn.mla.core",
                  "attn.mla/attn.mla.core/paged_attention", "mlp",
                  "moe/moe.router", "moe/moe.experts", "moe/moe.shared",
                  "lm_head"):
        assert f"/{scope}/" in scopes, scope
    first = ctx["trace"]["devices"][sorted(ctx["trace"]["devices"])[0]]
    kernels = [n for n, _s, _e in first["ops"] if is_kernel(n)]
    steps = [n for n, _s, _e in first["modules"]
             if n.startswith("jit_paged_decode_step(")]
    # once a layer a decode step: all three layers keep latent pages
    assert kernels and len(kernels) == 3 * len(steps)
    got = {name: harness.read_layer_metric(name, ctx)
           for name in NEW + SHARED}
    # attention and the expert layers are most of a decode step, and not
    # all of it
    shares = [got["mla_attn_share_pct"], got["moe_share_pct"]]
    assert all(0 < s < 100 for s in shares) and sum(shares) < 100
    # a tiny model is nowhere near its roofline; a share is still a share,
    # and the kernel alone is nearer its own than the whole step is
    assert 0 < got["mla_decode_roofline_pct"] < 100
    assert 0 < got["mla_decode_attn_roofline_pct"] < 100
    assert 0 < got["paged_attn_share_pct"] < 100
    assert got["moe_expert_load_max_over_mean"] >= 1.0
    assert got["decode_step_device_ms"] > 0
    assert got["prefill_chunk_device_ms"] > 0
    assert 0 < got["idle_attributed_pct_serve"] <= 100


def test_the_kernels_reader_counts_ticks_that_also_prefilled(monkeypatch):
    """``mla_decode_attn_roofline_pct`` reads every traced tick that
    launched a decode step: with every tick marked as one that also ran a
    prefill chunk it reads what it read, where a reader of decode-only
    ticks finds nothing."""
    from benchmark.drivers.serve import ran_prefill
    win = recorded_window()
    ctx = ctx_of(monkeypatch, "program_serve_deepseek.xplane.pb", window=win)
    before = harness.read_layer_metric("mla_decode_attn_roofline_pct", ctx)
    busy = dict(win, ticks=[t[:7] + [1] for t in win["ticks"]])
    assert all(ran_prefill(t) for t in busy["ticks"])
    ctx = ctx_of(monkeypatch, "program_serve_deepseek.xplane.pb", window=busy)
    assert harness.read_layer_metric("mla_decode_attn_roofline_pct",
                                     ctx) == before > 0


@pytest.mark.parametrize("file,config", [
    ("program_serve.xplane.pb", {"arch": "llama_like"}),
    ("program_serve_hybrid.xplane.pb", tiny_hybrid.NEMOTRON),
    ("program_serve_exaone.xplane.pb", tiny_exaone_moe.KERNEL),
    ("small_trace.xplane.pb", {"arch": "llama_like"})])
def test_nothing_to_read_gives_none(monkeypatch, file, config):
    """Another architecture's trace has no ``attn.mla`` scope and its
    configuration is not this one's; a trace without the program's spans
    gives nothing at all; and this architecture's readers find nothing in a
    parent's run, which has no such program."""
    ctx = ctx_of(monkeypatch, file, config=config)
    assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
        == dict.fromkeys(NEW)
    for ctx in ({"kind": "serve", "trace": None, "config": {}},
                {"kind": "fit", "trace": None, "config": {}},
                {"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}},
                 "config": tiny_deepseek_v3.KERNEL}):
        assert {n: harness.read_layer_metric(n, ctx) for n in NEW} \
            == dict.fromkeys(NEW)


def test_the_other_architectures_readers_find_nothing_of_theirs_here(
        monkeypatch):
    ctx = ctx_of(monkeypatch, "program_serve_deepseek.xplane.pb",
                 window=recorded_window())
    for name in ("hybrid_decode_roofline_pct", "mamba_share_pct",
                 "paged_decode_attn_roofline_pct",
                 "exaone_decode_roofline_pct", "full_attn_share_pct",
                 "window_attn_share_pct", "full_attn_decode_roofline_pct"):
        assert harness.read_layer_metric(name, ctx) is None, name
