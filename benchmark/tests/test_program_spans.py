"""CPU tests of the readers built on the program's boundary spans and scopes
(benchmark/lib/program_spans.py), on two small traces recorded on the chip
with the spans in them (lib/testdata/program_fit.xplane.pb: four steps of a
two-layer GPT through Engine.fit; program_serve.xplane.pb: ten ticks of a
two-layer Llama through Router -> PagedEngine). Run by hand:

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

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW = ["idle_attributed_pct_fit", "idle_attributed_pct_serve",
       "step_dispatch_exposed_ms", "step_host_other_ms",
       "tick_host_exposed_ms", "prefill_chunk_device_ms",
       "decode_step_device_ms", "paged_attn_share_pct",
       "lm_head_loss_ms_per_step", "optimizer_ms_per_step"]


def ctx_of(monkeypatch, kind, file):
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return {"kind": kind, "trace": trace_reduce.reduce(path)}


def read_all(ctx):
    return {name: harness.read_layer_metric(name, ctx) for name in NEW}


# ---------------------------------------------------------------- arithmetic
def test_interval_arithmetic():
    assert program_spans.merged([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert program_spans.overlap_seconds([(0, 2), (3, 5)],
                                         [(1, 4)]) == pytest.approx(2)
    assert program_spans.overlap_seconds([(0, 1)], [(2, 3)]) == 0
    assert program_spans.contained([(0, 1), (2, 3), (9, 9.5)],
                                   (2, 9)) == [(2, 3)]


def test_a_scope_is_one_component_of_the_path():
    under = program_spans._under("attn")
    assert under.search("jit(f)/jit(main)/attn/dot_general")
    assert under.search("jit(f)/jit(main)/transpose(jvp(attn))/mul")
    assert under.search("jit(f)/jit(main)/attn/paged_attention/gather")
    assert not under.search("jit(f)/jit(main)/paged_attention/gather")
    assert not under.search("jit(f)/jit(main)/cross_attn/dot_general")


def test_leaves_are_the_spans_no_other_names_as_parent():
    assert "fit.dispatch" in program_spans.LEAVES
    assert "serving.decode.wait" in program_spans.LEAVES
    for parent in ("fit.step", "router.step", "serving.tick",
                   "serving.decode", "io.prefetch"):
        assert parent not in program_spans.LEAVES


def test_the_programs_table_lists_the_same_spans():
    from paddle_tpu.observability import trace
    assert {n: p for n, (_c, p, _w) in trace.BOUNDARY_SPANS.items()} \
        == program_spans.SPANS


def test_new_entries_only_append_and_each_has_its_reader():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == NEW
    cells = {w["name"]: w for w in BENCH["workloads"]}
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"][-len(NEW):]:
        assert callable(__import__("benchmark.layer_metrics." + m["name"],
                                   fromlist=["read"]).read)
        assert set(m["workloads"]) <= reports[m["moves"]]


# ------------------------------------------------------- the file's bytes
def test_op_names_are_read_from_the_metadata_stats():
    scopes = program_spans.op_scopes(
        os.path.join(DATA, "small_trace.xplane.pb"))
    (name, scope), = scopes.items()
    assert name.startswith("%convolution_tanh_fusion = ")
    assert scope == "jit(small_step)/dot_general:"


# ------------------------------------------- a program without the spans
def test_a_program_without_spans_gives_nothing_to_read(monkeypatch):
    for kind in ("fit", "serve"):
        ctx = ctx_of(monkeypatch, kind, "small_trace.xplane.pb")
        assert read_all(ctx) == dict.fromkeys(NEW)
    assert read_all({"kind": "fit", "trace": None}) == dict.fromkeys(NEW)
    # the harness's own test hands a summary without the per-device events
    assert read_all({"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}}}) \
        == dict.fromkeys(NEW)


# ------------------------------------------------- the recorded training
def test_fit_readers_on_the_recorded_trace(monkeypatch):
    ctx = ctx_of(monkeypatch, "fit", "program_fit.xplane.pb")
    rec = program_spans.recording(ctx)
    assert len(rec["spans"]["fit.dispatch"]) == 4
    # the recording starts at the epoch's first fetch, inside the first
    # fit.step (an annotation open before the recording is not in it); the
    # last fit.step holds only the fetch that finds the loader exhausted
    assert len(rec["spans"]["fit.step"]) == 4
    assert {n.split("(")[0] for n, _s, _e in rec["modules"]} >= \
        {"jit_engine_train_step"}
    got = read_all(ctx)
    for name in ("idle_attributed_pct_serve", "tick_host_exposed_ms",
                 "prefill_chunk_device_ms", "decode_step_device_ms",
                 "paged_attn_share_pct"):
        assert got[name] is None
    assert 0 < got["idle_attributed_pct_fit"] <= 100
    idle_ms = 1e3 * sum(e - s for s, e in program_spans.idle_gaps(ctx))
    steps = 3            # dispatches begun inside the window: all but the first
    assert got["step_dispatch_exposed_ms"] >= 0
    assert got["step_host_other_ms"] >= 0
    assert (got["step_dispatch_exposed_ms"] + got["step_host_other_ms"]) \
        * steps <= idle_ms * 1.001
    step_ms = 1e3 * ctx["trace"]["busy_s"] / 4
    assert 0 < got["lm_head_loss_ms_per_step"] < step_ms
    assert 0 < got["optimizer_ms_per_step"] < step_ms
    # the three flash kernels under their names
    kernels = {program_spans.kernel_name(n) for n, _s, _e in rec["ops"]}
    assert {"flash_fwd", "flash_dq", "flash_dkv"} <= kernels


# --------------------------------------------------- the recorded serving
def test_serve_readers_on_the_recorded_trace(monkeypatch):
    ctx = ctx_of(monkeypatch, "serve", "program_serve.xplane.pb")
    rec = program_spans.recording(ctx)
    assert len(rec["spans"]["router.step"]) == 10
    programs = {n.split("(")[0] for n, _s, _e in rec["modules"]}
    assert "jit__unknown" not in programs
    assert {"jit_paged_prefill_chunk", "jit_paged_decode_step"} <= programs
    got = read_all(ctx)
    for name in ("idle_attributed_pct_fit", "step_dispatch_exposed_ms",
                 "step_host_other_ms", "lm_head_loss_ms_per_step",
                 "optimizer_ms_per_step"):
        assert got[name] is None
    assert 0 < got["idle_attributed_pct_serve"] <= 100
    assert 0 < got["prefill_chunk_device_ms"]
    assert 0 < got["decode_step_device_ms"]
    assert 0 < got["paged_attn_share_pct"] < 100
    tick_ms = 1e3 * max(e - s for s, e in rec["spans"]["router.step"])
    assert 0 < got["tick_host_exposed_ms"] < tick_ms
