"""CPU tests of the readers of the program's start-up record
(benchmark/lib/startup_record.py) and of its launch and read counters
(benchmark/lib/serving_counters.py), on hand-made records. Run by hand:

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

from benchmark.lib import harness, startup_record  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
STARTUP = ["startup_import_s", "startup_backend_s", "startup_trace_lower_s",
           "startup_compile_s", "startup_cache_misses", "fit_setup_s",
           "engine_warmup_s", "startup_unattributed_s"]
SHARES = ["host_late_share_pct", "mixed_share_pct"]
CELLS = [w["name"] for w in BENCH["workloads"]]
FIT = [c for c in CELLS if "fit" in c]
SERVE = [c for c in CELLS if "fit" not in c]


def entry(name, t0, t1, parent=None, **args):
    cat = "compile" if name.startswith("compile.") else "startup"
    return (name, cat, t0, t1, 0, args, parent)


def serve_record():
    """Process start 100; import to 103; backend 103.5-111.5 by marks; the
    caller's weights compile under no phase; build 114-115; warm-up
    115-119 with one program traced 0.5 (a nested second of which is inside
    the first), lowered 0.25, compiled 1.0 (a hit) and one missed, 0.5;
    READY at 119; a compile in the window after it."""
    return {"process_start": 100.0, "dropped": 0,
            "ready": [("replica", "replica0", 119.0)],
            "entries": [
        entry("startup.import", 100.0, 103.0, before_package_s=2.6),
        entry("startup.backend", 103.5, 111.5, bracketed=False),
        entry("compile.backend", 112.0, 113.0, None, program="jit(make)",
              cache="hit"),
        entry("startup.engine_build", 114.0, 115.0, replica="replica0"),
        entry("compile.trace", 115.0, 115.5, "startup.warmup",
              program="paged_decode_step", inner=40),
        entry("compile.trace", 115.25, 115.5, "startup.warmup",
              program="attend", inner=0),
        entry("compile.lower", 115.5, 115.75, "startup.warmup",
              program="jit(paged_decode_step)"),
        entry("compile.backend", 116.0, 117.0, "startup.warmup",
              program="jit(paged_decode_step)", cache="hit",
              retrieval_s=0.9),
        entry("compile.backend", 117.0, 117.5, "startup.warmup",
              program="jit(_feed_tokens)", cache="miss"),
        entry("startup.warmup", 115.0, 119.0, replica="replica0",
              state="READY"),
        entry("compile.backend", 130.0, 140.0, "startup.fit_call",
              program="jit(late)", cache="miss"),
    ]}


def fit_record():
    """Process start 0; import to 3; backend 3-10; the reference's compile
    under no phase at 12-20; call 0 (30-40: setup 30-32 with prepare 30-31
    and a compile 31-31.5 inside, the step traced 32-35, compiled 35-38,
    writeback 39.5-40); the window's call 50-120, its setup 50-51, the cut
    at its first fetch, 51.5."""
    call = "startup.fit_call"
    return {"process_start": 0.0, "dropped": 0, "ready": [], "entries": [
        entry("startup.import", 0.0, 3.0),
        entry("startup.backend", 3.0, 10.0, bracketed=False),
        entry("compile.backend", 12.0, 20.0, None, program="jit(reference)",
              cache="miss"),
        entry("startup.prepare", 30.0, 31.0, "fit.setup"),
        entry("compile.backend", 31.0, 31.5, "fit.setup",
              program="jit(zeros)", cache="hit"),
        entry("fit.setup", 30.0, 32.0, call),
        entry("compile.trace", 32.0, 35.0, call, program="step", inner=900),
        entry("compile.backend", 35.0, 38.0, call, program="jit(step)",
              cache="miss"),
        entry("fit.writeback", 39.5, 40.0, call),
        entry(call, 30.0, 40.0, engine="engine0", call=0),
        entry("fit.setup", 50.0, 51.0, call),
        entry("fit.writeback", 119.0, 120.0, call),
        entry(call, 50.0, 120.0, engine="engine0", call=1),
    ]}


@pytest.fixture
def quiet(monkeypatch):
    monkeypatch.setattr(harness, "log", lambda *a: None)


def read_all(ctx, names=STARTUP):
    return {name: harness.read_layer_metric(name, ctx) for name in names}


# ------------------------------------------------------------- the entries
def test_the_new_entries_are_appended_with_their_cells():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    tail = [m["name"] for m in BENCH["per_layer"]][-len(STARTUP + SHARES):]
    assert sorted(tail) == sorted(STARTUP + SHARES)
    for name in STARTUP:
        assert by_name[name]["moves"] == "setup_s"
        assert by_name[name]["better"] == "lower"
    for name in SHARES:
        assert by_name[name]["moves"] == "itl_p95_ms"
        assert by_name[name]["workloads"] == SERVE
    assert by_name["fit_setup_s"]["workloads"] == FIT
    assert by_name["engine_warmup_s"]["workloads"] == SERVE
    for name in set(STARTUP) - {"fit_setup_s", "engine_warmup_s"}:
        assert by_name[name]["workloads"] == CELLS


# ---------------------------------------------------------------- the split
def test_serving_is_cut_at_ready_and_filtered_by_parent(monkeypatch, quiet):
    monkeypatch.setattr(startup_record, "record", serve_record)
    got = read_all({"kind": "serve"})
    assert got == {
        "startup_import_s": 3.0, "startup_backend_s": 8.0,
        # the nested trace is inside the outer one's half second
        "startup_trace_lower_s": 0.75,
        # the weight maker's compile has no program phase above it, the
        # late one began after READY
        "startup_compile_s": 1.5, "startup_cache_misses": 1,
        "fit_setup_s": None, "engine_warmup_s": 5.0,
        # 19 s less import 3, backend 8, build and warm-up 5
        "startup_unattributed_s": 3.0}


def test_fit_is_cut_at_the_first_fetch_of_the_window(monkeypatch, quiet):
    monkeypatch.setattr(startup_record, "record", fit_record)
    ctx = {"kind": "fit", "epoch_starts": [51.5, 60.0]}
    got = read_all(ctx)
    assert got == {
        "startup_import_s": 3.0, "startup_backend_s": 7.0,
        "startup_trace_lower_s": 3.0, "startup_compile_s": 3.5,
        "startup_cache_misses": 1,
        # setup 2 + writeback 0.5 + the window's setup 1, less the compile
        # inside the first; prepare lies inside its setup
        "fit_setup_s": 3.0, "engine_warmup_s": None,
        # 51.5 less import 3, backend 7 and the calls' 10 + 1.5
        "startup_unattributed_s": 30.0}
    split = ctx["startup_split"]
    assert split["own_compiles"] == {"trace": 0.0, "lower": 0.0,
                                     "backend": 8.0, "programs": 1}
    assert split["gaps"][:2] == [
        (10.0, "startup.fit_call", "startup.fit_call"),
        (10.0, "jit(reference)", "startup.fit_call")]
    assert [p["program"] for p in split["programs"]] == ["step", "zeros"]
    assert split["programs"][0]["cache"] == {"miss": 1}


@pytest.mark.parametrize("make, ctx", [
    (serve_record, {"kind": "serve"}),
    (fit_record, {"kind": "fit", "epoch_starts": [51.5]}),
    (fit_record, {"kind": "fit", "epoch_starts": [35.5]}),   # mid-compile
    (fit_record, {"kind": "fit", "epoch_starts": [5.0]}),    # mid-backend
])
def test_the_split_closes(monkeypatch, quiet, make, ctx):
    monkeypatch.setattr(startup_record, "record", make)
    got = startup_record.split(ctx)
    assert got["import_s"] + got["backend_s"] + got["phases_s"] \
        + got["unattributed_s"] == pytest.approx(
            got["cut"] - got["process_start"])
    assert got["unattributed_s"] >= 0
    assert got["trace_lower_s"] + got["compile_s"] <= got["phases_s"] + 1e-9


def test_the_split_is_made_and_logged_once(monkeypatch):
    lines = []
    monkeypatch.setattr(harness, "log", lambda *a: lines.append(a))
    monkeypatch.setattr(startup_record, "record", serve_record)
    ctx = {"kind": "serve"}
    read_all(ctx)
    assert len(lines) == 3 and "11 entries" in lines[0][0]


@pytest.mark.parametrize("rec", [
    None,                                                   # the parent
    {**serve_record(), "ready": []},                        # never READY
    {**serve_record(), "process_start": None},              # no import entry
])
def test_no_record_no_metric(monkeypatch, quiet, rec):
    monkeypatch.setattr(startup_record, "record", lambda: rec)
    assert set(read_all({"kind": "serve"}).values()) == {None}


def test_a_program_without_the_record_reads_none(monkeypatch, quiet):
    from paddle_tpu.observability import trace
    monkeypatch.delattr(trace, "startup_record")
    assert startup_record.record() is None
    assert set(read_all({"kind": "fit", "epoch_starts": [1.0]}).values()) \
        == {None}


# --------------------------------------------------------------- the shares
@pytest.fixture
def counters():
    from paddle_tpu.observability import metrics
    from paddle_tpu.inference import resilience
    was = metrics.enabled()
    metrics._enabled["on"] = True
    for m in (resilience.M_LAUNCHES, resilience.M_READS):
        m.clear()
    yield resilience
    for m in (resilience.M_LAUNCHES, resilience.M_READS):
        m.clear()
    metrics._enabled["on"] = was


def test_the_shares_read_the_programs_counters(counters):
    ctx = {"kind": "serve"}
    assert read_all(ctx, SHARES) == {"host_late_share_pct": None,
                                     "mixed_share_pct": None}
    for kind, overlapped, n in (("prefill", "true", 5), ("decode", "true", 6),
                                ("mixed", "true", 3), ("mixed", "false", 1)):
        counters.M_LAUNCHES.inc(n, overlapped=overlapped, kind=kind)
    counters.M_READS.inc(3, host_late="true")
    counters.M_READS.inc(9, host_late="false")
    assert read_all(ctx, SHARES) == {
        "host_late_share_pct": pytest.approx(25.0),
        "mixed_share_pct": pytest.approx(40.0)}       # 4 of 10 steps
    assert read_all({"kind": "fit"}, SHARES) == {
        "host_late_share_pct": None, "mixed_share_pct": None}
