"""CPU tests of the three readers of the host's pauses
(benchmark/lib/host_pauses.py) on hand-made recordings, of the pause record's
cut at a READY stamp, and of the loader on a recording made here (the host
plane needs no chip). Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import gc
import glob
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, host_pauses, program_spans  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW = ["gc_pause_share_pct", "idle_in_gc_pct", "tick_idle_ms"]
SERVING = ["mistral7b-chat-rate", "mistral7b-chat-saturated",
           "nemotron3s-reason-saturated", "kexaone-longmix-saturated",
           "kanana-longdoc-saturated", "olmohybrid-reason-saturated",
           "solaropen2-rag-saturated"]


def ctx_of(ops, lo, hi):
    """A serving run's ``ctx`` whose first chip ran ``ops`` ``(start,
    end)`` in the traced window [lo, hi]."""
    return {"kind": "serve",
            "trace": {"t_lo": lo, "t_hi": hi, "devices": {
                "/device:TPU:0": {"ops": [("op", s, e) for s, e in ops]}}}}


def rec_of(spans=None, passes=()):
    """A recording: ``spans`` ``{name: [(start, end, cpu_s)]}``, ``passes``
    ``[(start, end, generation)]``."""
    out = {name: [] for name in program_spans.SPANS}
    out.update(spans or {})
    return {"spans": out,
            "gc": [(s, e, g, 0, "python") for s, e, g in passes]}


@pytest.fixture
def recorded(monkeypatch):
    def put(rec):
        monkeypatch.setattr(host_pauses, "recording", lambda ctx: rec)
    return put


@pytest.fixture
def logged(monkeypatch):
    lines = []
    monkeypatch.setattr(harness, "log",
                        lambda *a: lines.append(" ".join(map(str, a))))
    return lines


# ------------------------------------------------------------------ the entries
def test_three_entries_appended_each_with_its_reader():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])            # later PRs append behind them
    assert names[at:at + 3] == NEW
    assert names.index("full_attn_train_share_pct") == at - 1
    for m in BENCH["per_layer"][at:at + 3]:
        assert m["workloads"] == SERVING
        assert (m["moves"], m["better"]) == ("itl_p95_ms", "lower")
        assert callable(__import__("benchmark.layer_metrics." + m["name"],
                                   fromlist=["read"]).read)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["gc_pause_share_pct"]["source"] == "program_counter"
    assert by_name["tick_idle_ms"]["layer"] == "serving tick"
    reports = next(m for m in BENCH["end_to_end"]
                   if m["name"] == "itl_p95_ms")["workloads"]
    assert set(SERVING) <= set(reports)


def test_interval_pieces():
    assert host_pauses.intersect([(0, 2), (3, 5), (8, 9)],
                                 [(1, 4), (4.5, 6)]) == [
        (1, 2), (3, 4), (4.5, 5)]
    assert host_pauses.intersect([(0, 1)], [(2, 3)]) == []


# ------------------------------------------------------------- idle_in_gc_pct
#: the chip runs 0-1, 2-3, 5-6, 8-10: idle 1-2, 3-5, 6-8 (5 s)
OPS = [(0, 1), (2, 3), (5, 6), (8, 10)]


def test_idle_under_a_pass_wholly_half_and_not_at_all(recorded, logged):
    # 1-2 wholly under a pass, 3-5 half under one, 6-8 under none
    recorded(rec_of(passes=[(0.5, 2.5, 2), (4, 5.5, 0)]))
    assert host_pauses.idle_in_gc_pct(ctx_of(OPS, 0, 10)) \
        == pytest.approx(100 * (1 + 1) / 5)
    assert "2 host.gc events" in logged[0]


def test_passes_that_meet_no_idle_time_read_zero(recorded):
    recorded(rec_of(passes=[(0.2, 0.8, 0), (8.5, 9, 2)]))
    assert host_pauses.idle_in_gc_pct(ctx_of(OPS, 0, 10)) == 0.0


def test_a_recording_without_a_pass_reads_none(recorded):
    recorded(rec_of(spans={"router.step": [(0, 10, None)]}))
    assert host_pauses.idle_in_gc_pct(ctx_of(OPS, 0, 10)) is None


def test_passes_on_two_threads_are_not_counted_twice(recorded):
    recorded(rec_of(passes=[(1, 2, 0), (1.5, 2, 1)]))
    assert host_pauses.idle_in_gc_pct(ctx_of(OPS, 0, 10)) \
        == pytest.approx(100 * 1 / 5)


# --------------------------------------------------------------- tick_idle_ms
def tick(t0, t1, chunk=None, step=None, cpu=None):
    """One ``router.step`` [t0, t1] with a ``serving.tick`` inside it and
    the launch brackets asked for, each with a build and a wait."""
    spans = {"router.step": [(t0, t1, cpu)],
             "serving.tick": [(t0 + 0.01, t1 - 0.01, cpu)]}
    for name, at in (("serving.prefill", chunk), ("serving.decode", step)):
        if at is not None:
            s, e = at
            mid = (s + e) / 2
            spans[name] = [(s, e, cpu)]
            spans[name + ".build"] = [(s, mid, cpu)]
            spans[name + ".wait"] = [(mid, e, cpu)]
    return spans


def joined(*ticks):
    out = {}
    for t in ticks:
        for name, ivs in t.items():
            out.setdefault(name, []).extend(ivs)
    return out


def test_a_tick_with_a_chunk_and_a_step_is_counted(recorded, logged):
    """What ``tick_host_exposed_ms`` leaves out: the tick of 0-4 holds a
    chunk AND a step. The tick of 6-8 launched nothing and is left out;
    the one of 9-11 ends outside the window."""
    recorded(rec_of(spans=joined(
        tick(0, 4, chunk=(0.5, 1.5), step=(1.5, 3.5)),
        tick(6, 8), tick(9, 11, step=(9.2, 9.8)))))
    ctx = ctx_of(OPS, 0, 10)
    # idle inside 0-4: 1-2 and 3-4
    assert host_pauses.tick_idle_ms(ctx) == pytest.approx(2000.0)
    assert "over 1 ticks that launched a program (1 with a chunk)" \
        in logged[0]
    split = eval(logged[0].split("own work): ")[1].split("; the leaf")[0])
    # 1-1.5 in the chunk's wait, 1.5-2 in the step's build, 3-3.5 in its
    # wait, 3.5-3.99 the tick's own work, the last 10 ms router.step's
    assert split["serving.prefill.wait"] == pytest.approx(500)
    assert split["serving.decode.build"] == pytest.approx(500)
    assert split["serving.decode.wait"] == pytest.approx(500)
    assert split["serving.tick"] == pytest.approx(490)
    assert split["router.step"] == pytest.approx(10)
    assert split["serving.decode"] == pytest.approx(0)
    assert sum(split.values()) == pytest.approx(2000)


def test_decode_only_ticks_read_what_tick_host_exposed_reads(recorded):
    spans = joined(tick(0, 4, step=(0.5, 3.5)), tick(5, 8, step=(5.5, 7)))
    recorded(rec_of(spans=spans))
    ctx = ctx_of(OPS, 0, 10)
    # 1-2 and 3-4 in the first, 6-8 in the second
    assert host_pauses.tick_idle_ms(ctx) == pytest.approx(1e3 * 4 / 2)


def test_the_longest_gaps_name_their_owner(recorded, logged):
    recorded(rec_of(
        spans=joined(tick(0, 4, step=(0.5, 3.5), cpu=0.25),
                     tick(5, 8.5, step=(5.5, 7), cpu=3.0)),
        passes=[(6.5, 7.5, 2), (1.2, 1.3, 0)]))
    host_pauses.tick_idle_ms(ctx_of(OPS, 0, 10))
    owners = eval(logged[1].split("ticks: ", 1)[1])
    first = owners[0]
    # the gap of 6-8: the step's build (5.5-6.25) was open at its start,
    # and a second of it lies under the full pass
    assert first == {"gap_s": 2.0, "span": "serving.decode.build",
                     "span_wall_s": 0.75, "span_cpu_s": 3.0,
                     "gc": [[1.0, 2]]}
    # 1-2 began in the first step's build (0.5-2), 3-4 in its wait (2-3.5)
    assert sorted(owners[1:], key=lambda o: o["span"]) == [
        {"gap_s": 1.0, "span": "serving.decode.build", "span_wall_s": 1.5,
         "span_cpu_s": 0.25, "gc": [[0.1, 0]]},
        {"gap_s": 1.0, "span": "serving.decode.wait", "span_wall_s": 1.5,
         "span_cpu_s": 0.25, "gc": []}]


def test_a_gap_outside_every_span_is_the_callers(recorded):
    rec = rec_of(spans=tick(0, 1.5, step=(0.2, 1.2)))
    assert host_pauses.owner_of((3, 5), rec) == {
        "gap_s": 2.0, "span": host_pauses.OUTSIDE, "gc": []}


def test_no_recording_or_no_tick_reads_none(recorded):
    recorded(None)
    ctx = ctx_of(OPS, 0, 10)
    assert host_pauses.tick_idle_ms(ctx) is None
    assert host_pauses.idle_in_gc_pct(ctx) is None
    recorded(rec_of(spans=tick(0, 4)))          # a tick that launched nothing
    assert host_pauses.tick_idle_ms(ctx) is None


def test_no_trace_reads_none(monkeypatch):
    monkeypatch.setattr(host_pauses, "pause_record", lambda: None)
    for ctx in ({"kind": "fit", "trace": None},
                {"kind": "serve", "trace": None},
                # the harness's own test hands a summary without the
                # per-device events
                {"kind": "serve", "trace": {"busy_s": 1.0, "ops": {}}}):
        for name in NEW:
            assert harness.read_layer_metric(name, ctx) is None


# --------------------------------------------------------- gc_pause_share_pct
def test_the_pause_record_is_cut_at_the_ready_stamp(monkeypatch, logged):
    ready = 100.0
    record = {"entries": [], "dropped": 0, "process_start": 0.0,
              "ready": [("replica", "replica-0", 90.0),
                        ("replica", "replica-0", ready)]}
    pauses = {"entries": [(99.0, 99.5, 2, 10, 0),      # before READY
                          (101.0, 101.25, 2, 5, 0),
                          (120.0, 120.002, 0, 0, 1),
                          (149.9, 150.4, 2, 7, 0),     # began inside
                          (150.5, 151.0, 2, 0, 0)],    # began after
              "dropped": 0}
    monkeypatch.setattr(host_pauses.startup_record, "record",
                        lambda: record)
    monkeypatch.setattr(host_pauses, "pause_record", lambda: pauses)
    monkeypatch.setattr(host_pauses, "_counters", lambda: {})
    ctx = {"kind": "serve", "window": {"window_s": 50.0}}
    assert host_pauses.gc_pause_share_pct(ctx) == pytest.approx(
        100 * (0.25 + 0.002 + 0.5) / 50)
    assert "3 began in the 50.00 s after READY" in logged[0]
    assert "{0: [1, 0.002], 2: [2, 0.75]}" in logged[0]
    # no pause record (a program from before the hook), no READY stamp
    monkeypatch.setattr(host_pauses, "pause_record", lambda: None)
    assert host_pauses.gc_pause_share_pct(ctx) is None
    monkeypatch.setattr(host_pauses, "pause_record", lambda: pauses)
    monkeypatch.setattr(host_pauses.startup_record, "record",
                        lambda: dict(record, ready=[]))
    assert host_pauses.gc_pause_share_pct(ctx) is None
    assert host_pauses.gc_pause_share_pct(dict(ctx, kind="fit")) is None


def test_the_programs_own_record_and_counters_are_read():
    import paddle_tpu as paddle
    from paddle_tpu.observability import trace
    if not hasattr(trace, "host_pauses"):
        pytest.skip("a program from before the hook")
    paddle.set_flags({"FLAGS_enable_metrics": True})
    try:
        trace.host_pauses_clear()
        gc.collect(2)
        (entry,) = [e for e in host_pauses.pause_record()["entries"]
                    if e[2] == 2]
        assert entry[1] >= entry[0]
        assert host_pauses._counters()["2"][0] >= 1
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})
        trace.host_pauses_clear()


# ------------------------------------------------------------------ the loader
def test_the_loader_reads_passes_and_cpu_seconds_from_a_recording(tmp_path):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.observability import trace
    if not hasattr(trace, "HOST_SPANS"):
        pytest.skip("a program from before the hook")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    paddle.set_flags({"FLAGS_enable_metrics": True})
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.boundary("router.step"):
            with trace.boundary("serving.emit"):
                gc.collect(2)
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
        paddle.set_flags({"FLAGS_enable_metrics": False})
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    rec = host_pauses._load(path, 0.0)
    (s, e, cpu), = rec["spans"]["serving.emit"]
    assert 0.0 <= cpu < (e - s) - 0.015
    (g0, g1, generation, collected, thread), = [
        p for p in rec["gc"] if p[2] == 2]
    assert s <= g0 <= g1 <= e and collected >= 0 and thread
    owner = host_pauses.owner_of((g0, g1), rec)
    assert owner["span"] == "serving.emit"
    assert owner["gc"][0] == [round(g1 - g0, 6), 2]
    assert owner["span_cpu_s"] == pytest.approx(cpu, abs=1e-6)


# --------------------------------- recordings of programs from before the hook
DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")


@pytest.mark.parametrize("file,same", [
    ("program_serve.xplane.pb", True),          # decode-only ticks alone
    ("program_serve_olmo.xplane.pb", True),
    ("program_serve_exaone.xplane.pb", False),  # two of five carry a chunk
    ("program_serve_kernel.xplane.pb", None)])  # its one tick carries a chunk
def test_on_a_recording_of_an_earlier_program(monkeypatch, file, same):
    """No ``host.gc``, no ``cpu_s``, no pause record: the two collector
    readers read None, ``tick_idle_ms`` reads what ``tick_host_exposed_ms``
    reads where every tick is decode-only and a number where that reads
    None."""
    from benchmark.lib import trace_reduce
    path = os.path.join(DATA, file)
    for module in (program_spans, host_pauses):
        monkeypatch.setattr(module, "newest_xplane", lambda: path)
    monkeypatch.setattr(host_pauses, "pause_record", lambda: None)
    ctx = {"kind": "serve", "trace": trace_reduce.reduce(path),
           "window": {"window_s": 4.0}}
    got = {name: harness.read_layer_metric(name, ctx)
           for name in NEW + ["tick_host_exposed_ms"]}
    assert got["gc_pause_share_pct"] is None
    assert got["idle_in_gc_pct"] is None
    assert got["tick_idle_ms"] > 0
    if same is None:
        assert got["tick_host_exposed_ms"] is None
    elif same:
        assert got["tick_idle_ms"] == pytest.approx(
            got["tick_host_exposed_ms"])
    else:
        assert got["tick_idle_ms"] > got["tick_host_exposed_ms"]
