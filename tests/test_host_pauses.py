"""The host's pauses (observability/trace.py HOST_SPANS): the collector's
hook exists only while FLAGS_enable_metrics is on; on, a pass is a
``host.gc`` annotation, two counters and an entry of the bounded pause
record; a boundary span carries its thread's CPU seconds only under a live
annotation."""
from __future__ import annotations

import gc
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics, trace
from paddle_tpu.serving import Router

from test_trace_boundary import (FakeAnnotation, boundary_only,  # noqa: F401
                                 fake_profiler, tiny_replica)

SECONDS = "paddle_tpu_host_gc_pause_seconds_total"
PASSES = "paddle_tpu_host_gc_collections_total"


class CountingClock:
    def __init__(self, clock):
        self.clock, self.reads = clock, 0

    def __call__(self):
        self.reads += 1
        return self.clock()


@pytest.fixture
def clocks(monkeypatch):
    """The two clocks this PR's instrumentation reads, counted."""
    wall = CountingClock(time.perf_counter)
    cpu = CountingClock(time.thread_time)
    monkeypatch.setattr(trace, "_perf_counter", wall)
    monkeypatch.setattr(trace, "_thread_time", cpu)
    return wall, cpu


@pytest.fixture
def metrics_on():
    """FLAGS_enable_metrics on over an empty pause record and zeroed
    counters; off again afterwards, whatever the worker's earlier files
    left."""
    gc.collect()
    paddle.set_flags({"FLAGS_enable_metrics": True})
    trace.host_pauses_clear()
    for name in (SECONDS, PASSES):
        metrics.REGISTRY.get(name).clear()
    yield
    paddle.set_flags({"FLAGS_enable_metrics": False})
    trace.host_pauses_clear()


def ours():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__module__", "").startswith("paddle_tpu")]


def value(name, generation):
    return metrics.REGISTRY.get(name).value(generation=generation)


# ------------------------------------------------------------------ flag off
def test_flag_off_no_hook_and_a_forced_pass_reads_no_clock(clocks):
    paddle.set_flags({"FLAGS_enable_metrics": False})
    wall, cpu = clocks
    trace.host_pauses_clear()
    assert ours() == []
    for generation in (0, 1, 2):
        gc.collect(generation)
    assert (wall.reads, cpu.reads) == (0, 0)
    assert trace.host_pauses() == {"entries": [], "dropped": 0}


def test_flag_off_a_tick_reads_neither_clock(clocks):
    """The driver's measured runs: no recording, no metrics. A warm tick
    reads ``trace``'s clocks as often as before this hook existed: never."""
    paddle.set_flags({"FLAGS_enable_metrics": False})
    # a primitive some earlier file of this worker left on JAX's Python path
    # reports a trace a call, which the start-up record reads the clock for
    # (tests/test_startup_record.py's ``clock`` fixture has the story)
    from jax._src import dispatch
    dispatch.xla_primitive_callable.cache_clear()
    paddle.seed(2024)
    router = Router([tiny_replica()]).warmup()
    wall, cpu = clocks
    reads = wall.reads
    router.add_request(list(range(1, 12)), max_new_tokens=3)
    while router.has_work():
        router.step()
        gc.collect()
    assert (wall.reads - reads, cpu.reads) == (0, 0)
    assert ours() == []


# ------------------------------------------------------------------- flag on
def test_flag_on_installs_one_hook_and_off_removes_it():
    paddle.set_flags({"FLAGS_enable_metrics": True})
    paddle.set_flags({"FLAGS_enable_metrics": True})    # twice: still one
    try:
        assert ours() == [trace._on_collector]
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})
    assert ours() == []
    before = trace.host_pauses()
    gc.collect()
    assert trace.host_pauses() == before


def test_a_full_pass_is_one_entry_two_counters_one_annotation(
        metrics_on, fake_profiler):
    t_before = time.perf_counter()
    gc.collect(2)
    t_after = time.perf_counter()
    full = [e for e in trace.host_pauses()["entries"] if e[2] == 2]
    (t0, t1, generation, collected, tid), = full
    assert t_before <= t0 <= t1 <= t_after
    assert generation == 2 and collected >= 0 and isinstance(tid, int)
    assert value(PASSES, 2) == 1
    assert value(SECONDS, 2) == pytest.approx(t1 - t0)
    # the forced pass was the full one, whatever younger ones ran beside it
    (ann,) = [a for a in fake_profiler if a.meta.get("generation") == 2]
    assert ann.name == "host.gc"
    assert set(ann.meta) == {"generation", "collected", "uncollectable"}
    assert ann.meta["collected"] == collected


@pytest.mark.parametrize("generation", [0, 1])
def test_a_short_young_pass_moves_the_counters_and_not_the_record(
        metrics_on, generation, monkeypatch):
    monkeypatch.setattr(trace, "HOST_PAUSE_MIN_S", 3600.0)
    gc.collect(generation)
    assert value(PASSES, generation) >= 1
    assert value(SECONDS, generation) > 0.0
    assert value(PASSES, 2) == 0
    assert trace.host_pauses() == {"entries": [], "dropped": 0}


def test_a_long_young_pass_enters_the_record(metrics_on, monkeypatch):
    monkeypatch.setattr(trace, "HOST_PAUSE_MIN_S", 0.0)
    gc.collect(0)
    entries = trace.host_pauses()["entries"]
    assert entries and all(e[2] == 0 and e[1] >= e[0] for e in entries)


def test_the_record_stops_at_its_cap_and_counts_dropped(metrics_on,
                                                        monkeypatch):
    monkeypatch.setattr(trace, "MAX_HOST_PAUSES", 3)
    for _ in range(5):
        gc.collect(2)
    got = trace.host_pauses()
    full = [e for e in got["entries"] if e[2] == 2]
    assert len(got["entries"]) == 3 and got["dropped"] >= 2
    assert len(full) + got["dropped"] >= 5
    assert value(PASSES, 2) == 5            # the counters go on
    trace.host_pauses_clear()
    assert trace.host_pauses() == {"entries": [], "dropped": 0}


def test_no_annotation_without_a_recording(metrics_on, monkeypatch):
    made = []

    class Off(FakeAnnotation):
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **k):
            made.append(a)

    class Fake:
        TraceAnnotation = StepTraceAnnotation = Off
    monkeypatch.setattr(trace, "_profiler", Fake)
    gc.collect(2)
    assert made == [] and value(PASSES, 2) == 1


def test_the_counters_reach_the_exposition(metrics_on):
    gc.collect(2)
    text = metrics.REGISTRY.to_prometheus()
    assert f'{PASSES}{{generation="2"}} 1' in text
    assert f"# TYPE {SECONDS} counter" in text


# -------------------------------------------------------- cpu_s on the spans
def test_a_span_carries_cpu_s_under_a_live_annotation(fake_profiler, clocks):
    _wall, cpu = clocks
    args = {"tick": 1}
    t0 = time.perf_counter()
    with trace.boundary("serving.emit", args=args):
        sum(range(20_000))
        time.sleep(0.02)                # off the CPU: wall, not cpu_s
    wall_s = time.perf_counter() - t0
    (ann,) = fake_profiler
    assert 0.0 <= ann.meta["cpu_s"] <= wall_s + 1e-3
    assert ann.meta["cpu_s"] < wall_s - 0.015
    assert ann.meta["tick"] == 1 and args == {"tick": 1}
    assert cpu.reads == 2


def test_no_cpu_s_and_no_thread_time_read_without_an_annotation(clocks):
    _wall, cpu = clocks
    trace.clear()
    trace.activate()                    # the buffer sink alone
    try:
        with trace.boundary("serving.emit", args={"tick": 1}):
            pass
        (_name, _cat, _t0, _t1, _tid, args), = trace.drain()
    finally:
        trace.deactivate()
    assert args == {"tick": 1} and cpu.reads == 0


# ------------------------------------------------------------------ the table
def test_host_spans_share_no_name_with_the_other_tables():
    others = (set(trace.BOUNDARY_SPANS) | set(trace.STARTUP_SPANS)
              | set(trace.DEVICE_SCOPES) | set(trace.STEP_COUNTERS))
    assert set(trace.HOST_SPANS) == {"host.gc"}
    assert not set(trace.HOST_SPANS) & others
    assert len(trace.BOUNDARY_SPANS) == 22
    for name, (cat, parent, what) in trace.HOST_SPANS.items():
        assert cat == "host" and parent == "*" and what


# ------------------------------------------- the tick's budget, metrics on
def test_a_pass_inside_a_tick_is_not_counted_as_the_ticks(
        metrics_on, fake_profiler, monkeypatch):
    """tests/test_trace_boundary.py pins a decode-only tick at ten
    annotations; with metrics on a collection inside it adds ``host.gc``
    events, which belong to the host and not to the tick."""
    replica = tiny_replica()
    router = Router([replica]).warmup()
    emit = replica._emit

    def emit_after_a_pass(rec, outs):
        gc.collect(2)
        return emit(rec, outs)

    monkeypatch.setattr(replica, "_emit", emit_after_a_pass)
    router.add_request(list(range(1, 12)), max_new_tokens=3)
    ticks = []
    while router.has_work():
        before = len(fake_profiler)
        router.step()
        ticks.append(fake_profiler[before:])
    assert len(ticks) == 3
    second = [a.name for a in boundary_only(ticks[1])]
    assert sorted(second) == sorted([
        "router.step", "serving.tick", "serving.admit", "serving.plan",
        "serving.decode", "serving.decode.build", "serving.decode.launch",
        "serving.decode.wait", "serving.emit", "router.deliver"])
    # one forced pass an emit, each a host.gc of its own inside the tick
    for tick in ticks:
        full = [a for a in tick if a.name == "host.gc"
                and a.meta.get("generation") == 2]
        assert len(full) == sum(a.name.endswith(".wait") for a in tick)
    assert value(PASSES, 2) == 3
