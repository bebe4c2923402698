"""The start-up record (observability/trace.py STARTUP_SPANS): the phases a
``fit`` call and a replica's warm-up leave, the trace / lower / backend entry
of every program with the persistent cache's answer, what steady state adds
(nothing), the cap, and the surfaces that read the record."""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.resilience import ReplicaLifecycle, ReplicaState
from paddle_tpu.observability import REGISTRY, trace
from paddle_tpu.serving import Router

from test_trace_boundary import Rows, tiny_gpt_engine, tiny_replica


@pytest.fixture
def record():
    trace.startup_clear()
    yield lambda: trace.startup_record()["entries"]
    trace.startup_clear()


def named(entries, name):
    return [e for e in entries if e[0] == name]


def fit_twice():
    engine = tiny_gpt_engine()
    for _ in range(2):
        engine.fit(Rows(16), epochs=1, batch_size=8)
    return engine


def warm_replica():
    replica = tiny_replica()
    Router([replica]).warmup()
    return replica


PATHS = {
    "fit": (fit_twice, "startup.fit_call", ["engine_train_step"]),
    "serve": (warm_replica, "startup.warmup",
              ["paged_prefill_chunk", "paged_decode_step",
               "paged_mixed_step"]),
}


# ---------------------------------------------------------------- the table
def test_the_table_names_every_entry_and_only_the_two_boundary_spans():
    assert set(trace.STARTUP_SPANS) & set(trace.BOUNDARY_SPANS) == {
        "fit.setup", "fit.writeback"}
    for name, (cat, parent, what) in trace.STARTUP_SPANS.items():
        assert parent in (None, "*") or parent in trace.STARTUP_SPANS, name
        assert cat and what
    with pytest.raises(KeyError):
        trace.startup_phase("startup.nothing")


def test_fit_calls_leave_their_phases_with_their_parents(record):
    engine = fit_twice()
    entries = record()
    calls = named(entries, "startup.fit_call")
    assert [c[5]["call"] for c in calls] == [0, 1]
    for call in calls:
        assert call[6] is None and call[5]["engine"] == engine._startup_name
        assert call[5]["steps"] == 2 and call[5]["epochs"] == 1
        assert 0 < call[5]["state_placed_s"] < call[5]["first_step_s"] \
            <= call[3] - call[2]
    # the step is built in the first call only, inside its fit.setup
    (prepare,) = named(entries, "startup.prepare")
    assert prepare[6] == "fit.setup"
    for name in ("fit.setup", "fit.writeback"):
        spans = named(entries, name)
        assert len(spans) == 2 and {s[6] for s in spans} == {
            trace.STARTUP_SPANS[name][1]} == {"startup.fit_call"}
        for span, call in zip(spans, calls):
            assert call[2] <= span[2] <= span[3] <= call[3]
    # ready is the program's mark: the first dispatch's return, a call each
    ready = trace.startup_record()["ready"]
    assert [(kind, who) for kind, who, _t in ready] == [
        ("fit", engine._startup_name)] * 2
    assert calls[0][2] < ready[0][2] < calls[0][3]


def test_a_replica_leaves_build_and_warmup(record):
    replica = warm_replica()
    name = replica.lifecycle.name
    (build,) = named(record(), "startup.engine_build")
    (warm,) = named(record(), "startup.warmup")
    assert build[6] is None and warm[6] is None and build[3] <= warm[2]
    assert build[5]["replica"] == name and build[5]["pool_bytes"] > 0
    assert warm[5] == {"replica": name, "state": "READY",
                       "ticks": replica._ticks, "synthetic": 2}
    assert trace.startup_record()["ready"] == [("replica", name, warm[3])]


def test_a_warmup_that_does_not_reach_ready_still_closes_its_phase(record):
    lifecycle = ReplicaLifecycle(name="lame")
    lifecycle.to(ReplicaState.WARMING)
    lifecycle.degrade("watchdog")
    (warm,) = named(record(), "startup.warmup")
    assert warm[5] == {"replica": "lame", "state": "DEGRADED"}
    assert trace.startup_record()["ready"] == []
    with trace.startup_phase("startup.prepare") as ph:   # nothing left open
        assert ph.parent is None


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_program_has_its_trace_lower_backend_triple(record, path):
    drive, phase, programs = PATHS[path]
    drive()
    entries = record()
    for program in programs:
        triple = [e for e in entries if e[0].startswith("compile.")
                  and program in (e[5]["program"] or "")]
        assert [e[0] for e in triple] == [
            "compile.trace", "compile.lower", "compile.backend"], program
        assert {e[6] for e in triple} == {phase}
        assert triple[0][2] <= triple[0][3] <= triple[1][3] <= triple[2][3]
        # jnp's own jitted functions traced inside are counted, not listed
        assert triple[0][5]["inner"] > 10
        assert triple[2][5]["cache"] in ("hit", "miss", "off")
    assert not [e for e in entries if e[0] == "compile.trace"
                and e[5]["program"] in ("multiply", "add")
                and e[6] == phase and e[5]["inner"]]


def test_traces_inside_a_lowering_are_counted_not_listed(record):
    key = jax.random.key(3)
    trace.startup_clear()

    def draw(key):
        # a shape no test has used: the lowering rule of the generator's
        # hash traces its jnp calls anew, hundreds of them
        return jax.random.normal(key, (1237,))
    jax.jit(draw)(key)
    entries = record()
    (lower,) = [e for e in named(entries, "compile.lower")
                if "draw" in e[5]["program"]]
    assert lower[5]["inner"] > 0
    assert not [e for e in entries if e[0] == "compile.trace"
                and lower[2] <= e[2] and e[3] <= lower[3]]


def test_a_kernels_wrapper_stamps_its_plan_on_the_trace_entry(record):
    """``compile_note`` from code that runs while a program is traced: the
    flash kernels' wrappers say which tiles and what share of the square
    the program was built with (``flash_plan``), once a key with a count;
    outside a trace it is a no-op."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    trace.compile_note("nobody", {"x": 1})      # no trace in flight
    q = jnp.ones((1, 512, 2, 32))

    def step(q):
        attend = lambda q: fa.flash_attention_fwd(  # noqa: E731
            q, q, q, causal=True, block_q=512, block_k=512)
        return jax.grad(lambda q: jnp.sum(attend(attend(q))))(q)

    old, fa.INTERPRET = fa.INTERPRET, True
    try:
        jax.jit(step).lower(q)
    finally:
        fa.INTERPRET = old
    entries = record()
    (traced,) = [e for e in named(entries, "compile.trace")
                 if e[5]["program"] == "step"]
    plan = dict(fa.flash_plan(512, 512, True, 512, 512, 2, 32, 4))
    assert plan["executed_share"] == 0.75 and plan["sub_block"] == 256
    # two float32 heads of 32 fill no 128-lane block: each its own, padded
    assert (plan["heads_per_block"], plan["io_bytes"]) == (1, 512 * 128 * 4)
    assert traced[5]["flash_fwd[512x512,causal,512x512]"] == dict(
        plan, calls=2)
    assert traced[5]["flash_bwd[512x512,causal,512x512]"] == dict(
        plan, calls=2)
    assert not [e for e in entries if "nobody" in (e[5] or {})]


def test_persistent_cache_outcomes_are_recorded(record, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    def probe():
        # a new function object each time: nothing in memory knows it, and
        # its module is byte for byte the last one's
        def startup_cache_probe(x):
            return jnp.tanh(x) * 3.0 + 1.0
        return jax.jit(startup_cache_probe)

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    x = jnp.arange(8.0)
    probe()(x)                          # cache off: no directory
    try:
        cc.reset_cache()
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0.0)
        jax.config.update(keys[2], 0)
        probe()(x)
        probe()(x)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()                # no other test sees the directory
    backend = [e for e in record() if e[0] == "compile.backend"
               and "startup_cache_probe" in e[5]["program"]]
    assert [e[5]["cache"] for e in backend] == ["off", "miss", "hit"]
    hit = backend[-1][5]
    assert hit["retrieval_s"] >= 0 and "saved_s" in hit
    assert "retrieval_s" not in backend[1][5]


# ------------------------------------------------------------ steady state
class CountingClock:
    def __init__(self):
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return time.perf_counter()


@pytest.fixture
def clock(monkeypatch):
    # An eager primitive called once inside an eager ``jax.vmap`` (the trace
    # state is not clean, so jit hands back no fast path) leaves its argument
    # signature on the Python path for good: every later call of it reports a
    # trace, which the record takes and reads the clock for.
    # tests/test_paged_attention.py's TestBlockwiseComposite leaves
    # ``jnp.full(shape, float, float32)`` so when it shares a worker with
    # this file. New wrappers start with a clean slate; conftest's seed
    # once more warms the ones it had warmed.
    from jax._src import dispatch
    dispatch.xla_primitive_callable.cache_clear()
    paddle.seed(2024)
    counting = CountingClock()
    monkeypatch.setattr(trace, "_perf_counter", counting)
    return counting


def test_warm_ticks_append_nothing_and_read_no_clock(record, clock):
    replica = warm_replica()
    router = Router([replica])
    n, reads = len(record()), clock.reads
    assert n and reads
    for prompt in ([1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11, 12]):
        router.add_request(prompt, max_new_tokens=6)
    ticks = 0
    while router.has_work():
        router.step()
        ticks += 1
    replica.health()
    assert ticks > 6 and len(record()) == n and clock.reads == reads


@pytest.mark.parametrize("steps", [2, 6])
def test_a_warm_fit_call_costs_the_same_whatever_its_steps(record, clock,
                                                           steps):
    engine = fit_twice()
    n, reads = len(record()), clock.reads
    engine.fit(Rows(8 * steps), epochs=1, batch_size=8)
    added = record()[n:]
    # a call's own three entries, no compile; six clock reads for them
    # (begin and end of each), one for the call's state placed and one for
    # its first step
    assert [e[0] for e in added] == ["fit.setup", "fit.writeback",
                                     "startup.fit_call"]
    assert added[-1][5]["steps"] == steps
    assert clock.reads - reads == 8


def test_the_cap_drops_and_counts(record, monkeypatch):
    monkeypatch.setattr(trace, "MAX_STARTUP_EVENTS", 3)
    for _ in range(5):
        with trace.startup_phase("startup.prepare"):
            pass
    rec = trace.startup_record()
    assert len(rec["entries"]) == 3 and rec["dropped"] == 2
    assert trace.startup_summary()["dropped"] == 2


# ---------------------------------------------------------------- surfaces
def hand_made(monkeypatch):
    """A record whose seconds are known: import 2, backend 3 by marks, a
    build of 1, a warm-up of 4 holding a program (trace 0.5, lower 0.25,
    backend 1.0, a miss), the caller's own compile before it, and one
    compile after READY."""
    compile_ = lambda name, t0, t1, parent, **kw: (  # noqa: E731
        name, "compile", t0, t1, 0, {"program": "jit(step)", **kw}, parent)
    entries = [
        ("startup.import", "startup", 100.0, 102.0, 0,
         {"before_package_s": 1.5, "source": "proc_stat"}, None),
        ("startup.backend", "startup", 102.5, 105.5, 0,
         {"bracketed": False}, None),
        compile_("compile.backend", 105.5, 106.0, None, cache="miss"),
        ("startup.engine_build", "startup", 106.0, 107.0, 0,
         {"replica": "r0", "pool_bytes": 1}, None),
        compile_("compile.trace", 107.0, 107.5, "startup.warmup", inner=9),
        compile_("compile.lower", 107.5, 107.75, "startup.warmup"),
        compile_("compile.backend", 108.0, 109.0, "startup.warmup",
                 cache="miss"),
        ("startup.warmup", "startup", 107.0, 111.0, 0,
         {"replica": "r0", "state": "READY"}, None),
        compile_("compile.backend", 120.0, 125.0, "startup.fit_call",
                 cache="miss"),
    ]
    monkeypatch.setattr(trace, "_startup", entries)
    trace._summaries.clear()
    return entries


def test_the_summary_adds_up(record, monkeypatch):
    hand_made(monkeypatch)
    for who in (None, "r0"):
        got = trace.startup_summary(who)
        assert got == {
            "ready_s": 11.0, "import_s": 2.0, "backend_s": 3.0,
            "build_s": 1.0, "warmup_s": 4.0, "fit_setup_s": 0.0,
            "trace_lower_s": 0.75, "compile_s": 1.0, "cache_misses": 1,
            "programs": 1, "entries": 9, "dropped": 0}
    other = trace.startup_summary("r1")
    assert other["ready_s"] is None and other["build_s"] == 0.0


def test_health_and_the_gauges_agree_with_the_record(record):
    replica = warm_replica()
    router = Router([replica])
    entries = record()
    summary = replica.health()["startup"]
    assert summary == trace.startup_summary(replica.lifecycle.name)
    (build,) = named(entries, "startup.engine_build")
    (warm,) = named(entries, "startup.warmup")
    assert summary["build_s"] == pytest.approx(build[3] - build[2])
    assert summary["warmup_s"] == pytest.approx(warm[3] - warm[2])
    mine = [e for e in entries if e[0] == "compile.backend"
            and e[6] in trace.PROGRAM_PHASES]
    assert summary["programs"] == len(mine) >= 3
    assert summary["compile_s"] == pytest.approx(
        sum(e[3] - e[2] for e in mine))
    assert 0 < summary["trace_lower_s"] < summary["warmup_s"]
    assert router.health()["startup"]["warmup_s"] == summary["warmup_s"]
    # the gauges are read at snapshot time, whatever the flag was
    assert not paddle.get_flags(["FLAGS_enable_metrics"])[
        "FLAGS_enable_metrics"]
    snap = json.loads(json.dumps(REGISTRY.snapshot()))
    seconds = {s["labels"][0]: s["value"]
               for s in snap["paddle_tpu_startup_seconds"]["series"]}
    whole = trace.startup_summary()
    assert seconds == {k[:-2]: v for k, v in whole.items()
                       if k.endswith("_s") and v is not None}
    assert seconds["warmup"] == summary["warmup_s"]
    (misses,) = snap["paddle_tpu_startup_cache_misses"]["series"]
    assert misses["value"] == whole["cache_misses"]
    assert 'paddle_tpu_startup_seconds{phase="warmup"}' in \
        REGISTRY.to_prometheus()


def test_the_engine_reports_its_summary(record):
    engine = fit_twice()
    got = engine.startup_summary()
    assert got == trace.startup_summary(engine._startup_name)
    assert got["programs"] >= 1 and got["compile_s"] > 0
    assert got["fit_setup_s"] > 0 and got["trace_lower_s"] > 0


def test_the_chrome_trace_begins_with_the_record(record, tmp_path):
    from paddle_tpu import profiler

    fit_twice()
    prof = profiler.Profiler(
        on_trace_ready=profiler.export_chrome_tracing(str(tmp_path)),
        timer_only=True)
    prof.start()
    with profiler.RecordEvent("steady"):
        pass
    prof.stop()
    with open(prof.trace_path) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in events]
    assert names.index("startup.fit_call") < names.index("steady")
    assert {"fit.setup", "compile.backend"} <= set(names)
    call = events[names.index("startup.fit_call")]
    assert call["cat"] == "startup" and call["args"]["steps"] == 2


# ----------------------------------------------------------- import, backend
def test_import_is_the_records_first_entry_in_a_fresh_process():
    import subprocess
    import sys
    code = ("import json, paddle_tpu as paddle\n"
            "from paddle_tpu.observability import trace\n"
            "paddle.seed(1)\n"
            "rec = trace.startup_record()\n"
            "print(json.dumps([rec['process_start'],"
            " [e[:4] + (e[5],) for e in rec['entries'][:2]]]))\n")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=300,
                         env={**__import__("os").environ,
                              "JAX_PLATFORMS": "cpu"})
    start, (imp, backend) = json.loads(out.stdout.splitlines()[-1])
    assert imp[0] == "startup.import" and imp[2] == start
    assert imp[4]["source"] == "proc_stat"
    assert 0 <= imp[4]["before_package_s"] < imp[3] - imp[2]
    # paddle.seed is the program's (and the process's) first device query
    assert backend[0] == "startup.backend" and backend[4] == {
        "bracketed": True}
    assert imp[3] <= backend[2] <= backend[3]


def test_a_backend_the_caller_brought_up_is_bounded_by_two_marks(
        record, clock, monkeypatch):
    monkeypatch.setattr(trace, "_backend", {"seen": False, "mark": None})
    trace.mark_backend()
    mark = trace._backend["mark"]
    trace.mark_backend()                        # the first stamp stands
    assert trace._backend["mark"] == mark
    paddle.nn.Linear(2, 2)                      # a Layer's construction
    (backend,) = named(record(), "startup.backend")
    assert backend[2] == mark and backend[5] == {"bracketed": False}
    reads = clock.reads
    paddle.nn.Linear(2, 2)
    paddle.seed(0)
    assert len(named(record(), "startup.backend")) == 1
    assert clock.reads == reads                 # one check of a flag
