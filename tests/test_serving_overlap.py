"""One program in flight: the serving tick launches its program(s) before it
reads the tokens of the program launched before, and ``step()`` returns with
its last program still running (``inference/serving.py``).

What that may not change is a served token. The reference here is the same
engine over the same model with every program read before the next is
launched (``serial``: ``_overlap`` off, the path ``speculate=`` engines
take), so each case compares two schedules of the engine's own programs;
the dense greedy cases are also held against the model's own ``generate``.
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.join(ROOT, "tests"))

import jax  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.fault import inject  # noqa: E402
from paddle_tpu.inference import PagedEngine  # noqa: E402
from paddle_tpu.inference.resilience import ReplicaState, RequestStatus  # noqa: E402
from paddle_tpu.serving import Router  # noqa: E402

from served import (LENGTHS, NEW, assert_greedy, engine, model_of,  # noqa: E402,F401
                    models, prompts_of, quiesced, serve)


# ------------------------------------------------------------ (a) the tokens
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("budget", [16, None], ids=["chunked", "whole"])
@pytest.mark.parametrize("kind", ["dense", "hybrid", "window", "latent"])
def test_tokens_are_those_of_programs_run_one_at_a_time(kind, budget,
                                                        sampled):
    prompts = prompts_of(kind, LENGTHS)
    want = serve(engine(kind, serial=True, budget=budget), prompts,
                 sampled=sampled)
    eng = engine(kind, budget=budget)
    got = serve(eng, prompts, sampled=sampled)
    assert got == want
    assert eng.health()["overlap_share"] > 0.5
    if kind == "dense" and not sampled:
        for i, p in enumerate(prompts):
            assert_greedy(model_of(kind), p, got[i], NEW[i])


def test_a_decode_step_feeds_on_the_unread_steps_tokens():
    """The lane's next token never leaves the device between two steps: the
    second step's feed is the hand-over program's output, not a host array."""
    eng = engine()
    seen = []
    run = eng._run_chunk

    def spy(rec, tokens, *a, **kw):
        if rec.phase == "decode":
            seen.append((isinstance(tokens, jax.Array),
                         eng._unread is not None))
        return run(rec, tokens, *a, **kw)

    eng._run_chunk = spy
    eng.add_request(prompts_of("dense", [5])[0], max_new_tokens=4)
    eng.run_to_completion()
    # fed after the final chunk, then twice after a decode step
    assert seen == [(True, True)] * 3


# ------------------------------------------------- (b) an EOS learnt late
def test_eos_mid_stream_ends_the_request_where_it_is_read():
    prompts = prompts_of("dense", (11, 19), seed=3)
    plain = serve(engine(serial=True, max_batch=1), prompts, new=(12, 8),
                  streams=False)
    eos = next(t for j, t in enumerate(plain[0])
               if 2 <= j < 10 and t not in plain[0][:j])
    cut = plain[0].index(eos) + 1
    want_next = plain[1][:plain[1].index(eos) + 1] if eos in plain[1] \
        else plain[1]
    eng = engine(max_batch=1, eos_id=eos)
    rids = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, (12, 8))]
    bufs = [eng.open_stream(r) for r in rids]
    fed = []
    plan = eng._plan_decode
    eng._plan_decode = lambda active, *aboard: fed.append(
        [int(eng._inflight[i]) for i in active]) or plan(active, *aboard)
    eng.run_to_completion()
    # the step after the EOS step was launched before the EOS was read:
    # its token is dropped
    assert eng.outcomes[rids[0]].tokens == plain[0][:cut] == bufs[0]
    assert len(plain[0][:cut]) < 12
    # the slot's next tenant starts clean, in blocks the first one wrote
    assert eng.outcomes[rids[1]].tokens == want_next == bufs[1]
    assert [1] in fed
    quiesced(eng)


# ------------------------- (c) a request ends while its program is unread
class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _midstream(eng):
    """Tick until every request decodes and a decode step is unread."""
    for _ in range(50):
        eng.step()
        if (eng._unread is not None and eng._unread.kind == "decode"
                and not eng._prefilling and not eng.queue):
            return
    raise AssertionError("never got mid-stream")


def _finish(eng, rids, bufs):
    eng.run_to_completion(max_ticks=2000)
    for rid, buf in zip(rids, bufs):
        assert eng.outcomes[rid].tokens == buf
    quiesced(eng)


@pytest.mark.parametrize("how", ["cancel", "cancel_last", "deadline",
                                 "recover", "evict"])
def test_a_request_ends_with_its_program_unread(how):
    prompts = prompts_of("dense", (9, 14, 20), seed=4)
    want = serve(engine(serial=True), prompts, new=(10, 10, 10),
                 streams=False)
    clock = FakeClock()
    kw = {}
    if how == "evict":      # 5 usable blocks of 16 for three lanes
        kw = dict(block_size=16, num_blocks=6, max_blocks_per_seq=6,
                  max_batch=3)
        want = serve(engine(serial=True, **kw), prompts, new=(24, 24, 24),
                     streams=False)
    eng = engine(**kw)
    eng._clock = clock
    new = 24 if how == "evict" else 10
    rids = [eng.add_request(p, max_new_tokens=new,
                            deadline_s=5.0 if (how == "deadline" and i == 1)
                            else None)
            for i, p in enumerate(prompts)]
    bufs = [eng.open_stream(r) for r in rids]
    if how == "evict":
        evicted = []
        evict = eng._evict
        eng._evict = lambda slot: (
            evicted.append((slot, eng._unread)), evict(slot))[-1]
        _finish(eng, rids, bufs)
        # a victim is chosen on what the host has read: nothing is unread
        assert evicted and all(unread is None for _s, unread in evicted)
        assert {i: eng.outcomes[r].tokens for i, r in enumerate(rids)} \
            == want
        return
    _midstream(eng)
    unread = eng._unread
    assert (1, int(eng._tenancy[1])) in unread.lanes
    before = list(bufs[1])
    if how == "cancel":
        assert eng.cancel(rids[1])
        assert eng._unread is unread        # no wait for the chip
        assert eng.outcomes[rids[1]].status == RequestStatus.CANCELLED
    elif how == "cancel_last":
        for r in (rids[0], rids[2], rids[1]):
            assert eng.cancel(r)
        assert eng._unread is None and not eng.has_work()
    elif how == "deadline":
        clock.t = 6.0
        eng.step()
        assert eng.outcomes[rids[1]].status == RequestStatus.DEADLINE_MISSED
    else:
        eng.lifecycle.degrade("drill")
        eng.recover()
        assert eng._unread is None and eng.lifecycle.ready()
        assert len(bufs[1]) == len(before) + 1      # read, not dropped
    if how != "recover":
        # the token that was in flight is dropped, not half delivered
        assert eng.outcomes[rids[1]].tokens == before == bufs[1]
        assert before == want[1][:len(before)]
    _finish(eng, rids, bufs)
    if how != "cancel_last":
        for i in ((0, 1, 2) if how == "recover" else (0, 2)):
            assert eng.outcomes[rids[i]].tokens == want[i]


# ------------------------------- (d) a program's failure shows at its read
def test_a_program_failure_surfaces_at_the_read_and_the_engine_serves_on():
    prompts = prompts_of("dense", (9, 14, 20), seed=6)
    eng = engine()
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts[:2]]
    bufs = [eng.open_stream(r) for r in rids]
    _midstream(eng)
    failing = eng._unread
    with inject.armed("serving.program_failure", tick=failing.tick):
        assert eng.step() == {}             # never raises
    assert eng.tick_failures == 1
    assert eng.lifecycle.state == ReplicaState.DEGRADED
    for rid, buf in zip(rids, bufs):
        oc = eng.outcomes[rid]
        assert oc.status == RequestStatus.FAILED and oc.tokens == buf
        assert f"tick {failing.tick + 1} failed" in oc.detail
    quiesced(eng)           # the newer launch went with the failed one
    later = eng.add_request(prompts[2], max_new_tokens=5)
    out = eng.run_to_completion()
    assert_greedy(model_of("dense"), prompts[2], out[later], 5)
    eng.recover()
    assert eng.lifecycle.ready()
    quiesced(eng)


# --------------------------------------- (e) nothing is left unread behind
@pytest.mark.parametrize("how", ["step", "run_to_completion", "drain",
                                 "warmup", "router"])
def test_nothing_is_left_unread(how):
    eng = engine()
    p = prompts_of("dense", (9,), seed=8)[0]
    if how == "warmup":
        early = eng.add_request(p, max_new_tokens=3)
        eng.warmup()
        assert eng._unread is None and eng.lifecycle.ready()
        assert_greedy(model_of("dense"), p, eng.run_to_completion()[early], 3)
        return quiesced(eng)
    front = Router([eng]).warmup() if how == "router" else eng
    rid = front.add_request(p, max_new_tokens=3)
    if how in ("step", "router"):
        done, seen = {}, []
        buf = (front.stream(rid)._buf if how == "router"
               else eng.open_stream(rid))
        while front.has_work():
            done.update(front.step())
            seen.append(len(buf))
        # chunk + step (the chunk's token is read behind the step's
        # launch), a step, and the read of the last one
        assert seen == [1, 2, 3]
        got = done[rid]
    elif how == "run_to_completion":
        got = front.run_to_completion()[rid]
    else:
        eng.step()
        assert eng._unread is not None and eng.has_work()
        got = eng.drain()[rid]
        assert eng.lifecycle.state == ReplicaState.STOPPED
    assert_greedy(model_of("dense"), p, got, 3)
    quiesced(eng)


def test_an_engine_with_an_unread_program_has_work():
    eng = engine()
    rid = eng.add_request(prompts_of("dense", (9,))[0], max_new_tokens=1)
    assert eng.step() == {}
    # the request's one token is in flight: no queue, a held slot
    assert eng._unread is not None and eng.has_work()
    assert eng.slots[0].generated == []
    done = eng.step()                   # nothing to launch: reads it
    assert list(done) == [rid] and len(done[rid]) == 1
    quiesced(eng)


# ---------------------------- (f) who still reads in the launching tick
def test_speculative_engine_reads_in_the_launching_tick():
    p = prompts_of("dense", (12,), seed=9)[0]
    eng = engine(speculate="ngram", budget=None)
    rid = eng.add_request(p + p, max_new_tokens=8)
    while eng.has_work():
        eng.step()
        assert eng._unread is None
    assert_greedy(model_of("dense"), p + p, eng.outcomes[rid].tokens, 8)
    h = eng.health()
    assert h["overlap_share"] == 0 and h["host_late_share"] is not None
    quiesced(eng)


def test_dense_scorer_reads_in_the_launching_tick():
    from paddle_tpu.models import DLRM, dlrm_tiny
    paddle.seed(0)
    model = DLRM(dlrm_tiny())
    eng = PagedEngine(model, max_batch=4)
    rid = eng.add_request([3] * model.serve_dense_width)
    done = eng.step()
    assert list(done) == [rid] and eng._unread is None
    assert not eng.has_work()
    assert eng.health()["overlap_share"] is None     # launches no program


# --------------------------------------------------------- (g) snapshots
@pytest.mark.parametrize("array", ["tables", "seq_lens", "last_token"])
def test_host_arrays_handed_to_a_launch_are_snapshots(array):
    prompts = prompts_of("dense", (9, 30), seed=10)
    want = serve(engine(serial=True), prompts, new=(8, 8), streams=False)
    eng = engine()
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    while eng.has_work():
        eng.step()
        # what the next tick's admission would do to a reused lane, while
        # the program just launched may still be reading its arguments
        saved = getattr(eng, array).copy()
        getattr(eng, array)[...] = 0
        jax.block_until_ready([eng.kc, eng.vc])
        getattr(eng, array)[...] = saved
    assert {i: eng.outcomes[r].tokens for i, r in enumerate(rids)} == want


# ------------------------------------------------------ (h) the counters
def test_overlap_share_of_a_saturated_run():
    eng = engine(max_batch=4).warmup()
    prompts = prompts_of("dense", [12] * 12, seed=11)
    serve(eng, prompts, new=[16] * 12, streams=False)
    h = eng.health()
    assert h["overlap_share"] > 0.9
    assert 0.0 <= h["host_late_share"] <= 1.0
    assert h["active"] == 0 and h["prefilling"] == 0


def test_counters_reach_the_registry():
    from paddle_tpu.observability import metrics
    paddle.set_flags({"FLAGS_enable_metrics": True})
    try:
        eng = engine()
        serve(eng, prompts_of("dense", (9,)), new=(4,), streams=False)
        text = metrics.REGISTRY.to_prometheus()
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})
    assert ('paddle_tpu_serving_launches_total{overlapped="true",'
            'kind="decode"}') in text
    assert "paddle_tpu_serving_reads_total{host_late=" in text


# -------------------------------------- nothing compiles after the warm-up
@pytest.mark.parametrize("kind", ["dense", "window", "latent"])
def test_no_program_compiles_after_warmup(kind):
    """The window's traffic meets every shape and every kind of argument in
    ``warmup()``: the two programs, and the hand-over of the fed token after
    a chunk and after a step."""
    eng = engine(kind).warmup()
    from jax._src import monitoring

    compiles = []

    def listen(name, _seconds, **_kw):
        if "backend_compile" in name:
            compiles.append(name)

    monitoring.register_event_duration_secs_listener(listen)
    try:
        serve(eng, prompts_of(kind, LENGTHS, seed=12), streams=False)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
