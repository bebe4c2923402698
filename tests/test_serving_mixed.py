"""One weight stream a tick: where a tick holds a prefill chunk to launch AND
a decode step with a lane to feed, the tick's last chunk and the step go out
as ONE program, ``paged_mixed_step`` (``inference/serving.py``): what is
row-wise runs once over the chunk's rows and the lanes' rows, every mixer
runs on each group's own rows.

What that may not change is a served token. As in
``tests/test_serving_overlap.py`` the reference is the same engine over the
same model with every program read before the next is launched (``serial``:
``_overlap`` off, the schedule ``speculate=`` engines keep), whose ticks
never mix; each case names what a mixed step has to meet on the way.
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import jax  # noqa: E402

import paddle_tpu as paddle  # noqa: E402

from served import (LENGTHS, NEW, assert_greedy, engine, model_of,  # noqa: E402,F401
                    models, prompts_of, quiesced, serve)

#: an adapter each: Llama, GPT, nemotron_h, exaone_moe, deepseek_v3
KINDS = ["dense", "gpt", "hybrid", "window", "latent"]


def watch(eng):
    """What the engine's mixed steps met, counted at their launch."""
    seen = {"mixed": 0, "final": 0, "nonfinal": 0, "sentinel": 0,
            "stalled": 0, "reused": 0, "sampled": 0}
    run, plan = eng._run_chunk, eng._plan_decode
    last = {}

    def plan_spy(active, aboard=None):
        last["plan"] = plan(active, aboard)
        return last["plan"]

    def run_spy(rec, *rows, **kw):
        if rec.kind == "mixed":
            chunk = rec.meta["chunk"]
            final = chunk["chunk"] + 1 == chunk["n_chunks"]
            seen["mixed"] += 1
            seen["final" if final else "nonfinal"] += 1
            # a slot mid-prefill beside the chunk's own rides the decode
            # group as a seq = 0 lane
            seen["sentinel"] += len(eng._prefilling) > (not final)
            seen["stalled"] += bool(last["plan"][-1])
            seen["reused"] += any(t > 0 for _slot, t in rec.lanes)
            seen["sampled"] += bool(np.any(rows[3] > 0)
                                    or np.any(kw["aboard"][3] > 0))
        return run(rec, *rows, **kw)

    eng._plan_decode, eng._run_chunk = plan_spy, run_spy
    return seen


def both(kind, prompts, new=NEW, budget=16, sampled=False, **kw):
    """``(tokens of the serial schedule, tokens of the mixing engine, what
    its mixed steps met, its health)``."""
    want = serve(engine(kind, serial=True, budget=budget, **kw), prompts,
                 new=new, sampled=sampled, streams=False)
    eng = engine(kind, budget=budget, **kw)
    seen = watch(eng)
    got = serve(eng, prompts, new=new, sampled=sampled)
    return want, got, seen, eng.health()


# ------------------------------------------------------------ (a) the tokens
@pytest.mark.parametrize("kind", KINDS)
def test_chunks_aboard_a_step_serve_the_serial_schedules_tokens(kind):
    """Seven requests through four slots under a budget of one 16-token
    chunk a tick: steps carry non-final and final chunks, lanes mid-prefill
    ride as sentinels, and a slot's second tenant prefills and decodes in
    rows its first one wrote."""
    want, got, seen, health = both(kind, prompts_of(kind, LENGTHS))
    assert got == want
    assert seen["final"] and seen["nonfinal"] and seen["sentinel"]
    assert seen["reused"]
    assert 0 < health["mixed_share"] <= 1


@pytest.mark.parametrize("kind", KINDS)
def test_whole_prompt_prefill_mixes_its_last_chunk_only(kind):
    """The default scheduler prefills a whole prompt in its tick: the chunks
    before the last stay programs of their own."""
    prompts = prompts_of(kind, LENGTHS, seed=2)
    want = serve(engine(kind, serial=True, budget=None), prompts,
                 streams=False)
    eng = engine(kind, budget=None)
    seen = watch(eng)
    kinds = []
    run = eng._run_chunk
    eng._run_chunk = lambda rec, *a, **kw: (kinds.append(
        (rec.tick, rec.kind)), run(rec, *a, **kw))[-1]
    assert serve(eng, prompts) == want
    assert seen["mixed"] and not seen["nonfinal"]
    by_tick = {}
    for tick, kind_ in kinds:
        by_tick.setdefault(tick, []).append(kind_)
    # a tick's programs: chunks alone, then a step, alone or with the last
    # chunk aboard
    assert any(ks[:-1] and ks[-1] == "mixed" for ks in by_tick.values())
    for ks in by_tick.values():
        assert "mixed" not in ks[:-1] and ks.count("decode") <= 1


@pytest.mark.parametrize("kind", KINDS)
def test_a_memory_stalled_lane_rides_a_mixed_step_as_a_sentinel(kind):
    """Ten usable blocks of 8 for three requests that come to need six, five
    and eight: a lane finds no block for its next token while another
    slot's chunk is aboard the step (it rides as a sentinel and is fed
    again once blocks free), one lane is preempted and prefilled again."""
    want, got, seen, _health = both(
        kind, prompts_of(kind, (20, 9, 33), seed=4), new=(24, 24, 24),
        usable=10)
    assert got == want
    assert seen["mixed"] and seen["stalled"]


@pytest.mark.parametrize("kind", [k for k in KINDS if k != "latent"])
def test_int8_pages_serve_the_serial_schedules_tokens(kind):
    """(A latent pool has no int8 form: the engine refuses the pair.)"""
    want, got, seen, _health = both(kind, prompts_of(kind, LENGTHS, seed=5),
                                    kv_dtype="int8")
    assert got == want and seen["final"] and seen["nonfinal"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_sampled_request_draws_the_same_key_the_same_token(kind):
    """The key of a sampled token is folded from (seed, request, position):
    whichever program samples it, alone or with another group's rows beside
    its own, it is the same token."""
    want, got, seen, _health = both(kind, prompts_of(kind, LENGTHS, seed=6),
                                    sampled=True)
    assert got == want and seen["sampled"]


def test_first_token_of_a_final_chunk_aboard_comes_from_its_lane():
    """The slot a final chunk finishes is not fed by the step that carries
    it: its first token is that program's output, in its own lane, and the
    slot joins the next tick's step."""
    a, b = prompts_of("dense", (9, 20), seed=7)
    want = serve(engine(serial=True, budget=16), [a, b], new=(8, 4),
                 streams=False)
    eng = engine(budget=16)
    first = eng.add_request(a, max_new_tokens=8)
    eng.step()
    late = eng.add_request(b, max_new_tokens=4)
    launched = []
    run = eng._run_chunk
    eng._run_chunk = lambda rec, *rows, **kw: (launched.append(
        (rec.kind, rows[1].copy(), list(rec.lanes))), run(rec, *rows,
                                                          **kw))[-1]
    eng.step()          # chunk 1 of 2 aboard the first request's step
    eng.step()          # the final chunk aboard
    assert [k for k, _seq, _lanes in launched] == ["mixed", "mixed"]
    _kind, seq, lanes = launched[1]
    assert seq[1] == 0 and (1, 0) in lanes and (0, 0) in lanes
    eng.step()          # its first token is read, the slot decodes
    assert launched[2][0] == "decode" and launched[2][1][1] == len(b) + 1
    out = eng.run_to_completion()
    assert [out[first], out[late]] == [want[0], want[1]]
    quiesced(eng)


# ------------------------------------------- (b) who keeps their programs
@pytest.mark.parametrize("kind", ["dense", "gpt", "latent"])
def test_a_speculative_engine_never_mixes(kind):
    """A verify step's accepted count decides the next rows, so a
    ``speculate=`` engine reads in the launching tick and keeps chunk and
    step apart."""
    prompts = [p + p for p in prompts_of(kind, (7, 12, 9), seed=8)]
    want = serve(engine(kind, serial=True, budget=16), prompts,
                 new=(8, 8, 8), streams=False)
    eng = engine(kind, budget=16, speculate="ngram")
    seen = watch(eng)
    assert serve(eng, prompts, new=(8, 8, 8)) == want
    assert not seen["mixed"] and eng.health()["mixed_share"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_one_tick_a_share_window_launches_chunk_and_step_apart(kind):
    """Every ``share_window_ticks``-th tick that could mix sends its chunk
    and its step out as the programs they were (each program's own device
    time stays readable where every step would ride a chunk), and serves
    the same tokens."""
    from paddle_tpu.serving import SchedulerConfig
    prompts = prompts_of(kind, LENGTHS, seed=14)
    want = serve(engine(kind, serial=True), prompts, streams=False)
    eng = engine(kind, scheduler=SchedulerConfig(
        prefill_token_budget=16, share_window_ticks=3))
    kinds = {}
    run = eng._run_chunk
    eng._run_chunk = lambda rec, *a, **kw: (kinds.setdefault(
        rec.tick, []).append(rec.kind), run(rec, *a, **kw))[-1]
    assert serve(eng, prompts) == want
    ticks = list(kinds.values())
    # (a tick whose lanes all wait for their last token sends its chunk
    # alone as well, so at least one in three)
    assert eng._mixable >= 6
    assert ticks.count(["prefill", "decode"]) >= eng._mixable // 3
    assert ticks.count(["mixed"]) >= eng._mixable // 2
    assert 0 < eng.health()["mixed_share"] < 1


def test_an_engine_of_one_lane_never_mixes():
    eng = engine(max_batch=1).warmup()
    serve(eng, prompts_of("dense", (20, 9), seed=9), new=(4, 4))
    assert eng.health()["mixed_share"] == 0


def test_mixed_share_before_any_step_and_in_the_registry():
    from paddle_tpu.observability import metrics
    assert engine().health()["mixed_share"] is None
    paddle.set_flags({"FLAGS_enable_metrics": True})
    try:
        eng = engine()
        serve(eng, prompts_of("dense", LENGTHS), streams=False)
        text = metrics.REGISTRY.to_prometheus()
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})
    assert eng.health()["mixed_share"] > 0
    for kind in ("prefill", "decode", "mixed"):
        assert (f'paddle_tpu_serving_launches_total{{overlapped="true",'
                f'kind="{kind}"}}') in text


# -------------------------------------- nothing compiles after the warm-up
@pytest.mark.parametrize("kind", KINDS)
def test_no_mixed_tick_compiles_after_warmup(kind):
    """``warmup()`` admits a second synthetic request while the first
    decodes, so the third program is compiled (and the hand-over of a fed
    token after it) before the window's first mixed tick."""
    eng = engine(kind).warmup()
    assert eng.health()["mixed_share"] > 0
    from jax._src import monitoring

    compiles = []

    def listen(name, _seconds, **_kw):
        if "backend_compile" in name:
            compiles.append(name)

    seen = watch(eng)
    monitoring.register_event_duration_secs_listener(listen)
    try:
        serve(eng, prompts_of(kind, LENGTHS, seed=12), streams=False)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert seen["final"] and seen["nonfinal"]
    assert compiles == []


def test_warmup_leaves_nothing_behind_and_early_traffic_is_served():
    eng = engine()
    p = prompts_of("dense", (9,), seed=13)[0]
    early = eng.add_request(p, max_new_tokens=3)
    eng.warmup()
    assert eng._unread is None and eng.lifecycle.ready()
    assert not eng.outcomes or set(eng.outcomes) == {early}
    assert_greedy(model_of("dense"), p, eng.run_to_completion()[early], 3)
    quiesced(eng)


# ------------------- (c) the unmixed programs are the ones they were before
def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), tree)


@pytest.mark.parametrize("kind", KINDS)
def test_chunk_only_and_step_only_calls_are_the_programs_they_were(kind):
    """A tick with only a chunk, or only a step, launches what it launched
    before there was a third program: ``jit_paged_prefill_chunk`` over (1,
    W) rows and ``jit_paged_decode_step`` over (B, 1) rows, each with the
    parameters, the pools, one group's seven row arrays, the key and the
    states (and the chunk's slot only where a layer keeps state a slot),
    returning (tokens, pools, states); no rider's rows, no second group."""
    eng = engine(kind, budget=16)
    calls = {}
    for name, fn in list(eng._fns.items()):
        def spy(*args, _fn=fn, _name=name, **kw):
            calls.setdefault(_name, (_fn, args, kw))
            return _fn(*args, **kw)
        eng._fns[name] = spy
    # one request alone: chunks, then steps, never both in a tick
    serve(eng, prompts_of(kind, (20,), seed=14), new=(3,), streams=False)
    assert set(calls) == {"prefill", "decode"}
    n_params = len(eng._params)
    W, B, blocks = eng.prefill_width, eng.max_batch, eng.max_blocks_per_seq
    pools = _shapes((eng.kc, eng.vc))
    states = _shapes(eng.state)
    key = _shapes(eng._base_key)
    for name, module, rows, width in (
            ("prefill", "jit_paged_prefill_chunk", 1, W),
            ("decode", "jit_paged_decode_step", B, 1)):
        fn, args, kw = calls[name]
        assert set(kw) == {"sampling"}
        text = fn.lower(*args, **kw).as_text()
        assert f"module @{module} " in text.splitlines()[0]
        per_row = [((rows,), "int32"), ((rows, blocks), "int32"),
                   ((rows,), "float32"), ((rows,), "float32"),
                   ((rows,), "int32"), ((rows,), "int32")]
        want = [_shapes([p._data for p in eng._params]), *pools,
                ((rows, width), "int32"), *per_row, key, states]
        if name == "prefill" and eng._has_slot_state:
            want.append(((1,), "int32"))
        assert len(args[0]) == n_params
        assert _shapes(list(args)) == want
