"""Boundary spans (observability/trace.py BOUNDARY_SPANS): both sinks, the
annotation budget of a training step and a serving tick, and the names the
device side carries: programs, kernels, scopes."""
from __future__ import annotations

import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.auto_parallel import Engine
from paddle_tpu.inference import PagedEngine
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.observability import trace
from paddle_tpu.serving import Router


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation while 'a recording runs':
    counts what is entered, keeps the metadata it was given."""

    entered: list = []

    def __init__(self, name, **kwargs):
        self.name, self.meta = name, dict(kwargs)

    @staticmethod
    def is_enabled():
        return True

    def set_metadata(self, **kwargs):
        self.meta.update(kwargs)

    def __enter__(self):
        FakeAnnotation.entered.append(self)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def fake_profiler(monkeypatch):
    class Fake:
        TraceAnnotation = FakeAnnotation
        StepTraceAnnotation = FakeAnnotation
    FakeAnnotation.entered = []
    monkeypatch.setattr(trace, "_profiler", Fake)
    return FakeAnnotation.entered


@pytest.fixture
def buffer():
    trace.clear()
    trace.activate()
    yield
    trace.deactivate()
    trace.clear()


def tiny_gpt_engine():
    class LMLoss(nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids):
            return self.lm(ids, labels=ids)[1]

    paddle.seed(0)
    lm = GPTForCausalLM(GPTConfig(vocab_size=61, hidden_size=32,
                                  num_layers=1, num_heads=2, max_seq_len=16,
                                  use_flash_attention=False))
    net = LMLoss(lm)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    return Engine(net, loss=lambda loss, _y: loss, optimizer=opt)


class Rows:
    def __init__(self, n, seq=16, vocab=61):
        self.x = np.random.default_rng(0).integers(0, vocab, (n, seq))

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.x[i]


def tiny_replica():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=67, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=2, num_kv_heads=1, max_seq_len=64,
        use_flash_attention=False))
    return PagedEngine(model, max_batch=2, block_size=8, num_blocks=16,
                       max_blocks_per_seq=8)


# ------------------------------------------------------------- two sinks
def test_boundary_span_enters_both_sinks(fake_profiler, buffer):
    args = {"batch": 8}
    with trace.boundary("serving.tick", args=args):
        args["decode_slots"] = 3        # filled while the span is open
    (name, cat, t0, t1, _tid, got), = trace.drain()
    assert (name, cat) == ("serving.tick", "serving") and t1 >= t0
    assert got == {"batch": 8, "decode_slots": 3}
    (ann,) = fake_profiler
    assert ann.name == "serving.tick"
    # the annotation alone carries the thread's CPU seconds inside the span
    assert 0.0 <= ann.meta.pop("cpu_s") <= (t1 - t0) + 1e-3
    assert ann.meta == {"batch": 8, "decode_slots": 3}
    assert "cpu_s" not in args


def test_buffer_stays_empty_when_inactive(fake_profiler):
    trace.clear()
    assert not trace.active()
    with trace.boundary("fit.step", step_num=7):
        pass
    assert trace.drain() == []
    (ann,) = fake_profiler                # the recording still sees it
    assert ann.name == "fit.step" and ann.meta.pop("cpu_s") >= 0.0
    assert ann.meta == {"step_num": 7}


def test_no_annotation_object_without_a_recording(monkeypatch):
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
    with trace.boundary("fit.dispatch"):
        pass
    assert made == []


def test_only_listed_names_are_boundary_spans():
    with pytest.raises(KeyError):
        trace.boundary("matmul")
    for name, (cat, parent, what) in trace.BOUNDARY_SPANS.items():
        assert parent is None or parent in trace.BOUNDARY_SPANS, name
        assert cat and what


def test_a_real_recording_holds_the_span_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.boundary("router.step"):
            with trace.boundary("serving.tick", args={"tick": 3}):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = {ev.name: ev for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events}
    tick, step = events["serving.tick"], events["router.step"]
    stats = dict(tick.stats)
    assert 0.0 <= stats.pop("cpu_s") <= tick.duration_ns * 1e-9 + 1e-3
    assert stats == {"tick": 3}
    assert step.start_ns <= tick.start_ns
    assert (tick.start_ns + tick.duration_ns
            <= step.start_ns + step.duration_ns)


# ----------------------------------------------------- annotation budget
def boundary_only(annotations):
    """The step's or the tick's own annotations: a ``host.gc`` (HOST_SPANS,
    entered by the collector's hook while metrics are on) lands inside
    whichever span is open and belongs to none of them."""
    return [a for a in annotations if a.name in trace.BOUNDARY_SPANS]


def test_fit_step_enters_at_most_six_annotations(fake_profiler):
    engine = tiny_gpt_engine()
    engine.fit(Rows(24), epochs=1, batch_size=8)
    fake_profiler[:] = boundary_only(fake_profiler)
    names = [a.name for a in fake_profiler]
    assert names[0] == "fit.setup" and names[-1] == "fit.writeback"
    assert names.count("fit.dispatch") == 3
    # three steps and the fetch that finds the loader exhausted
    assert [a.meta["step_num"] for a in fake_profiler
            if a.name == "fit.step"] == [0, 1, 2, 3]
    per_step = [n for n in names
                if n in ("fit.step", "fit.next_batch", "fit.dispatch",
                         "fit.post_step", "io.prefetch")]
    assert len(per_step) <= 6 * 3
    assert set(names) == {"fit.setup", "fit.step", "fit.next_batch",
                          "fit.dispatch", "fit.post_step", "io.prefetch",
                          "fit.epoch_sync", "fit.writeback"}


def test_tick_enters_four_annotations_and_six_a_program(fake_profiler):
    """A launch enters plan, its bracket, build and launch, and behind it
    the wait for the program launched before and that one's emit: six, as
    when a program was read in the tick that launched it. The first launch
    of all has nothing to wait for (four), and the step that finds nothing
    to launch only reads (a wait and an emit)."""
    router = Router([tiny_replica()]).warmup()
    del fake_profiler[:]
    rid = router.add_request(list(range(1, 12)), max_new_tokens=3)
    ticks = []
    while router.has_work():
        before = len(fake_profiler)
        router.step()
        ticks.append([a.name
                      for a in boundary_only(fake_profiler[before:])])
    assert router.outcomes[rid].status == "FINISHED"
    # chunk + step, step, the read of the last step
    assert len(ticks) == 3
    launches = 0
    for names in ticks:
        programs = sum(n in ("serving.prefill", "serving.decode")
                       for n in names)
        waits = sum(n.endswith(".wait") for n in names)
        assert names.count("serving.emit") == waits
        assert waits == programs - (launches == 0) or (
            programs == 0 and waits == 1)
        plans = names.count("serving.plan")
        assert plans == programs or (programs == 0 and plans == 1)
        assert len(names) == 4 + plans + 3 * programs + 2 * waits
        launches += programs
    # a decode-only tick: ten, under the twelve the budget allows
    assert sorted(ticks[1]) == sorted([
        "router.step", "serving.tick", "serving.admit", "serving.plan",
        "serving.decode",
        "serving.decode.build", "serving.decode.launch",
        "serving.decode.wait", "serving.emit", "router.deliver"])
    # the wait sits under the bracket of the launch it follows, the emit
    # under the tick
    at = ticks[1].index
    assert at("serving.decode") < at("serving.decode.build") \
        < at("serving.decode.launch") < at("serving.decode.wait") \
        < at("serving.emit")
    # the last tick plans, finds no lane to feed and launches nothing: it
    # reads the unread step
    assert sorted(ticks[2]) == sorted([
        "router.step", "serving.tick", "serving.admit", "serving.plan",
        "serving.decode.wait", "serving.emit", "router.deliver"])
    # the first tick prefills (one chunk of 16 rows, 11 of them the
    # prompt) and decodes
    assert ticks[0].count("serving.prefill") == 1
    tick = next(a for a in fake_profiler if a.name == "serving.tick")
    assert tick.meta["prompt_tokens"] == 16
    assert tick.meta["decode_slots"] == 1 and tick.meta["queued"] == 1


# ------------------------------------------------ names on the device side
def _scoped(text, scope):
    """``scope`` as one component of an op's name-stack path, bare or
    inside jvp(...) / transpose(...)."""
    return re.search(r'[/(]%s[/)"]' % re.escape(scope), text) is not None


def _lower_train_step(engine, batch=8, seq=16):
    engine.prepare()
    pa = [p._data for p in engine._params]
    ids = jnp.zeros((batch, seq), jnp.int32)
    return engine._train_step.lower(pa, engine._init_opt_state(pa),
                                    jnp.float32(1e-3), ids, ids)


def _lower_serving(replica, phase):
    # the prefill program carries one slot, the decode step every lane
    b, t = ((1, replica.prefill_width) if phase == "prefill"
            else (replica.max_batch, 1))
    host = (np.zeros((b, t), np.int32), np.full((b,), t, np.int32),
            replica.tables[:b], np.zeros((b,), np.float32),
            np.ones((b,), np.float32), np.zeros((b,), np.int32),
            np.zeros((b,), np.int32))
    return replica._fns[phase].lower(*replica._chunk_args(*host),
                                     sampling=False)


def test_train_step_carries_its_name_and_scopes():
    text = _lower_train_step(tiny_gpt_engine()).as_text(debug_info=True)
    assert "module @jit_engine_train_step" in text
    # the head's product is inside ``loss`` (``models/_head.py``); a forward
    # without labels, the serving programs', has it under ``lm_head``
    for scope in ("embed", "attn", "mlp", "loss", "optimizer"):
        assert _scoped(text, scope), scope


@pytest.mark.parametrize("phase,module", [
    ("prefill", "jit_paged_prefill_chunk"),
    ("decode", "jit_paged_decode_step")])
def test_serving_programs_carry_their_names_and_scopes(phase, module):
    text = _lower_serving(tiny_replica(), phase).as_text(debug_info=True)
    assert f"module @{module}" in text
    for scope in ("embed", "attn", "mlp", "lm_head", "paged_attention"):
        assert _scoped(text, scope), scope


def test_flash_kernels_carry_their_names_in_the_lowered_custom_calls():
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return fa._flash_attention(q, k, v, True, 0.125, 128, 128).sum()

    q = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, q).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert len(calls) == 3
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert sum(f'kernel_name = "{name}"' in c for c in calls) == 1


@pytest.mark.parametrize("program", ["train_step", "prefill_chunk"])
def test_scopes_leave_the_compiled_program_unchanged(program, monkeypatch):
    def flops():
        if program == "train_step":
            lowered = _lower_train_step(tiny_gpt_engine())
        else:
            lowered = _lower_serving(tiny_replica(), "prefill")
        cost = lowered.compile().cost_analysis()
        return cost["flops"], cost["bytes accessed"]

    with_scopes = flops()
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    assert flops() == with_scopes
    assert with_scopes[0] > 0
