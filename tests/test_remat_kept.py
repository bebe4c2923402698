"""What a rematerialised block keeps (``models/_remat.py``): its input, for
each flash-attention call inside it the forward kernel's two residuals under
the names of ``residuals.KEPT_RESIDUALS``, so that the backward runs every
forward kernel once and not twice, and, while the program's byte budget
lasts, the outputs of its ``F.linear`` products (``residuals.LINEAR_OUT``),
so that the backward runs three of a block's four projections once and not
twice. On the CPU with the kernels interpreted: the kernel calls and the
products of a gradient counted in its jaxpr, the saved residuals of one
block, the gradients against the bare ``jax.checkpoint`` and against the
flash pair alone bit for bit, the budget, the programs that hold no remat
byte for byte and name for name, and the note on the start-up record."""
from __future__ import annotations

import logging
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional  # noqa: F401  (the module below, by name)
import paddle_tpu.ops.pallas.flash_attention as fa
from paddle_tpu.core import residuals
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.models import GPTConfig, GPTForCausalLM, Lfm2MoeForCausalLM
from paddle_tpu.models import _remat
from paddle_tpu.models._remat import remat_block
from paddle_tpu.models.lfm2_moe import lfm2_moe_tiny
from paddle_tpu.observability import trace

functional = sys.modules["paddle_tpu.nn.functional.flash_attention"]

LAYERS, HEADS, WIDTH, SEQ, BATCH = 3, 2, 64, 64, 4
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


@pytest.fixture(autouse=True)
def kernels_on_the_cpu(monkeypatch):
    """The models take the Pallas path and the kernels run interpreted."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    monkeypatch.setattr(functional, "_use_pallas", lambda *a, **k: True)


@pytest.fixture
def one_device_mesh():
    before = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh_mod.build_mesh(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_mesh(before)


@pytest.fixture
def dp_mesh():
    before = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": 4},
                                          devices=jax.devices()[:4]))
    yield
    mesh_mod.set_mesh(before)


def keep_nothing(monkeypatch):
    """``remat_block`` as it was: a ``jax.checkpoint`` that keeps nothing."""
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *_names: jax.checkpoint_policies.nothing_saveable)


@pytest.fixture
def bare_checkpoint(monkeypatch):
    keep_nothing(monkeypatch)


def flash_pair_alone(monkeypatch):
    """``remat_block`` as PR 40 left it: no byte of budget for the
    projections' outputs, so a policy holds the flash pair alone."""
    monkeypatch.setattr(_remat, "_kept_budget", lambda: 0)


@pytest.fixture
def no_budget(monkeypatch):
    flash_pair_alone(monkeypatch)


def gpt(recompute=True):
    paddle.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=WIDTH, num_layers=LAYERS,
        num_heads=HEADS, max_seq_len=SEQ, recompute=recompute))


def lfm2():
    paddle.seed(0)
    return Lfm2MoeForCausalLM(lfm2_moe_tiny(recompute=True))


def loss_and_arrays(model, vocab):
    """(loss_of(arrays), arrays, names) over the model's trained leaves."""
    named = [(n, p) for n, p in model.named_parameters()
             if not p.stop_gradient]
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, vocab, (BATCH, SEQ)))

    def loss_of(arrays):
        olds = [p._data for _n, p in named]
        for (_n, p), a in zip(named, arrays):
            p._data = a
        try:
            return model(Tensor(ids), labels=Tensor(ids))[1]._data
        finally:
            for (_n, p), o in zip(named, olds):
                p._data = o

    return loss_of, [p._data for _n, p in named], [n for n, _p in named]


def kernel_calls(jaxpr):
    """{kernel name: ``pallas_call`` equations} of a jaxpr and all inside."""
    counts = dict.fromkeys(KERNELS, 0)

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                counts[name] = counts.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return counts


def gradient_calls(model, vocab):
    loss_of, arrays, _names = loss_and_arrays(model, vocab)
    return kernel_calls(jax.make_jaxpr(jax.grad(loss_of))(arrays).jaxpr)


# ------------------------------------------------- the forward kernel, once
MODELS = {"gpt": (gpt, 128, LAYERS), "lfm2_moe": (lfm2, 256, 1)}


@pytest.mark.parametrize("which", sorted(MODELS))
def test_a_gradient_runs_each_forward_kernel_once(which, one_device_mesh):
    make, vocab, calls = MODELS[which]
    assert gradient_calls(make(), vocab) == dict.fromkeys(KERNELS, calls)


@pytest.mark.parametrize("which", sorted(MODELS))
def test_the_bare_checkpoint_ran_it_twice(which, one_device_mesh,
                                          bare_checkpoint):
    make, vocab, calls = MODELS[which]
    assert gradient_calls(make(), vocab) == {
        "flash_fwd": 2 * calls, "flash_dq": calls, "flash_dkv": calls}


def test_the_same_under_shard_map_over_a_dp_mesh(dp_mesh):
    """On a mesh of several devices the kernel runs in a full-manual
    ``shard_map`` (``_flash_pallas``): the policy reaches into its body."""
    loss_of, arrays, _names = loss_and_arrays(gpt(), 128)
    jaxpr = jax.make_jaxpr(jax.grad(loss_of))(arrays).jaxpr
    assert "shard_map" in str(jaxpr)
    assert kernel_calls(jaxpr) == dict.fromkeys(KERNELS, LAYERS)


def test_the_dp_engine_step_holds_one_forward_call_a_layer(dp_mesh):
    """The Engine's own step over the suite's host devices, lowered."""
    from paddle_tpu import nn
    from paddle_tpu.distributed.auto_parallel import Engine

    class LMLoss(nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids):
            return self.lm(ids, labels=ids)[1]

    net = LMLoss(gpt())
    engine = Engine(net, loss=lambda loss, _y: loss,
                    optimizer=paddle.optimizer.AdamW(
                        learning_rate=1e-3, parameters=net.parameters()))
    engine.prepare()
    arrays = [p._data for p in engine._params]
    ids = jnp.zeros((BATCH, SEQ), jnp.int32)
    jaxpr = jax.make_jaxpr(engine._train_step.__wrapped__)(
        arrays, engine._init_opt_state(arrays), jnp.float32(1e-3), ids, ids)
    assert kernel_calls(jaxpr.jaxpr) == dict.fromkeys(KERNELS, LAYERS)


# ------------------------------------------------------- what a block keeps
def handed_in(what):
    """A saved residual the block was given: its input, or a parameter it
    closes over (``saved_residuals`` calls that a constant)."""
    return "argument" in what or "constant" in what


def test_a_block_keeps_its_arguments_and_the_two_named_arrays(
        one_device_mesh, no_budget):
    """``saved_residuals`` is not public: only shapes, dtypes and names are
    pinned. The logsumexp is kept as the kernel writes it, a lane-dense row
    a head, the heads of a 128-lane block together, (B, H // hpb, hpb, S):
    no (.., S, 1) column exists to keep."""
    from jax._src.ad_checkpoint import saved_residuals
    model = gpt()
    blk = model.gpt.blocks[0]
    x = jnp.ones((BATCH, SEQ, WIDTH), jnp.float32)

    def run(x):
        return remat_block(blk, Tensor(x))._data

    kept = saved_residuals(run, x)
    made = [(aval, what) for aval, what in kept if not handed_in(what)]
    # ``out`` feeds the block's own projection too, so JAX rounds it to its
    # own precision on the way out (``reduce_precision``, the identity
    # here) and the description names that
    (out, out_what), (lse, lse_what) = sorted(made, key=lambda m: -m[0].ndim)
    assert out.shape == (BATCH, SEQ, HEADS, WIDTH // HEADS)
    assert "flash_out" in out_what or "reduce_precision" in out_what
    hpb = fa._head_layout(HEADS, WIDTH // HEADS)[0]
    assert (lse.shape, lse.dtype) == ((BATCH, HEADS // hpb, hpb, SEQ),
                                      jnp.float32)
    assert "flash_lse" in lse_what
    assert (BATCH, SEQ, WIDTH) in [aval.shape for aval, _what in kept]


def test_a_block_with_no_flash_call_keeps_its_arguments_only(
        one_device_mesh, monkeypatch, no_budget):
    from jax._src.ad_checkpoint import saved_residuals
    monkeypatch.setattr(functional, "_use_pallas", lambda *a, **k: False)
    blk = gpt().gpt.blocks[0]
    kept = saved_residuals(
        lambda x: remat_block(blk, Tensor(x))._data,
        jnp.ones((BATCH, SEQ, WIDTH), jnp.float32))
    assert kept and all(handed_in(what) for _aval, what in kept), kept


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "composite"])
def test_within_the_budget_a_block_keeps_three_projections_outputs_too(
        flash, one_device_mesh, monkeypatch):
    """qkv's, the out-projection's and fc1's: the backward reads them. fc2's
    output is named like them and read by nothing, so nothing saves it."""
    from jax._src.ad_checkpoint import saved_residuals
    monkeypatch.setattr(functional, "_use_pallas", lambda *a, **k: flash)
    blk = gpt().gpt.blocks[0]
    kept = saved_residuals(
        lambda x: remat_block(blk, Tensor(x))._data,
        jnp.ones((BATCH, SEQ, WIDTH), jnp.float32))
    made = [aval.shape for aval, what in kept if not handed_in(what)]
    # the flash pair is four-dimensional, a projection's output (B, S, width)
    assert sorted(shape for shape in made if len(shape) == 3) == [
        (BATCH, SEQ, WIDTH), (BATCH, SEQ, 3 * WIDTH), (BATCH, SEQ, 4 * WIDTH)]
    assert len(made) == (5 if flash else 3)


# ----------------------------------------------------------- the same numbers
def test_loss_and_gradients_are_the_bare_checkpoints_bit_for_bit(
        one_device_mesh, monkeypatch):
    loss_of, arrays, names = loss_and_arrays(gpt(), 128)
    kept_loss, kept_grads = jax.jit(jax.value_and_grad(loss_of))(arrays)
    keep_nothing(monkeypatch)
    bare_loss, bare_grads = jax.jit(jax.value_and_grad(loss_of))(arrays)
    assert np.array_equal(kept_loss, bare_loss)
    for name, a, b in zip(names, kept_grads, bare_grads):
        assert np.array_equal(a, b), name
    assert float(jnp.max(jnp.abs(kept_grads[0]))) > 0


def test_loss_and_gradients_are_those_without_recompute(one_device_mesh):
    loss_of, arrays, names = loss_and_arrays(gpt(), 128)
    kept_loss, kept_grads = jax.jit(jax.value_and_grad(loss_of))(arrays)
    plain_of, plain_arrays, _names = loss_and_arrays(gpt(recompute=False),
                                                     128)
    plain_loss, plain_grads = jax.jit(jax.value_and_grad(plain_of))(
        plain_arrays)
    assert abs(float(kept_loss) - float(plain_loss)) < 1e-6
    for name, a, b in zip(names, kept_grads, plain_grads):
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)


# ------------------------------------------- the projections, once a block
def products(jaxpr):
    """``dot_general`` equations of a jaxpr and all inside, the kernels'
    own (interpreted here) left out."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += eqn.primitive.name == "dot_general"
        found += sum(products(sub)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
    return found


def gradient(loss_of):
    """A function of its own each call: JAX keeps a traced function's jaxpr,
    and a second trace is the point."""
    return lambda arrays: jax.grad(loss_of)(arrays)


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "composite"])
def test_a_gradient_runs_three_projections_a_block_once(
        flash, one_device_mesh, monkeypatch):
    """With the name kept the gradient holds qkv's, the out-projection's and
    fc1's product once a block and not twice (fc2's never ran twice), in the
    jaxpr and in the compiled program, and the same numbers bit for bit."""
    monkeypatch.setattr(functional, "_use_pallas", lambda *a, **k: flash)
    loss_of, arrays, names = loss_and_arrays(gpt(), 128)
    kept_jaxpr = jax.make_jaxpr(gradient(loss_of))(arrays).jaxpr
    kept = jax.jit(gradient(loss_of)).lower(arrays).compile()
    flash_pair_alone(monkeypatch)
    pair_jaxpr = jax.make_jaxpr(gradient(loss_of))(arrays).jaxpr
    pair = jax.jit(gradient(loss_of)).lower(arrays).compile()
    assert products(pair_jaxpr) - products(kept_jaxpr) == 3 * LAYERS

    def dots(compiled):
        return len(re.findall(r" dot\(", compiled.as_text()))

    if not flash:       # the interpreted kernels' own products are loops'
        assert dots(pair) - dots(kept) >= 3 * LAYERS
    for name, a, b in zip(names, kept(arrays), pair(arrays)):
        assert np.array_equal(a, b), name


def linear_bytes_of_a_block(itemsize=4):
    """qkv, out-projection, fc1, fc2: (3 + 1 + 4 + 1) widths a row."""
    return 9 * BATCH * SEQ * WIDTH * itemsize


@pytest.fixture
def record():
    trace.startup_clear()
    yield lambda: trace.startup_record()["entries"]
    trace.startup_clear()


def note_of(record, program):
    (traced,) = [e for e in record() if e[0] == "compile.trace"
                 and e[5]["program"] == program]
    return traced[5].get("remat_kept")


def test_a_budget_of_one_block_keeps_block_0s_and_no_others(
        record, one_device_mesh, monkeypatch):
    """Blocks are taken in trace order; a block whose outputs no longer fit
    keeps the flash pair alone, as every block did."""
    monkeypatch.setattr(_remat, "_kept_budget",
                        lambda: linear_bytes_of_a_block() * 3 // 2)
    policies = []
    made = jax.checkpoint_policies.save_only_these_names
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *names: policies.append(names) or made(*names))
    loss_of, arrays, _names = loss_and_arrays(gpt(), 128)

    def budget_step(arrays):
        return jax.grad(loss_of)(arrays)

    jaxpr = jax.make_jaxpr(jax.jit(budget_step))(arrays).jaxpr
    pair = residuals.KEPT_RESIDUALS
    assert policies == [pair + (residuals.LINEAR_OUT,), pair, pair]
    head = WIDTH // HEADS
    assert note_of(record, "budget_step") == {
        "names": list(pair) + [residuals.LINEAR_OUT],
        "arrays": {"flash_out": LAYERS, "flash_lse": LAYERS,
                   residuals.LINEAR_OUT: 4},
        "bytes": {"flash_out": LAYERS * BATCH * SEQ * HEADS * head * 4,
                  "flash_lse": LAYERS * BATCH * HEADS * SEQ * 4,
                  residuals.LINEAR_OUT: linear_bytes_of_a_block()},
        "blocks": LAYERS, "blocks_keeping": 1, "calls": LAYERS}
    flash_pair_alone(monkeypatch)
    none = jax.make_jaxpr(gradient(loss_of))(arrays).jaxpr
    assert products(none) - products(jaxpr) == 3


def test_on_a_dp_mesh_the_budget_counts_a_devices_rows(record, dp_mesh,
                                                       monkeypatch):
    """A projection is traced at the global batch and the mesh's data axes
    cut it: four devices hold a quarter of its output each."""
    monkeypatch.setattr(_remat, "_kept_budget",
                        lambda: linear_bytes_of_a_block() // 4)
    loss_of, arrays, _names = loss_and_arrays(gpt(), 128)

    def dp_step(arrays):
        return jax.grad(loss_of)(arrays)

    jax.jit(dp_step).lower(arrays)
    note = note_of(record, "dp_step")
    assert (note["blocks"], note["blocks_keeping"]) == (LAYERS, 1)
    assert note["bytes"][residuals.LINEAR_OUT] == \
        linear_bytes_of_a_block() // 4


def test_the_budget_reads_nothing_that_moves_between_two_traces(
        record, one_device_mesh, monkeypatch):
    """The budget is a share of what the device states as its capacity; what
    is in use while the step is traced (other programs' arrays, a reference
    not yet freed) does not reach it: the same step traced a second time
    beside large live arrays keeps the same list."""
    asked = []

    def stats():
        asked.append(sum(a.nbytes for a in jax.live_arrays()))
        return {"bytes_limit": int(2.5 * linear_bytes_of_a_block()
                                   / _remat.KEPT_SHARE),
                "bytes_in_use": asked[-1], "peak_bytes_in_use": max(asked)}

    monkeypatch.setattr(_remat, "_device_memory", stats)
    loss_of, arrays, _names = loss_and_arrays(gpt(), 128)

    def first_trace(arrays):
        return jax.grad(loss_of)(arrays)

    def second_trace(arrays):
        return jax.grad(loss_of)(arrays)

    jax.jit(first_trace).lower(arrays)
    quiet = max(asked)
    ballast = [jnp.ones((1 << 22,), jnp.float32) + i for i in range(4)]
    jax.block_until_ready(ballast)
    jax.jit(second_trace).lower(arrays)
    # 64 MB more, less whatever an earlier test's garbage gave back
    assert max(asked) > quiet + (1 << 25)
    first, second = note_of(record, "first_trace"), \
        note_of(record, "second_trace")
    assert first == second
    assert (first["blocks"], first["blocks_keeping"]) == (LAYERS, 2)
    del ballast


# ------------------------------------------------- inert where nothing remats
def without_names(monkeypatch):
    monkeypatch.setattr(residuals, "checkpoint_name", lambda x, _name: x)


def forward_only():
    q = jnp.ones((2, SEQ, HEADS, 32), jnp.float32)
    return jax.jit(lambda q: fa.flash_attention_fwd(q, q, q, causal=True)), q


def differentiated_without_remat():
    loss_of, arrays, _names = loss_and_arrays(gpt(recompute=False), 128)
    return jax.jit(jax.grad(loss_of)), arrays


def differentiated_kernel():
    q = jnp.ones((2, SEQ, HEADS, 32), jnp.float32)
    return jax.jit(jax.grad(lambda q: jnp.sum(
        fa.flash_attention_fwd(q, q * 2, q + 1, causal=True) ** 2))), q


def but_for_the_symbol_counter(text):
    """JAX emits every primitive's lowering as a function named after the
    primitive before it inlines it, and a repeated symbol gets the module's
    collision counter for a suffix: two ``name`` equations are one collision
    more, so ``@_pad_40`` is ``@_pad_41``. Nothing else may differ."""
    return re.sub(r"(@[A-Za-z_][\w.]*?)_\d+\b", r"\1", text)


@pytest.mark.parametrize("program, exact", [
    (forward_only, True), (differentiated_kernel, False),
    (differentiated_without_remat, False)], ids=lambda f: getattr(
        f, "__name__", ""))
def test_a_program_with_no_remat_lowers_to_the_same_text(
        program, exact, one_device_mesh, monkeypatch):
    """A name is the identity and lowers to nothing. A program that does not
    differentiate never reaches a name and is the same byte for byte; one
    that does holds the same operations in the same order."""
    fn, arg = program()
    named = fn.lower(arg).as_text()
    without_names(monkeypatch)
    fn, arg = program()
    plain = fn.lower(arg).as_text()
    if exact:
        assert plain == named
    assert but_for_the_symbol_counter(plain) == \
        but_for_the_symbol_counter(named)


def test_a_forward_only_program_holds_no_trace_of_the_residuals(
        one_device_mesh):
    """Not differentiated, the op is the kernel and the output's layout
    change: the logsumexp is never reshaped, nothing is named."""
    fn, q = forward_only()
    jaxpr = jax.make_jaxpr(fn)(q)
    assert "name[" not in str(jaxpr) and "squeeze" not in str(jaxpr)
    assert kernel_calls(jaxpr.jaxpr) == {"flash_fwd": 1, "flash_dq": 0,
                                         "flash_dkv": 0}


def names_in(jaxpr):
    """The names of a jaxpr's ``name`` equations and of all inside."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += names_in(sub)
    return found


def a_bare_product():
    x, w = jnp.ones((4, 8), jnp.float32), jnp.ones((8, 8), jnp.float32)
    return (lambda x, w: paddle.nn.functional.linear(
        Tensor(x), Tensor(w), Tensor(w[0]))._data), (x, w)


def a_trained_model(make, vocab):
    def program():
        loss_of, arrays, _names = loss_and_arrays(make(), vocab)
        return jax.grad(loss_of), (arrays,)
    return program


def llama_without_remat():
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(vocab_size=128, hidden_size=64,
                                       intermediate_size=128, num_heads=2,
                                       max_seq_len=SEQ))


def smallthinker_without_remat():
    from paddle_tpu.models import SmallThinkerForCausalLM, smallthinker_tiny
    paddle.seed(0)
    return SmallThinkerForCausalLM(smallthinker_tiny(
        num_hidden_layers=2, head_dim=32, num_attention_heads=2,
        num_key_value_heads=1))


def a_serving_prefill_chunk():
    from test_trace_boundary import tiny_replica
    eng = tiny_replica()
    rows = (np.zeros((1, eng.prefill_width), np.int32),
            np.ones((1,), np.int32), np.zeros((1, 8), np.int32),
            np.zeros((1,), np.float32), np.ones((1,), np.float32),
            np.zeros((1,), np.int32), np.zeros((1,), np.int32))
    args = eng._chunk_args(*rows) + (jnp.zeros((1,), jnp.int32),)
    return (lambda *a: eng._fns["prefill"](*a, sampling=False)), args


NOT_REMATERIALISED = {
    "F.linear": a_bare_product,
    "gpt": a_trained_model(lambda: gpt(recompute=False), 128),
    "llama": a_trained_model(llama_without_remat, 128),
    "lfm2_moe": a_trained_model(lambda: Lfm2MoeForCausalLM(
        lfm2_moe_tiny(recompute=False)), 256),
    "smallthinker": a_trained_model(smallthinker_without_remat, 256),
    "serving_prefill_chunk": a_serving_prefill_chunk,
}


@pytest.mark.parametrize("which", sorted(NOT_REMATERIALISED))
def test_outside_a_rematerialised_block_no_product_is_named(
        which, one_device_mesh):
    """``F.linear`` names its result only while a ``kept_residuals()`` block
    is open, and ``remat_block`` alone opens one: a model run without it,
    differentiated or not, and a serving adapter's ``forward_chunk`` hold no
    such equation (a differentiated flash call names its pair wherever it
    runs, as it did), so each lowers to what it lowered to."""
    fn, args = NOT_REMATERIALISED[which]()
    found = names_in(jax.make_jaxpr(fn)(*args).jaxpr)
    assert residuals.LINEAR_OUT not in found
    if which in ("F.linear", "serving_prefill_chunk"):
        assert not found


def test_inside_one_every_product_is(one_device_mesh):
    loss_of, arrays, _names = loss_and_arrays(gpt(), 128)
    found = names_in(jax.make_jaxpr(jax.grad(loss_of))(arrays).jaxpr)
    assert found.count(residuals.LINEAR_OUT) >= 4 * LAYERS


def test_the_serving_prefill_chunk_lowers_to_the_same_text(monkeypatch):
    """A serving program differentiates nothing and rematerialises nothing:
    the tiny Llama's paged prefill chunk, as the suite lowers it."""
    from test_trace_boundary import tiny_replica

    def lowered():
        eng = tiny_replica()
        rows = (np.zeros((1, eng.prefill_width), np.int32),
                np.ones((1,), np.int32), np.zeros((1, 8), np.int32),
                np.zeros((1,), np.float32), np.ones((1,), np.float32),
                np.zeros((1,), np.int32), np.zeros((1,), np.int32))
        args = eng._chunk_args(*rows) + (jnp.zeros((1,), jnp.int32),)
        return eng._fns["prefill"].lower(*args, sampling=False).as_text()

    named = lowered()
    without_names(monkeypatch)
    assert lowered() == named


# ---------------------------------------------------------------- the note
def test_tracing_a_rematerialised_step_leaves_one_note(record,
                                                       one_device_mesh):
    loss_of, arrays, _names = loss_and_arrays(gpt(), 128)

    def remat_step(arrays):
        return jax.grad(loss_of)(arrays)

    jax.jit(remat_step).lower(arrays)
    head = WIDTH // HEADS
    assert note_of(record, "remat_step") == {
        "names": list(residuals.KEPT_RESIDUALS) + [residuals.LINEAR_OUT],
        "arrays": {"flash_out": LAYERS, "flash_lse": LAYERS,
                   residuals.LINEAR_OUT: 4 * LAYERS},
        "bytes": {"flash_out": LAYERS * BATCH * SEQ * HEADS * head * 4,
                  "flash_lse": LAYERS * BATCH * HEADS * SEQ * 4,
                  residuals.LINEAR_OUT: LAYERS * linear_bytes_of_a_block()},
        "blocks": LAYERS, "blocks_keeping": LAYERS, "calls": LAYERS}


def test_a_program_with_no_remat_leaves_none(record, one_device_mesh):
    loss_of, arrays, _names = loss_and_arrays(gpt(recompute=False), 128)

    def plain_step(arrays):
        return jax.grad(loss_of)(arrays)

    def remat_forward(arrays):          # rematerialised, not differentiated
        return loss_and_arrays(gpt(), 128)[0](arrays)

    jax.jit(plain_step).lower(arrays)
    jax.jit(remat_forward).lower(arrays)
    traced = [e for e in record() if e[0] == "compile.trace"
              and e[5]["program"] in ("plain_step", "remat_forward")]
    assert len(traced) == 2
    assert not [e for e in traced if "remat_kept" in e[5]]
    assert all(any(k.startswith("flash_fwd[") for k in e[5])
               for e in traced)


def test_the_note_is_logged_once_a_block_signature(one_device_mesh, caplog,
                                                   monkeypatch):
    from paddle_tpu.core import flags
    from paddle_tpu.models import _remat
    monkeypatch.setattr(_remat, "_logged", set())
    old = flags.get_flag("log_level")
    flags.set_flags({"log_level": 1})
    # the paddle_tpu parent logger does not propagate to root (rank-aware
    # handler), so capture on the logger itself
    logger = logging.getLogger("paddle_tpu.remat")
    logger.addHandler(caplog.handler)
    try:
        loss_of, arrays, _names = loss_and_arrays(gpt(), 128)
        with caplog.at_level(logging.INFO, "paddle_tpu.remat"):
            jax.jit(jax.grad(loss_of)).lower(arrays)
            jax.jit(jax.grad(loss_of)).lower(arrays)
    finally:
        logger.removeHandler(caplog.handler)
        flags.set_flags({"log_level": old})
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "flash_out" in said[0] \
        and "flash_lse" in said[0]
