"""What a rematerialised block keeps (``models/_remat.py``): its input and,
for each flash-attention call inside it, the forward kernel's two residuals
under the names of ``flash_attention.KEPT_RESIDUALS``, so that the backward
runs every forward kernel once and not twice. On the CPU with the kernels
interpreted: the kernel calls of a gradient counted in its jaxpr, the saved
residuals of one block, the gradients against the bare ``jax.checkpoint``
bit for bit, the programs that hold no remat byte for byte, and the note on
the start-up record."""
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
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.models import GPTConfig, GPTForCausalLM, Lfm2MoeForCausalLM
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
        one_device_mesh):
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
        one_device_mesh, monkeypatch):
    from jax._src.ad_checkpoint import saved_residuals
    monkeypatch.setattr(functional, "_use_pallas", lambda *a, **k: False)
    blk = gpt().gpt.blocks[0]
    kept = saved_residuals(
        lambda x: remat_block(blk, Tensor(x))._data,
        jnp.ones((BATCH, SEQ, WIDTH), jnp.float32))
    assert kept and all(handed_in(what) for _aval, what in kept), kept


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


# ------------------------------------------------- inert where nothing remats
def without_names(monkeypatch):
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, _name: x)


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
@pytest.fixture
def record():
    trace.startup_clear()
    yield lambda: trace.startup_record()["entries"]
    trace.startup_clear()


def test_tracing_a_rematerialised_step_leaves_one_note(record,
                                                       one_device_mesh):
    loss_of, arrays, _names = loss_and_arrays(gpt(), 128)

    def remat_step(arrays):
        return jax.grad(loss_of)(arrays)

    jax.jit(remat_step).lower(arrays)
    (traced,) = [e for e in record() if e[0] == "compile.trace"
                 and e[5]["program"] == "remat_step"]
    head = WIDTH // HEADS
    assert traced[5]["remat_kept"] == {
        "names": list(fa.KEPT_RESIDUALS),
        "arrays": {"flash_out": LAYERS, "flash_lse": LAYERS},
        "bytes": {"flash_out": LAYERS * BATCH * SEQ * HEADS * head * 4,
                  "flash_lse": LAYERS * BATCH * HEADS * SEQ * 4},
        "calls": LAYERS}


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
