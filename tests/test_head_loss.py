"""A model's head and loss chunk by chunk (``F.fused_linear_cross_entropy``,
``models/_head.py``): the op against ``cross_entropy(matmul(x, w))`` in value
and in every gradient; what a differentiated call lowers to (three products
with the vocabulary, nothing of (rows, vocabulary) outside the scan); what a
``dp`` mesh does to it (a device chunks its own rows, the table's gradient is
reduced once); the note on the start-up record; the cost model's bill."""
from __future__ import annotations

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.auto_parallel import Engine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import loss as loss_mod
from paddle_tpu.observability import trace

HIDDEN, VOCAB, CHUNK = 16, 37, 16


@pytest.fixture
def no_mesh(monkeypatch):
    """No mesh set: the call is one device's (files share a worker, and a
    mesh an earlier file set would partition the rows)."""
    monkeypatch.setattr(mesh_mod, "_global_mesh", None)


def mesh_of(shape, monkeypatch):
    size = int(np.prod(list(shape.values())))
    mesh = mesh_mod.build_mesh(shape, devices=jax.devices()[:size])
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh)
    return mesh


def plain(x, w, y, b=None, reduction="mean", transpose_y=False):
    """``cross_entropy(matmul(x, w) + b, y)``, written out."""
    logits = x @ (w.T if transpose_y else w)
    if b is not None:
        logits = logits + b
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    valid = y != -100
    nll = -jnp.take_along_axis(logp, jnp.where(valid, y, 0)[..., None],
                               -1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    if reduction == "none":
        return nll
    if reduction == "sum":
        return nll.sum()
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def fused(x, w, y, b=None, **kw):
    return F.fused_linear_cross_entropy(
        Tensor(x), Tensor(w), Tensor(y),
        bias=None if b is None else Tensor(b), **kw)._data


def operands(lead, dtype=jnp.float32, transpose_y=False, bias=False,
             vocab=VOCAB, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*lead, HIDDEN), dtype)
    w = jnp.asarray(0.3 * rng.randn(*((vocab, HIDDEN) if transpose_y
                                      else (HIDDEN, vocab))), dtype)
    b = jnp.asarray(rng.randn(vocab), dtype) if bias else None
    y = rng.randint(0, vocab, lead)
    y.flat[3] = y.flat[-1] = -100           # rows that count for nothing
    return x, w, b, jnp.asarray(y)


def both(lead, reduction, dtype=jnp.float32, transpose_y=False, bias=False,
         chunk_rows=CHUNK, seed=0):
    """(value, gradients) of the op and of the unfused path under a cotangent
    that is not 1 (``jax.grad`` of a scaled loss, a weight a row for
    "none")."""
    x, w, b, y = operands(lead, dtype, transpose_y, bias, seed=seed)
    scale = jnp.asarray(np.random.RandomState(seed + 1).randn(
        *(lead if reduction == "none" else ())), jnp.float32) + 1.7
    argnums = (0, 1, 2) if bias else (0, 1)

    def of(op, **kw):
        def scaled(x, w, b):
            return jnp.sum(op(x, w, y, b, reduction=reduction,
                              transpose_y=transpose_y, **kw) * scale)
        return jax.jit(jax.value_and_grad(scaled, argnums=argnums))(x, w, b)

    return of(fused, chunk_rows=chunk_rows), of(plain)


# ------------------------------------------------ value and every gradient
@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("transpose_y", [False, True], ids=["hv", "vh"])
@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
def test_value_and_gradients_are_the_unfused_paths(no_mesh, reduction,
                                                   transpose_y, bias):
    # 50 rows: three chunks of 16 and two rows of a fourth
    (fv, fg), (pv, pg) = both((50,), reduction, transpose_y=transpose_y,
                              bias=bias)
    np.testing.assert_allclose(fv, pv, rtol=1e-5)
    assert len(fg) == 2 + bias
    for got, want in zip(fg, pg):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("lead,chunk_rows", [
    pytest.param((48,), 16, id="whole-chunks"),
    pytest.param((50,), 16, id="a-ragged-last-chunk"),
    pytest.param((10,), 16, id="fewer-rows-than-a-chunk"),
    pytest.param((4, 12), 16, id="batch-by-positions"),
    pytest.param((2, 3, 7), 8, id="three-leading-dims")])
@pytest.mark.parametrize("reduction", ["mean", "none"])
def test_rows_of_any_leading_shape_chunk(no_mesh, reduction, lead,
                                         chunk_rows):
    (fv, fg), (pv, pg) = both(lead, reduction, transpose_y=True,
                              chunk_rows=chunk_rows)
    np.testing.assert_allclose(fv, pv, rtol=1e-5)
    for got, want in zip(fg, pg):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    x, w, _b, y = operands(lead, transpose_y=True)
    rows = fused(x, w, y, reduction="none", transpose_y=True,
                 chunk_rows=chunk_rows)
    assert rows.shape == lead and rows.dtype == jnp.float32
    assert float(rows.reshape(-1)[3]) == 0.0        # an ignored row


@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
def test_bfloat16_operands_keep_float32_statistics(no_mesh, reduction):
    """bf16 rows and table: the logits are the bf16 matmul's, the statistics
    float32, so value and gradients stand as near the float32 answer as the
    unfused path's own bf16 run does (twice its distance is the room)."""
    (fv, fg), (pv, pg) = both((50,), reduction, jnp.bfloat16,
                              transpose_y=True)
    _, (ev, eg) = both((50,), reduction, jnp.float32, transpose_y=True)
    assert fv.dtype == jnp.float32
    np.testing.assert_allclose(fv, pv, rtol=2e-2)
    for got, unfused, exact in zip(fg, pg, eg):
        assert got.dtype == jnp.bfloat16
        far = np.abs(np.asarray(unfused, np.float32) - exact).max()
        near = np.abs(np.asarray(got, np.float32) - exact).max()
        assert near <= 2 * far + 1e-3, (near, far)


def test_the_table_gradient_accumulates_in_float32(no_mesh):
    """512 bf16 rows in chunks of 16, every row the same: the table's
    gradient is 32 equal chunk sums. Summed in bf16 the total would stall
    (a bf16 has eight bits); summed in float32 and rounded once it is the
    one-chunk call's, rounded."""
    x, w, _b, _y = operands((8,), jnp.bfloat16, transpose_y=True)
    x, y = jnp.tile(x[:1], (512, 1)), jnp.full((512,), 5)

    def grad_w(chunk_rows):
        return jax.grad(lambda w: fused(x, w, y, reduction="sum",
                                        transpose_y=True,
                                        chunk_rows=chunk_rows))(w)

    np.testing.assert_array_equal(grad_w(16), grad_w(512))


def test_all_rows_ignored_is_zero_with_zero_gradients(no_mesh):
    x, w, _b, y = operands((20,), transpose_y=True)
    value, (gx, gw) = jax.value_and_grad(
        lambda x, w: fused(x, w, y * 0 - 100, transpose_y=True,
                           chunk_rows=8), argnums=(0, 1))(x, w)
    assert float(value) == 0.0
    assert not np.any(np.asarray(gx)) and not np.any(np.asarray(gw))


def test_the_eager_tape_takes_the_rule(no_mesh):
    x, w, _b, y = operands((50,), transpose_y=True)
    xt, wt = paddle.to_tensor(np.asarray(x)), paddle.to_tensor(np.asarray(w))
    xt.stop_gradient = wt.stop_gradient = False
    loss = F.fused_linear_cross_entropy(
        xt, wt, paddle.to_tensor(np.asarray(y)), transpose_y=True,
        chunk_rows=CHUNK)
    (loss * 3.0).backward()
    _, (gx, gw) = jax.value_and_grad(
        lambda x, w: 3.0 * plain(x, w, y, transpose_y=True),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(xt.grad.numpy(), gx, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(wt.grad.numpy(), gw, rtol=1e-4, atol=1e-6)


# ------------------------------------------------ what a call lowers to
def eqns_of(jaxpr, _in_loop=False):
    """Every equation of a jaxpr and all inside it, with whether it sits in
    the body of a ``scan`` or ``while``."""
    for eqn in jaxpr.eqns:
        yield eqn, _in_loop
        loop = _in_loop or eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_of(sub, loop)


def vocabulary_products(jaxpr, vocab):
    return [eqn for eqn, _ in eqns_of(jaxpr)
            if eqn.primitive.name == "dot_general"
            and any(vocab in v.aval.shape for v in eqn.invars + eqn.outvars)]


def tiny_gpt(vocab=131, recompute=False):
    paddle.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=16, use_flash_attention=False, recompute=recompute))


def step_of(model):
    """``(loss_and_grad(arrays, ids), arrays)`` over the model's leaves."""
    params = [p for p in model.parameters() if not p.stop_gradient]

    def loss_of(arrays, ids):
        olds = [p._data for p in params]
        for p, a in zip(params, arrays):
            p._data = a
        try:
            return model(Tensor(ids), labels=Tensor(ids))[1]._data
        finally:
            for p, o in zip(params, olds):
                p._data = o

    return jax.value_and_grad(loss_of), [p._data for p in params]


def test_a_gpt_step_multiplies_by_the_vocabulary_three_times(no_mesh):
    """A jitted ``value_and_grad`` of ``GPTForCausalLM`` with labels: exactly
    three ``dot_general``s see the vocabulary (logits, the rows' gradient,
    the table's), all three inside the scan, and no float32 value as large
    as (rows, vocabulary) exists outside it."""
    vocab, batch, seq = 131, 4, 16
    step, arrays = step_of(tiny_gpt(vocab))
    ids = jnp.zeros((batch, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.jit(step))(arrays, ids).jaxpr
    products = vocabulary_products(jaxpr, vocab)
    assert len(products) == 3
    in_loop = {id(eqn) for eqn, loop in eqns_of(jaxpr) if loop}
    assert all(id(eqn) in in_loop for eqn in products)
    wide = [v.aval for eqn, loop in eqns_of(jaxpr) if not loop
            for v in eqn.outvars
            if getattr(v.aval, "dtype", None) == jnp.float32
            and vocab in v.aval.shape
            and v.aval.size >= batch * (seq - 1) * vocab]
    assert not wide, wide


def test_a_call_that_is_not_differentiated_multiplies_once(no_mesh):
    x, w, _b, y = operands((50,), transpose_y=True)
    jaxpr = jax.make_jaxpr(lambda x, w: fused(
        x, w, y, transpose_y=True, chunk_rows=CHUNK))(x, w).jaxpr
    assert len(vocabulary_products(jaxpr, VOCAB)) == 1


def test_per_row_losses_rematerialise_their_chunk(no_mesh):
    """``reduction="none"`` keeps the checkpointed chunk: four products."""
    x, w, _b, y = operands((50,), transpose_y=True)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, w: jnp.sum(fused(
        x, w, y, reduction="none", transpose_y=True,
        chunk_rows=CHUNK)), argnums=(0, 1)))(x, w).jaxpr
    assert len(vocabulary_products(jaxpr, VOCAB)) == 4


# --------------------------------------- each model against the plain path
def bert():
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    return BertForPretraining(BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, recompute=True))


def llama(tied):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, max_seq_len=16, use_flash_attention=False,
        recompute=True, tie_embeddings=tied))


def test_forward_without_labels_still_returns_logits(no_mesh):
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 128, (2, 16)))
    assert tiny_gpt(128)(ids).shape == [2, 16, 128]
    assert llama(True)(ids).shape == [2, 16, 128]
    assert llama(False)(ids).shape == [2, 16, 128]
    mlm, nsp = bert()(ids)
    assert mlm.shape == [2, 16, 128] and nsp.shape == [2, 2]
    none, nsp, loss = bert()(ids, masked_lm_labels=ids)
    assert none is None and nsp.shape == [2, 2] and loss.shape == []


# ------------------------------------------------------------- on a mesh
@pytest.mark.parametrize("shape", [{"dp": 4}, {"dp": 2, "sharding": 2},
                                   {"dp": 2, "mp": 2}],
                         ids=["dp4", "dp2-sharding2", "dp2-mp2"])
@pytest.mark.parametrize("reduction", ["mean", "none"])
def test_on_a_mesh_the_numbers_are_one_devices(monkeypatch, reduction,
                                               shape):
    """Rows cut over the data axes, the table whole or cut over ``mp`` on
    its vocabulary: value and gradients are the unfused path's."""
    mesh = mesh_of(shape, monkeypatch)
    (fv, fg), (pv, pg) = both((8, 12), reduction, transpose_y=True)
    np.testing.assert_allclose(fv, pv, rtol=1e-5)
    for got, want in zip(fg, pg):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    x, w, _b, y = operands((8, 12), transpose_y=True, vocab=40)
    data = tuple(a for a in ("dp", "sharding") if a in shape)
    xs = jax.device_put(x, NamedSharding(mesh, P(data)))
    ws = jax.device_put(w, NamedSharding(
        mesh, P("mp") if "mp" in shape else P()))
    value, grads = jax.jit(jax.value_and_grad(
        lambda x, w: jnp.sum(fused(x, w, y, reduction=reduction,
                                   transpose_y=True, chunk_rows=CHUNK)),
        argnums=(0, 1)))(xs, ws)
    want_v, want_g = jax.value_and_grad(
        lambda x, w: jnp.sum(plain(x, w, y, reduction=reduction,
                                   transpose_y=True)), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(value, want_v, rtol=1e-5)
    for got, want in zip(grads, want_g):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_a_device_chunks_its_own_rows(monkeypatch):
    """32 x 16 positions over ``dp`` 4: the scan inside the ``shard_map``
    walks a device's 128 rows, 2 chunks of 64, not the 512 of the batch."""
    mesh_of({"dp": 4}, monkeypatch)
    x, w, _b, y = operands((32, 16), transpose_y=True)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, w: fused(
        x, w, y, transpose_y=True, chunk_rows=64), argnums=(0, 1)))(x, w)
    assert "shard_map" in str(jaxpr)
    (scan,) = [eqn for eqn, _ in eqns_of(jaxpr.jaxpr)
               if eqn.primitive.name == "scan"]
    assert scan.params["length"] == 2


class UnfusedLoss(nn.Layer):
    """The parent's labels branch over the same ``GPTModel``."""

    def __init__(self, lm):
        super().__init__()
        self.lm = lm

    def forward(self, ids):
        h = self.lm.gpt(ids)
        logits = paddle.ops.matmul(h, self.lm.gpt.wte.weight,
                                   transpose_y=True)
        v = logits.shape[-1]
        return F.cross_entropy(
            paddle.ops.reshape(logits[:, :-1, :], [-1, v]),
            paddle.ops.reshape(ids[:, 1:], [-1]))


class FusedLoss(nn.Layer):
    def __init__(self, lm):
        super().__init__()
        self.lm = lm

    def forward(self, ids):
        return self.lm(ids, labels=ids)[1]


def compiled_dp_step(wrap, mesh, batch=2048, seq=16):
    net = wrap(tiny_gpt())
    engine = Engine(net, loss=lambda loss, _y: loss,
                    optimizer=paddle.optimizer.AdamW(
                        learning_rate=1e-3, parameters=net.parameters()))
    engine.prepare()
    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))

    def s(a, sharding=whole):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    arrays = [p._data for p in engine._params]
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rows)
    return engine._train_step.lower(
        [s(a) for a in arrays],
        jax.tree_util.tree_map(s, engine._init_opt_state(arrays)),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=whole),
        ids, ids).compile().as_text()


def collectives(text, kind):
    return re.findall(rf" {kind}(?:-start)?\(", text)


def loop_bodies(text):
    """The text of every ``while`` instruction's body computation."""
    names = re.findall(r" while\(.*?body=%([\w.-]+)", text)
    return [re.search(rf"(?ms)^%{re.escape(name)} \(.*?^}}", text).group(0)
            for name in names]


def test_the_dp_step_gathers_nothing_and_reduces_as_often(monkeypatch):
    """``Engine``'s step with the batch cut over ``dp`` 4, compiled for the
    suite's host devices: no all-gather and no all-to-all of hidden rows or
    logits (nothing is gathered at all), and as many all-reduces as the
    unfused step holds: the table's gradient is reduced once, after the
    scan, never once a chunk."""
    mesh = mesh_of({"dp": 4}, monkeypatch)
    text = compiled_dp_step(FusedLoss, mesh)
    assert not collectives(text, "all-gather")
    assert not collectives(text, "all-to-all")
    assert not collectives(text, "collective-permute")
    unfused = compiled_dp_step(UnfusedLoss, mesh)
    assert 0 < len(collectives(text, "all-reduce")) \
        <= len(collectives(unfused, "all-reduce"))
    scans = [body for body in loop_bodies(text) if " dot(" in body]
    assert scans and not any("all-reduce" in body for body in scans)


# ------------------------------------------------------------- the note
@pytest.fixture
def record():
    trace.startup_clear()
    yield lambda: trace.startup_record()["entries"]
    trace.startup_clear()


def test_tracing_a_step_stamps_the_plan(record, no_mesh):
    vocab, batch, seq = 131, 4, 16
    step, arrays = step_of(tiny_gpt(vocab))

    def head_step(arrays, ids):
        return step(arrays, ids)

    jax.jit(head_step).lower(arrays, jnp.zeros((batch, seq), jnp.int32))
    (traced,) = [e for e in record() if e[0] == "compile.trace"
                 and e[5]["program"] == "head_step"]
    assert traced[5]["head_loss_plan"] == {
        "rows": batch * seq, "chunk": batch * seq, "chunks": 1,
        "vocab": vocab, "hidden": 32, "products": 3, "dtype": "float32",
        "calls": 1}


def test_the_plan_counts_a_devices_rows(record, monkeypatch):
    mesh_of({"dp": 4}, monkeypatch)
    x, w, _b, y = operands((32, 16), transpose_y=True)

    def head_only(x, w):
        return fused(x, w, y, reduction="none", transpose_y=True,
                     chunk_rows=48)

    jax.jit(head_only).lower(x, w)
    (traced,) = [e for e in record() if e[0] == "compile.trace"
                 and e[5]["program"] == "head_only"]
    assert traced[5]["head_loss_plan"] == {
        "rows": 128, "chunk": 48, "chunks": 3, "vocab": VOCAB,
        "hidden": HIDDEN, "products": 4, "dtype": "float32", "calls": 1}


def test_the_plan_is_logged_once_a_signature(no_mesh, caplog, monkeypatch):
    from paddle_tpu.core import flags
    monkeypatch.setattr(loss_mod, "_head_loss_logged", set())
    before = flags.get_flag("log_level")
    flags.set_flags({"log_level": 1})
    # the paddle_tpu parent logger does not propagate to root (rank-aware
    # handler), so capture on the logger itself
    logger = logging.getLogger("paddle_tpu.head_loss")
    logger.addHandler(caplog.handler)
    try:
        x, w, _b, y = operands((50,), transpose_y=True)
        with caplog.at_level(logging.INFO, logger="paddle_tpu.head_loss"):
            for _ in range(2):
                fused(x, w, y, transpose_y=True, chunk_rows=CHUNK)
    finally:
        flags.set_flags({"log_level": before})
        logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "'products': 3" in lines[0]
    assert "'chunks': 4" in lines[0]


# --------------------------------------------------------- the cost model
@pytest.mark.parametrize("transpose_y", [False, True], ids=["hv", "vh"])
def test_the_cost_model_bills_three_products(transpose_y):
    from paddle_tpu.observability.perf import costmodel
    rows, hidden, vocab = 8 * 1024, 1024, 50257
    table = (vocab, hidden) if transpose_y else (hidden, vocab)
    cost = costmodel.COST_MODELS["fused_linear_cross_entropy"](
        [(8, 1024, hidden), table, (8, 1024)],
        ["bfloat16", "bfloat16", "int32"], {}, [()])
    products = 3 * 2.0 * rows * hidden * vocab
    assert products <= cost.flops <= 1.01 * products
    # the logits never leave the op: its traffic is rows, table and labels
    assert cost.bytes_read < 2 * (rows * hidden + hidden * vocab) * 2
