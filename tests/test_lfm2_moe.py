"""LFM2-MoE (gated short convolutions beside grouped-query attention, routed
SwiGLU experts with no shared expert) on the TRAINING path, against its plain
reference ``benchmark/reference/lfm2_moe.py`` on seeded weights at tiny widths
(``benchmark/tests/tiny_lfm2_moe.py``: hidden 64, 4 query heads over 2 KV
heads of 16, three taps, six layers ``conv conv | full conv conv conv`` of
which two dense and four with 16 experts top-4, four held).

Tolerances, each with its reason:

* ``TIGHT`` 2e-5 absolute on values of order 1, float32 against float32 at
  ``highest`` precision: a few hundred additions taken in another order (a
  grouped product against a loop over experts, shifted slices against a
  token loop);
* ``GRAD`` 2e-4 relative to the largest entry of a gradient: the same
  reordering through a backward pass, where sums run over every token;
* the ``Engine.fit`` comparison runs the program as the cell does, under
  bfloat16 O1 autocast, so its limits are bfloat16's: ``LOSS`` 2e-4 relative
  (the loss is a mean over ~130 tokens of float32 logits from bfloat16
  products; seeds 1-6, 11 and 12 read 3e-6 to 2.6e-5), ``GRAD_NORM`` 0.03 on
  the worst leaf's norm (a top-4 choice that flips between bfloat16 and
  float32 activations moves a router's and an expert's gradient; the seeds
  read 0.0016-0.0066), ``DELTA_NORM`` 0.03 (AdamW's first steps are ``lr x
  sign`` wherever the gradient's sign agrees; the seeds read 0.0014-0.0046).
  A step that leaves its state unchanged reads 1.0 on the last, half a batch
  left out reads above 1e-3 on the first
  (``benchmark/tests/test_correct_lfm2_moe.py``).
"""
from __future__ import annotations

import copy
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from benchmark.drivers import fit_lfm2_moe as driver  # noqa: E402
from benchmark.drivers.fit import TokenStream  # noqa: E402
from benchmark.lib import check as check_lib  # noqa: E402
from benchmark.lib import flops_lfm2_moe  # noqa: E402
from benchmark.lib import weights_lfm2_moe as weights_lib  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from benchmark.tests.tiny_lfm2_moe import FIT, LFM2  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models import Lfm2MoeForCausalLM  # noqa: E402
from paddle_tpu.models._remat import remat_block  # noqa: E402
from paddle_tpu.nn.functional import experts as E  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.observability import trace as obs_trace  # noqa: E402

from served import close, rand, traced  # noqa: E402

TIGHT, GRAD = 2e-5, 2e-4
LOSS, GRAD_NORM, DELTA_NORM = 2e-4, 0.03, 0.03
CFG = LFM2


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def own_mesh(monkeypatch):
    """The driver's ``build`` sets the program's mesh to one device, as a
    run of the cell does; the suite's own (the CPU's eight) is put back for
    the files that share this worker."""
    from paddle_tpu.distributed import mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh_mod._global_mesh)


@pytest.fixture
def metrics_on():
    paddle.set_flags({"FLAGS_enable_metrics": True})
    yield
    paddle.set_flags({"FLAGS_enable_metrics": False})


def grads_close(got, want):
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        close(g / scale, w / scale, GRAD)


# ========================================================= the expert product
def skewed_routing(n, k, router_width, lo, held, seed):
    """Indices under which held expert ``lo + 1`` gets most pairs and
    ``lo + 3`` none; a tenth of the rows are padding."""
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, router_width, (n, k))
    idx = np.where(rng.rand(n, k) < 0.6, lo + 1, idx)
    idx[idx == lo + 3] = lo + 4
    assert held > 4
    return (jnp.asarray(idx, jnp.int32),
            jnp.asarray(rng.rand(n, k), jnp.float32),
            jnp.asarray(rng.rand(n) < 0.9))


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("padded", [False, True], ids=["whole", "padded"])
def test_grouped_product_is_the_masked_product(form, padded):
    """``grouped_experts_arrays`` against ``experts_arrays``, the
    definition: values and the gradients in ``x``, the routing weights and
    every matrix, under a routing that gives one held expert most pairs and
    one none. Equal sums mean no pair was dropped."""
    n, k, hidden, width, held, lo = 96, 4, 16, 24, 8, 8
    idx, w, valid = skewed_routing(n, k, 32, lo, held, 3)
    valid = valid if padded else None
    x = rand((n, hidden), 1)
    shapes = [(held, hidden, width)] * (2 if form == "swiglu" else 1) \
        + [(held, width, hidden)]
    mats = [rand(s, 10 + i, 0.3) for i, s in enumerate(shapes)]
    load = np.asarray(E.load_arrays(idx, lo, held, valid))
    assert load[1] > 0.5 * load[-2] and load[3] == 0      # the skew

    def masked(x, w, *m):
        return E.experts_arrays(x, E.combine_arrays(idx, w, lo, held, valid),
                                m)

    def grouped(x, w, *m):
        return E.grouped_experts_arrays(x, idx, w, m, lo, valid)

    close(jax.jit(grouped)(x, w, *mats), jax.jit(masked)(x, w, *mats),
          TIGHT * 10)
    args = tuple(range(2 + len(mats)))
    want = jax.jit(jax.grad(lambda *a: jnp.sum(masked(*a) ** 2),
                            argnums=args))(x, w, *mats)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(grouped(*a) ** 2),
                           argnums=args))(x, w, *mats)
    grads_close(got, want)


#: the strided walk's test shapes: 400 x 4 = 1600 pairs, 4 of 32 experts
#: held, so ``pair_stride`` is one 512-row tile and the buffer four strides
#: (the last one 448 rows of padding that are no pair's)
WALK = dict(n=400, k=4, hidden=16, width=24, held=4, lo=8, router=32)


def landing(landed, favourite=None, padded=False, seed=7):
    """Routing of the ``WALK`` shapes under which exactly ``landed`` pairs
    land on the held experts (``favourite``: the share of them that one
    held expert takes), the rest on absent ones; ``padded`` marks a tenth of
    the rows as padding, whose pairs land nowhere whatever they chose."""
    g = WALK
    rng = np.random.RandomState(seed)
    valid = np.ones(g["n"], bool)
    if padded:
        valid[rng.permutation(g["n"])[:g["n"] // 10]] = False
    idx = rng.randint(g["lo"] + g["held"], g["router"], (g["n"], g["k"]))
    idx[~valid] = g["lo"]              # padding rows choose a held expert
    free = np.flatnonzero(np.repeat(valid, g["k"]))
    assert landed <= len(free)
    here = rng.permutation(free)[:landed]
    chosen = g["lo"] + rng.randint(0, g["held"], landed)
    if favourite is not None:
        chosen[:int(favourite * landed)] = g["lo"] + 1
    idx.reshape(-1)[here] = chosen
    idx, valid = jnp.asarray(idx, jnp.int32), jnp.asarray(valid)
    load = np.asarray(E.load_arrays(idx, g["lo"], g["held"],
                                    valid if padded else None))
    assert load[-2] == landed
    return (idx, jnp.asarray(rng.rand(g["n"], g["k"]), jnp.float32),
            valid if padded else None)


def walk_operands(form):
    g = WALK
    shapes = [(g["held"], g["hidden"], g["width"])] \
        * (2 if form == "swiglu" else 1) \
        + [(g["held"], g["width"], g["hidden"])]
    return rand((g["n"], g["hidden"]), 1), \
        [rand(s, 30 + i, 0.3) for i, s in enumerate(shapes)]


def both_forms(idx, valid):
    """``(masked, grouped)``: each ``(x, w, *mats) -> (values, gradients in
    every operand of the sum of squares)`` as one program."""
    g = WALK

    def masked(x, w, *m):
        return E.experts_arrays(
            x, E.combine_arrays(idx, w, g["lo"], g["held"], valid), m)

    def grouped(x, w, *m):
        return E.grouped_experts_arrays(x, idx, w, m, g["lo"], valid,
                                        num_experts=g["router"])

    def with_gradients(fn):
        def run(*a):
            grads, out = jax.grad(
                lambda *a: (lambda y: (jnp.sum(y ** 2), y))(fn(*a)),
                argnums=tuple(range(len(a))), has_aux=True)(*a)
            return out, grads
        return jax.jit(run)

    return with_gradients(masked), with_gradients(grouped)


STRIDE = 512
WALKS = {"nothing-lands": (0, None, 0),
         "one-stride-exactly": (STRIDE, None, 1),
         "one-pair-more": (STRIDE + 1, None, 2),
         "one-expert-takes-nearly-all": (1200, 0.95, 3)}


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("padded", [False, True], ids=["whole", "padded"])
@pytest.mark.parametrize("case", [*WALKS, "every-pair-lands"])
def test_strided_walk_is_the_masked_product(case, padded, form):
    """The grouped form walks ``ceil(landed / stride)`` strides of its
    sorted buffer and multiplies every pair that landed, whatever the
    count: values and the gradients in ``x``, the routing weights and every
    matrix against ``experts_arrays``, the definition."""
    g = WALK
    assert E.pair_stride(g["n"], g["k"], g["held"], g["router"]) == STRIDE
    if case == "every-pair-lands":      # the whole buffer, the worst case
        rows = g["n"] - (g["n"] // 10 if padded else 0)
        landed, favourite, strides = rows * g["k"], None, -(
            -rows * g["k"] // STRIDE)
    else:
        landed, favourite, strides = WALKS[case]
    assert E.strides_walked(landed, STRIDE) == strides
    idx, w, valid = landing(landed, favourite, padded)
    x, mats = walk_operands(form)
    masked, grouped = both_forms(idx, valid)
    want, want_grads = masked(x, w, *mats)
    got, got_grads = grouped(x, w, *mats)
    close(got, want, TIGHT * 10)
    assert all(bool(jnp.all(jnp.isfinite(d))) for d in got_grads)
    grads_close(got_grads, want_grads)
    if not landed:
        assert not np.asarray(got).any()
        assert not any(np.asarray(d).any() for d in got_grads)


@pytest.mark.parametrize("landed", [
    pytest.param(700, id="last-stride-half-live"),
    pytest.param(0, id="no-stride-walked")])
def test_rows_past_the_last_group_never_reach_a_result(monkeypatch, landed):
    """On a TPU the grouped-matmul kernel does not write the rows that
    belong to no group, in the product or in its transpose (the CPU's
    ``ragged_dot`` zeroes them, which hid a gradient of 3e5 times its size
    on the chip), and the buffers the strides write into start as whatever
    the memory held. Stand-ins that fill both with NaN: the rows of the last
    stride past the landed pairs, and the buffer rows of strides never
    walked (two of four here, or all four), forward and backward. Values
    and gradients still equal the masked product's."""
    real = jax.lax.ragged_dot

    def kernel(lhs, rhs, sizes, **kw):
        rows = jnp.arange(lhs.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), real(lhs, rhs, sizes, **kw),
                         jnp.nan)

    monkeypatch.setattr(E.jax.lax, "ragged_dot", kernel)
    monkeypatch.setattr(E, "_unwritten",
                        lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    idx, w, valid = landing(landed, padded=True)
    x, mats = walk_operands("swiglu")
    masked, grouped = both_forms(idx, valid)
    want, want_grads = masked(x, w, *mats)
    got, got_grads = grouped(x, w, *mats)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, want, TIGHT * 10)
    assert all(bool(jnp.all(jnp.isfinite(d))) for d in got_grads)
    grads_close(got_grads, want_grads)


def test_the_walk_is_one_loop_a_direction_and_no_branch():
    """The lowered grouped call, forward + backward: one ``while`` a
    direction (a data-dependent trip count, ONE body), no ``case`` / ``if``
    over sizes, every grouped matmul inside a loop body, and the layer's two
    scopes on the operations of both bodies (the backward is a
    ``custom_vjp``'s, traced apart from the forward: it opens them
    itself)."""
    g = WALK
    idx, w, _valid = landing(700)
    x, mats = walk_operands("swiglu")

    def loss(x, w, *m):
        with jax.named_scope("moe"):
            return jnp.sum(E.grouped_experts_arrays(
                x, idx, w, m, g["lo"], num_experts=g["router"]) ** 2)

    step = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    text = jax.jit(step).lower(x, w, *mats).as_text(debug_info=True)
    assert text.count("stablehlo.while") == 2
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    names = re.findall(r'loc\("([^"]*ragged_dot_general[^"]*)"', text)
    assert names and all("/while/body/moe.grouped_matmul/" in n
                         for n in names)
    for direction in ("jvp(moe)", "transpose(jvp(moe))"):
        for scope in ("moe.group", "moe.grouped_matmul"):
            assert f"{direction}/while/body/{scope}/" in text, (
                direction, scope)
    # three products forward; backward the first two again, three
    # transposes in the rows and three in the matrices: none outside a loop
    jaxpr = str(jax.make_jaxpr(step)(x, w, *mats))
    assert jaxpr.count("ragged_dot_general[") == 3 + 2 + 3 + 3
    assert jaxpr.count("while[") == 2 and "cond[" not in jaxpr


@pytest.mark.parametrize("rows,grouped", [
    (64, False), (256, False), (512, False), (2047, False), (2048, True),
    (16384, True)])
def test_the_form_follows_from_the_rows(rows, grouped):
    """Decode lanes and prefill chunks keep the masked product, a training
    batch takes the grouped one: read from the traced program, which holds
    a ``ragged_dot`` or does not."""
    assert E.takes_grouped_form(rows) is grouped
    hidden, width, held = 8, 8, 2
    x = jax.ShapeDtypeStruct((rows, hidden), jnp.float32)
    idx = jax.ShapeDtypeStruct((rows, 2), jnp.int32)
    w = jax.ShapeDtypeStruct((rows, 2), jnp.float32)
    up = jax.ShapeDtypeStruct((held, hidden, width), jnp.float32)
    down = jax.ShapeDtypeStruct((held, width, hidden), jnp.float32)

    def call(x, idx, w, g, u, d):
        return F.held_experts_swiglu(Tensor(x), Tensor(idx), Tensor(w),
                                     Tensor(g), Tensor(u), Tensor(d))._data

    text = str(jax.make_jaxpr(call)(x, idx, w, up, up, down))
    assert ("ragged_dot" in text) is grouped


def test_router_epsilon_is_an_argument_and_defaults_as_before():
    """``norm_eps`` 1e-20 is what the three serving configurations
    compiled: the default gives their numbers bit for bit; LFM2's 1e-6 is
    its own."""
    u, gate = rand((12, 16), 1), rand((16, 8), 2)
    bias = jnp.zeros((8,), jnp.float32)
    _i, before = E.route_arrays(u, gate, bias, 4, 2.5, True)
    _i, same = E.route_arrays(u, gate, bias, 4, 2.5, True, 1e-20)
    _i, lfm2 = E.route_arrays(u, gate, bias, 4, 2.5, True, 1e-6)
    assert (np.asarray(before) == np.asarray(same)).all()
    s = np.asarray(lfm2).sum(axis=1) / 2.5
    assert (s < 1.0).all() and (s > 1.0 - 1e-5).all()
    idx, w = F.sigmoid_topk_route(Tensor(u), Tensor(gate), Tensor(bias), 4,
                                  scale=2.5, norm_eps=1e-6)
    assert (np.asarray(w._data) == np.asarray(lfm2)).all()


def test_no_shared_width_builds_no_shared_expert():
    layer = nn.SwiGLUMoE(16, 24, 0, 8, 2, experts_held=(2, 6), norm_eps=1e-6)
    names = {n for n, _p in layer.named_parameters()}
    assert names == {"gate_weight", "e_score_correction_bias", "w_gate",
                     "w_up", "w_down"}
    layer.shared = None                      # calling it would raise
    out, load = traced(layer, Tensor(rand((3, 5, 16), 4)), with_load=True)
    assert out.shape == [3, 5, 16] and load.shape == [6]
    with_shared = nn.SwiGLUMoE(16, 24, 24, 8, 2)
    assert "shared_gate.weight" in {
        n for n, _p in with_shared.named_parameters()}


# ============================================================ the short conv
@pytest.mark.parametrize("taps", [3, 2, 4])
def test_gated_short_conv_is_the_token_loop(taps):
    """``c[t] = sum_j w[:, j] * (B * z)[t - (K - 1) + j]`` with zeros left
    of the first token, then ``C * c``: forward against a loop over tokens,
    gradients against the reference's own form."""
    bsz, t, ch = 2, 9, 6
    bcz, w = rand((bsz, t, 3 * ch), 1), rand((ch, taps), 2)
    b, c, z = (np.asarray(a) for a in jnp.split(bcz, 3, axis=-1))
    g, wn = b * z, np.asarray(w)
    want = np.zeros((bsz, t, ch), np.float32)
    for ti in range(t):
        for j in range(taps):
            src = ti - (taps - 1) + j
            if src >= 0:
                want[:, ti] += wn[:, j] * g[:, src]
    want *= c
    got = F.gated_short_conv(Tensor(bcz), Tensor(w))._data
    close(got, want, TIGHT)

    def program(bcz, w):
        return jnp.sum(F.gated_short_conv(Tensor(bcz), Tensor(w))._data ** 2)

    def reference(bcz, w):
        eye = jnp.eye(3 * ch)
        one = lambda x: ref.short_conv(  # noqa: E731
            x, eye, w, jnp.eye(ch), jnp.matmul)
        return jnp.sum(jax.vmap(one)(bcz) ** 2)

    grads_close(jax.jit(jax.grad(program, argnums=(0, 1)))(bcz, w),
                jax.jit(jax.grad(reference, argnums=(0, 1)))(bcz, w))


# ============================================================ the share test
def uncut_cfg():
    cfg = copy.deepcopy(CFG)
    cfg["num_experts"], cfg["experts_held"] = 16, [0, 16]
    return cfg


def moe_layer(cfg, lw, held):
    lo, hi = held
    layer = nn.SwiGLUMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"], 0,
        cfg["router_width"], cfg["num_experts_per_tok"], experts_held=held,
        routed_scale=cfg["routed_scaling_factor"], norm_eps=1e-6)
    put = {"gate_weight": lw["moe.router"],
           "e_score_correction_bias": lw["moe.bias"],
           "w_gate": lw["moe.w1"][lo:hi], "w_up": lw["moe.w3"][lo:hi],
           "w_down": lw["moe.w2"][lo:hi]}
    for name, p in layer.named_parameters():
        p._swap_payload(put[name])
    return layer


def test_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the parts of all four shares (experts 0-3, 4-7, 8-11,
    12-15: the deployment's 4-way split at tiny size; the layer has no
    shared expert, so nothing is counted once) are the uncut reference
    layer, and each share is the reference's own share. Tolerance 4 x TIGHT
    on the sum: four shares' roundings add."""
    cfg = uncut_cfg()
    # weights of order 0.2, so that the layer's output is of order 1 and
    # an absolute tolerance means what it says (the table's 0.02 gives 1e-3)
    lw = {"moe.router": rand((64, 16), 1, 0.3),
          "moe.bias": rand((16,), 5, 0.05),        # a bias that steers
          "moe.w1": rand((16, 64, 48), 2, 0.2),
          "moe.w3": rand((16, 64, 48), 3, 0.2),
          "moe.w2": rand((16, 48, 64), 4, 0.2)}
    u = rand((40, 64), 23)
    whole = ref.moe(u, lw, lw["moe.bias"], cfg, jnp.matmul)
    total = None
    for lo in range(0, 16, 4):
        out = traced(moe_layer(cfg, lw, (lo, lo + 4)), Tensor(u))._data
        part = dict(cfg, experts_held=[lo, lo + 4])
        share = {k: (v[lo:lo + 4] if k in ("moe.w1", "moe.w3", "moe.w2")
                     else v) for k, v in lw.items()}
        close(out, ref.moe(u, share, lw["moe.bias"], part, jnp.matmul),
              TIGHT)
        total = out if total is None else total + out
    close(total, whole, 4 * TIGHT)
    assert float(jnp.mean(jnp.abs(whole))) > 0.1


# ========================================================= the whole model
def float32_model(seed):
    cfg = driver.model_config(CFG)
    model = Lfm2MoeForCausalLM(cfg)
    made = weights_lib.make(CFG, seed)
    for name, p in model.named_parameters():
        p._swap_payload(made[driver.table_key(name)])
    return model


@pytest.mark.parametrize("recompute", [False, True], ids=["plain", "remat"])
def test_float32_loss_and_gradients_are_the_references(recompute):
    """The model outside autocast, float32 against float32: the loss to
    ``TIGHT`` and every leaf's gradient to ``GRAD`` of its largest entry,
    with and without the blocks rematerialised."""
    model = float32_model(3)
    model.cfg.recompute = model.model.cfg.recompute = recompute
    ids = np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 48))
    params, buffers = ref.init_params(CFG, 3)
    want, want_grads = ref.loss_and_grads(params, buffers, ids, CFG)
    named = [(n, p) for n, p in model.named_parameters()
             if not p.stop_gradient]

    def loss_of(arrays):
        olds = [p._data for _n, p in named]
        for (_n, p), a in zip(named, arrays):
            p._data = a
        try:
            return model(Tensor(jnp.asarray(ids)),
                         labels=Tensor(jnp.asarray(ids)))[1]._data
        finally:
            for (_n, p), o in zip(named, olds):
                p._data = o

    got, grads = jax.jit(jax.value_and_grad(loss_of))(
        [p._data for _n, p in named])
    assert abs(float(got) - float(want)) < TIGHT * 5
    for (name, _p), g in zip(named, grads):
        w = want_grads[driver.table_key(name)]
        scale = float(jnp.max(jnp.abs(w)))
        close(g / scale, w / scale, GRAD)


def test_logits_come_from_the_tied_embedding():
    model = float32_model(4)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 97, (1, 16)))
    logits = traced(model, Tensor(ids))
    assert logits.shape == [1, 16, CFG["vocab_size"]]
    assert "lm_head" not in {n for n, _p in model.named_parameters()}


@pytest.mark.parametrize("seed", [11, 12])
def test_engine_fit_follows_the_reference(seed):
    """``Engine.fit`` under bfloat16 O1 autocast, as the cell runs it: the
    first three losses, the first step's gradient norms and the parameters'
    change after three steps against the reference's, two ``fit`` calls of
    one and two steps."""
    devices = jax.devices()[:1]
    calls = FIT["check_calls"]
    data = TokenStream(seed, CFG["vocab_size"], FIT["seq_len"], FIT["batch"],
                       sum(calls))
    batches = [data.rows_of_epoch(e) for e in range(sum(calls))]
    want = driver.reference_numbers(CFG, seed, batches, calls)
    engine, opt, names = driver.build(CFG, seed, devices)
    got = driver.first_steps(engine, opt, names, CFG, seed, data, calls)
    numbers = check_lib.train_numbers(got, want)
    for step in (1, 2, 3):
        assert numbers[f"loss_gap_step{step}"] < LOSS, numbers
    assert numbers["grad_norm_gap"] < GRAD_NORM, numbers
    assert numbers["delta_norm_gap"] < DELTA_NORM, numbers
    # the bias is a buffer: no gradient, no moment, no change
    assert all(name != "moe.bias" for _layer, name in names)
    assert len(names) == len(engine._params)


# ===================================================== counters and scopes
def tiny_engine(seed=5):
    return driver.build(CFG, seed, jax.devices()[:1])


def test_step_carries_the_load_counter_only_under_metrics(metrics_on):
    """Metrics on: the donated state has a fourth entry, the epoch's read
    brings the load vector to the host and to the expert-load metrics, and
    every selected pair is counted. Off (the next test): none of it."""
    engine, _opt, _names = tiny_engine()
    data = TokenStream(5, CFG["vocab_size"], FIT["seq_len"],
                       2 * FIT["batch"], 1)
    pairs0 = metrics.REGISTRY.get("paddle_tpu_moe_routed_pairs_total").total()
    engine.fit(data, epochs=2, batch_size=FIT["batch"])
    load = engine.step_counters["moe.expert_load"]
    sparse = CFG["num_hidden_layers"] - CFG["num_dense_layers"]
    assert load.shape == (sparse, CFG["num_experts"] + 2)
    selected = 4 * FIT["batch"] * FIT["seq_len"] * CFG["num_experts_per_tok"]
    assert (load[:, -1] == selected).all()
    assert (load[:, :-2].sum(axis=1) == load[:, -2]).all()
    assert (load[:, -2] > 0).all() and (load[:, -2] < selected).all()
    assert int(engine._train_step._cache_size()) == 1
    pairs = metrics.REGISTRY.get("paddle_tpu_moe_routed_pairs_total").total()
    assert pairs - pairs0 == load[:, -2:].sum()


@pytest.mark.parametrize("rows,k,held,router,want", [
    pytest.param(16384, 4, 8, 32, (32768, 65536), id="lfm2-cell"),
    pytest.param(16384, 6, 8, 64, (24576, 98304), id="smallthinker-cell"),
    pytest.param(2048, 4, 8, 32, (4096, 8192), id="first-grouped-rows"),
    pytest.param(2047, 4, 8, 32, None, id="masked-form"),
    pytest.param(2100, 4, 3, 32, (2048, 8400), id="whole-tiles"),
    pytest.param(4096, 2, 2, None, (8192, 8192), id="router-unknown"),
    pytest.param(4096, 2, 8, 8, (8192, 8192), id="every-expert-held")])
def test_the_stride_follows_from_the_shapes(rows, k, held, router, want):
    """Twice the pairs even routing lands on the held experts, in whole
    512-row tiles, never longer than the buffer; the whole buffer where the
    router's width is not known; nothing under ``GROUPED_MIN_ROWS``."""
    assert E.pair_walk(rows, k, held, router) == want


def test_strides_walked_are_exported_beside_the_pairs(metrics_on):
    """From an epoch's landed and selected pairs a layer and the call's
    static stride: ``ceil(landed a step / stride)`` strides a layer a step
    against the strides of the whole buffer."""
    from paddle_tpu.distributed.fleet import moe as fleet_moe
    counter = metrics.REGISTRY.get("paddle_tpu_moe_pair_strides_total")
    before = counter.total()
    steps, pairs, stride = 10, 65536, 32768
    landed = np.array([20000, 40000, 0, 3]) * steps
    walked, whole = fleet_moe.stamp_pair_strides(
        landed, np.full(4, steps * pairs), stride, pairs)
    assert (walked, whole) == (steps * (1 + 2 + 0 + 1), steps * 4 * 2)
    assert counter.total() - before == walked + whole


def test_a_counting_step_notes_what_its_routed_layers_walk(metrics_on):
    """The model notes the ``(stride, pairs)`` of its routed layers where
    the counting step is traced (here: shapes only), and hands it to the
    exporter with each epoch's load; a batch that takes the masked form
    walks nothing and exports no stride."""
    from paddle_tpu.distributed.fleet import moe as fleet_moe
    lm = Lfm2MoeForCausalLM(driver.model_config(CFG))
    model = lm.model
    lo, hi = model.cfg.experts_held
    counter = metrics.REGISTRY.get("paddle_tpu_moe_pair_strides_total")
    sparse = CFG["num_hidden_layers"] - CFG["num_dense_layers"]
    for rows, walks in ((E.GROUPED_MIN_ROWS, True), (64, False)):
        def forward(ids):
            with obs_trace.step_counters():
                return model(Tensor(ids))._data
        jax.eval_shape(forward, jax.ShapeDtypeStruct((1, rows), jnp.int32))
        want = E.pair_walk(rows, CFG["num_experts_per_tok"], hi - lo,
                           model.cfg.num_experts)       # the router's width
        assert model._walk == want and (want is not None) is walks
        fresh = np.zeros((sparse, hi - lo + 2), np.int64)
        fresh[:, -1] = 3 * rows * CFG["num_experts_per_tok"]   # three steps
        fresh[:, -2] = 3 * 5
        before = counter.total()
        model._export_load(fresh)
        whole = 0 if not walks else sparse * 3 * -(-want[1] // want[0])
        assert counter.total() - before == (sparse * 3 + whole
                                            if walks else 0)


def test_step_carries_no_counter_with_metrics_off():
    engine, _opt, _names = tiny_engine()
    data = TokenStream(5, CFG["vocab_size"], FIT["seq_len"], FIT["batch"], 1)
    engine.fit(data, epochs=1, batch_size=FIT["batch"])
    assert engine.step_counters == {}
    assert len(engine._init_opt_state(None)) == 3
    assert not obs_trace.counting_step()


def test_the_counter_table_is_closed():
    with pytest.raises(KeyError):
        obs_trace.count_in_step("moe.nothing", 1)
    assert not obs_trace.counting_step()
    obs_trace.count_in_step("moe.expert_load", 1)        # no step: nothing
    with obs_trace.step_counters() as counted:
        assert obs_trace.counting_step()
        obs_trace.count_in_step("moe.expert_load", 2)
        obs_trace.count_in_step("moe.expert_load", 3)
    assert counted.values == {"moe.expert_load": 5}
    assert not obs_trace.counting_step()


def test_scopes_are_in_the_lowered_step():
    """Every scope ``observability.trace.DEVICE_SCOPES`` lists for this
    model, in the ``op_name`` of the lowered train step; the tiny batch
    takes the masked product, so the grouped form's two scopes are looked
    for in a call of its own."""
    engine, _opt, _names = tiny_engine()
    engine.prepare()
    ids = jnp.zeros((FIT["batch"], FIT["seq_len"]), jnp.int32)
    text = engine._train_step.lower(
        [p._data for p in engine._params], engine._init_opt_state(None),
        jnp.float32(1e-4), ids, ids).as_text(debug_info=True)
    for scope in ("embed", "short_conv", "attn", "mlp", "moe",
                  "moe/moe.router", "moe/moe.experts", "loss", "optimizer"):
        assert scope in obs_trace.DEVICE_SCOPES or "/" in scope
        assert f"{scope}/" in text or f"{scope})" in text, scope
    n = E.GROUPED_MIN_ROWS
    grouped = jax.jit(lambda x, i, w, a, b, c: E.grouped_experts_arrays(
        x, i, w, (a, b, c), 0)).lower(
            jnp.zeros((n, 8)), jnp.zeros((n, 2), jnp.int32),
            jnp.zeros((n, 2)), jnp.zeros((2, 8, 8)), jnp.zeros((2, 8, 8)),
            jnp.zeros((2, 8, 8))).as_text(debug_info=True)
    for scope in ("moe.group", "moe.grouped_matmul"):
        assert scope in obs_trace.DEVICE_SCOPES
        assert f"{scope}/" in grouped, scope


def test_remat_block_takes_a_block_that_returns_two():
    def blk(x):
        return x * 2.0, Tensor(jnp.sum(x._data > 0).astype(jnp.int32))

    def f(a):
        out, count = remat_block(blk, Tensor(a))
        return jnp.sum(out._data), count._data

    (total, count), grad = jax.value_and_grad(f, has_aux=True)(
        jnp.asarray([1.0, -2.0, 3.0]))
    assert float(total) == 4.0 and int(count) == 2
    assert np.asarray(grad).tolist() == [2.0, 2.0, 2.0]


# ============================================== the configuration's numbers
def published():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-L6-ep4.json")) as f:
        return json.load(f)


def test_the_cut_keeps_every_width_and_counts_as_the_issue_does():
    cfg = published()
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"],
            cfg["num_experts_per_tok"], cfg["router_width"]) == (
                2048, 7168, 1792, 32, 8, 3, 4, 32)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types",
                                   "num_experts", "vocab_size"}
    assert cfg["reduced_from"]["num_experts"] == 32
    assert cfg["experts_held"] == [0, cfg["num_experts"]]
    assert flops_lfm2_moe.param_count(cfg) == 568_647_936
    # parameters of the weight table, buffers and all
    table = sum(int(np.prod(shape)) for _l, _n, shape, _i, _d
                in weights_lib.leaves(cfg))
    assert table == flops_lfm2_moe.param_count(cfg)


def test_operations_a_token_bill_the_landed_pairs_only():
    cfg = published()
    fwd = flops_lfm2_moe.fwd_flops_per_token(cfg, 8192)
    masked = flops_lfm2_moe.fwd_flops_per_token(cfg, 8192, masked=True)
    assert round(fwd / 1e6) == 554 and round(masked / 1e6) == 1171
    assert flops_lfm2_moe.train_flops_per_token(cfg, 8192) == 3 * fwd
    assert 0.15 < flops_lfm2_moe.expert_layer_share(cfg, 8192) < 0.17
    sizes = [2048] * 7 + [0]
    assert flops_lfm2_moe.grouped_call_flops(sizes, 2048, 1792) == \
        2.0 * 7 * 2048 * 2048 * 1792
    assert flops_lfm2_moe.grouped_call_bytes(sizes, 2048, 1792) == \
        2.0 * (7 * 2048 * (2048 + 1792) + 7 * 2048 * 1792)
