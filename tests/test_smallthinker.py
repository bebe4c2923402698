"""SmallThinker (window + rotary layers beside full NoPE layers, a router that
reads the attention's input, routed ReGLU experts with a softmax over the
chosen logits) on the TRAINING path, against its plain reference
``benchmark/reference/smallthinker.py`` on seeded weights at tiny widths
(``benchmark/tests/tiny_smallthinker.py``: hidden 64, 4 query heads over 2 KV
heads of 16, four layers ``full | window window window`` with a window of 24
of the 64 tokens, 16 experts top-4, four held).

Few compiled programs (ROADMAP D19: a test costs its compiles): one model
a file, the whole-model comparison one jitted program, ``Engine.fit`` one
step program a seed.

Tolerances, each with its reason:

* ``TIGHT`` 2e-5 absolute on values of order 1, float32 against float32 at
  ``highest`` precision: a few hundred additions taken in another order (a
  masked product against a loop over experts, whole-row attention against
  blocks of queries);
* ``GRAD`` 2e-4 relative to the largest entry of a gradient: the same
  reordering through a backward pass;
* the ``Engine.fit`` comparison runs the program as the cell does, under
  bfloat16 O1 autocast, so its limits are bfloat16's: ``LOSS`` 2e-4 relative,
  ``GRAD_NORM`` 0.06 on the worst leaf's norm (a top-4 choice that flips
  between bfloat16 and float32 activations moves a router's and an expert's
  gradient: seeds 11-13 read 0.001-0.037 in
  ``benchmark/tests/test_correct_smallthinker.py``'s runs), ``DELTA_NORM``
  0.007 (the same seeds read 0.0024-0.0028; the window dropped from one
  layer reads 0.013-0.018).
"""
from __future__ import annotations

import json
import os
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
from benchmark.drivers import fit_smallthinker as driver  # noqa: E402
from benchmark.drivers.fit import TokenStream  # noqa: E402
from benchmark.lib import check as check_lib  # noqa: E402
from benchmark.lib import flops_smallthinker  # noqa: E402
from benchmark.lib import weights_smallthinker as weights_lib  # noqa: E402
from benchmark.reference import smallthinker as ref  # noqa: E402
from benchmark.tests.tiny_smallthinker import FIT, SMALLTHINKER  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models import (SmallThinkerConfig,  # noqa: E402
                               SmallThinkerForCausalLM, smallthinker_tiny)
from paddle_tpu.nn.functional import experts as E  # noqa: E402
from paddle_tpu.observability import trace as obs_trace  # noqa: E402

from served import close, rand, traced  # noqa: E402

TIGHT, GRAD = 2e-5, 2e-4
LOSS, GRAD_NORM, DELTA_NORM = 2e-4, 0.06, 0.007
CFG = SMALLTHINKER


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


# ======================================================= route and experts
def test_softmax_route_is_a_softmax_over_the_chosen_logits():
    """The k largest logits, a softmax over those k: the weights sum to 1
    whatever the other logits are, and they carry gradients to the input
    and the gate."""
    u, gate = rand((12, 16), 1), rand((16, 8), 2, 0.5)
    idx, w = F.softmax_topk_route(Tensor(u), Tensor(gate), 3)
    logits = np.asarray(u) @ np.asarray(gate)
    order = np.argsort(-logits, axis=1)[:, :3]
    assert (np.asarray(idx._data) == order).all()
    chosen = np.take_along_axis(logits, order, axis=1)
    want = np.exp(chosen - chosen.max(1, keepdims=True))
    close(w._data, want / want.sum(1, keepdims=True), TIGHT)
    close(jnp.sum(w._data, axis=1), np.ones(12), TIGHT)
    grads = jax.grad(lambda u, g: jnp.sum(
        E.softmax_route_arrays(u, g, 3)[1] ** 2), argnums=(0, 1))(u, gate)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in grads)


@pytest.mark.parametrize("rows", [40, E.GROUPED_MIN_ROWS],
                         ids=["masked", "grouped"])
def test_a_relu_gate_is_an_argument_of_both_forms(rows):
    """``held_experts_swiglu(..., activation="relu")`` in the form the rows
    ask for, against the reference's loop over the held experts; the
    default stays SiLU (the three SwiGLU models trace what they traced)."""
    hidden, width, held, lo, k = 16, 24, 4, 4, 3
    u = rand((rows, hidden), 1)
    gate, up = rand((held, hidden, width), 2, 0.3), rand(
        (held, hidden, width), 3, 0.3)
    down = rand((held, width, hidden), 4, 0.3)
    idx, w = E.softmax_route_arrays(u, rand((hidden, 16), 5, 0.5), k)
    cfg = {"experts_held": [lo, lo + held]}
    lp = {"moe.gate": gate, "moe.up": up, "moe.down": down}
    want = jax.jit(lambda u: ref.experts(u, idx, w, lp, cfg, jnp.matmul))(u)

    def call(activation):
        return traced(F.held_experts_swiglu, Tensor(u), Tensor(idx),
                      Tensor(w), Tensor(gate), Tensor(up), Tensor(down),
                      lo=lo, **activation)._data

    close(call({"activation": "relu"}), want, TIGHT * 10)
    silu = call({})
    assert float(jnp.max(jnp.abs(silu - want))) > 1e-3
    close(silu, call({"activation": "silu"}), 0.0)
    with pytest.raises(ValueError):
        F.held_experts_swiglu(Tensor(u), Tensor(idx), Tensor(w), Tensor(gate),
                              Tensor(up), Tensor(down), activation="gelu")


def moe_layer(lw, held, router_width=16, top_k=4):
    lo, hi = held
    layer = nn.SwiGLUMoE(64, 48, 0, router_width, top_k, experts_held=held,
                         activation="relu", route="softmax")
    put = {"gate_weight": lw["moe.router"], "w_gate": lw["moe.gate"][lo:hi],
           "w_up": lw["moe.up"][lo:hi], "w_down": lw["moe.down"][lo:hi]}
    assert {n for n, _p in layer.named_parameters()} == set(put)
    for name, p in layer.named_parameters():
        p._swap_payload(put[name])
    return layer


def test_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the routed parts of all eight shares (two experts
    each of 16: the deployment's 8-way split at tiny size; the layer has no
    shared expert, so nothing is counted once) sum to the uncut reference
    layer, each share is the reference's own share, and the routing every
    share is handed is the one routing of the uncut layer. Tolerance
    8 x TIGHT on the sum: eight shares' roundings add."""
    lw = {"moe.router": rand((64, 16), 1, 0.3),
          "moe.gate": rand((16, 64, 48), 2, 0.2),
          "moe.up": rand((16, 64, 48), 3, 0.2),
          "moe.down": rand((16, 48, 64), 4, 0.2)}
    x, u = rand((40, 64), 22), rand((40, 64), 23)
    cfg = {"moe_num_active_primary_experts": 4}
    idx, w = ref.route(x, lw["moe.router"], cfg)
    whole = ref.experts(u, idx, w, lw, dict(cfg, experts_held=[0, 16]),
                        jnp.matmul)
    total = None
    for lo in range(0, 16, 2):
        layer = moe_layer(lw, (lo, lo + 2))
        out = traced(lambda x, u, layer=layer: layer(
            u, routing=layer.route(x)), Tensor(x), Tensor(u))._data
        share = {k: (v if k == "moe.router" else v[lo:lo + 2])
                 for k, v in lw.items()}
        close(out, ref.experts(u, idx, w, share,
                               dict(cfg, experts_held=[lo, lo + 2]),
                               jnp.matmul), TIGHT)
        total = out if total is None else total + out
    close(total, whole, 8 * TIGHT)
    assert float(jnp.mean(jnp.abs(whole))) > 0.1


# ========================================================= the whole model
def float32_model(seed, **over):
    cfg = driver.model_config(dict(CFG, **over))
    model = SmallThinkerForCausalLM(cfg)
    made = weights_lib.make(CFG, seed)
    for name, p in model.named_parameters():
        p._swap_payload(made[driver.table_key(name)])
    return model


def loss_and_grads_of(model, ids):
    named = list(model.named_parameters())

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
    return float(got), {driver.table_key(n): g
                        for (n, _p), g in zip(named, grads)}


def test_float32_loss_and_gradients_are_the_references():
    """The model outside autocast, float32 against float32, its blocks
    rematerialised as the cell's are: the loss to ``TIGHT`` and every
    leaf's gradient to ``GRAD`` of its largest entry. 48 tokens against a
    window of 24: the band's lower edge is inside the sequence."""
    model = float32_model(3)
    ids = np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 48))
    want, want_grads = ref.loss_and_grads(ref.init_params(CFG, 3), ids, CFG)
    got, grads = loss_and_grads_of(model, ids)
    assert abs(got - float(want)) < TIGHT * 5
    assert set(grads) == set(want_grads)
    for key, w in want_grads.items():
        scale = float(jnp.max(jnp.abs(w)))
        close(grads[key] / scale, w / scale, GRAD)


def test_the_routing_is_taken_before_the_attention():
    """A block routes from ``RMSNorm_in(h)``, the attention's input: the
    load it counts is that routing's, and it is NOT the routing its experts'
    own input ``u`` would give (the published block's order, which the cell's
    ``assumed`` records)."""
    model = float32_model(4)
    blk = model.model.layers[1]
    # the table's N(0, 0.02) leaves the attention a hundredth of the stream:
    # larger v and o, so that a = h + Attn(x) is another tensor than h
    for proj, scale in ((blk.self_attn.v_proj, 10.0),
                        (blk.self_attn.o_proj, 50.0)):
        proj.weight._swap_payload(proj.weight._data * scale)
    h = Tensor(rand((1, 40, 64), 7))
    lo, hi = CFG["experts_held"]

    def parts(h):
        moe = blk.block_sparse_moe
        x = blk.input_layernorm(h)
        idx_x, _w = moe.route(x)
        u = blk.post_attention_layernorm(h + blk.self_attn(x))
        idx_u, _w = moe.route(u)
        _out, load = blk(h, with_load=True)
        return idx_x, idx_u, load

    idx_x, idx_u, load = (np.asarray(a._data) for a in traced(parts, h))
    want = np.asarray(E.load_arrays(jnp.asarray(idx_x), lo, hi - lo))
    assert (load == want).all()
    assert (np.sort(idx_x, axis=1) != np.sort(idx_u, axis=1)).any()
    other = np.asarray(E.load_arrays(jnp.asarray(idx_u), lo, hi - lo))
    assert (load != other).any()


def test_layers_take_positions_and_window_from_the_two_lists():
    """``rope_layout`` / ``sliding_window_layout`` a layer: the published
    pattern where none is given, the lists' own where they differ; a tied
    head or another route is refused."""
    cfg = SmallThinkerConfig(num_hidden_layers=8)
    assert cfg.rope_layout == cfg.sliding_window_layout == (0, 1, 1, 1) * 2
    model = SmallThinkerForCausalLM(smallthinker_tiny(
        rope_layout=(1, 0, 1, 0), sliding_window_layout=(0, 0, 1, 1)))
    got = [(blk.self_attn.rotary, blk.self_attn.window, blk.attn_scope)
           for blk in model.model.layers]
    assert got == [(True, None, "attn.full"), (False, None, "attn.full"),
                   (True, 8, "attn.window"), (False, 8, "attn.window")]
    assert model.lm_head.weight.shape == [64, 256]
    for bad in (dict(tie_word_embeddings=True),
                dict(moe_primary_router_apply_softmax=False),
                dict(rope_layout=(0, 1))):
        with pytest.raises(ValueError):
            smallthinker_tiny(**bad)


def test_dropping_the_window_from_a_layer_changes_the_gradients():
    """The fault of the cell's check at test size: a window layer run causal
    over every key (48 tokens, a window of 24) meets the reference in no
    leaf of that layer's attention, where the sound model is within ``GRAD``
    (the loss barely moves at this size: N(0, 0.02) weights leave the
    attention a hundredth of the stream)."""
    ids = np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 48))
    _loss, want = ref.loss_and_grads(ref.init_params(CFG, 3), ids, CFG)
    broken = float32_model(3)
    broken.model.layers[1].self_attn.window = None
    _loss, grads = loss_and_grads_of(broken, ids)
    for leaf in ("attn.q", "attn.k", "attn.v"):
        w = want[(1, leaf)]
        gap = float(jnp.linalg.norm(grads[(1, leaf)] - w)
                    / jnp.linalg.norm(w))
        assert gap > 0.05, (leaf, gap)


@pytest.mark.parametrize("seed", [11, 12])
def test_engine_fit_follows_the_reference(seed):
    """``Engine.fit`` under bfloat16 O1 autocast, as the cell runs it: the
    first three losses, the first step's gradient norms and the parameters'
    change after three steps against the reference's ``follow``, two
    ``fit`` calls of one and two steps."""
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
    assert len(names) == len(engine._params) == len(set(names))


# ===================================================== counters and scopes
def test_step_carries_the_load_counter_and_the_scopes(metrics_on):
    """Metrics on: the step's donated state has the load counter, every
    selected pair of every layer is in it; and the lowered step names every
    scope the cell's readers look for, the router's under ``moe`` though it
    is entered before the attention's."""
    engine, _opt, _names = driver.build(CFG, 5, jax.devices()[:1])
    data = TokenStream(5, CFG["vocab_size"], FIT["seq_len"],
                       2 * FIT["batch"], 1)
    engine.fit(data, epochs=1, batch_size=FIT["batch"])
    load = engine.step_counters["moe.expert_load"]
    assert load.shape == (CFG["num_hidden_layers"],
                          CFG["moe_num_primary_experts"] + 2)
    selected = (2 * FIT["batch"] * FIT["seq_len"]
                * CFG["moe_num_active_primary_experts"])
    assert (load[:, -1] == selected).all()
    assert (load[:, :-2].sum(axis=1) == load[:, -2]).all()
    assert (load[:, -2] > 0).all() and (load[:, -2] < selected).all()
    ids = jnp.zeros((FIT["batch"], FIT["seq_len"]), jnp.int32)
    text = engine._train_step.lower(
        [p._data for p in engine._params], engine._init_opt_state(None),
        jnp.float32(1e-4), ids, ids).as_text(debug_info=True)
    for scope in ("embed", "attn.full", "attn.window", "moe",
                  "moe/moe.router", "moe/moe.experts", "loss", "optimizer"):
        assert scope.split("/")[-1] in obs_trace.DEVICE_SCOPES, scope
        assert f"{scope}/" in text or f"{scope})" in text, scope
    assert "attn.window/moe.router" not in text
    assert "attn.full/moe.router" not in text


# ============================================== the configuration's numbers
def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b-L4-ep8.json")) as f:
        return json.load(f)


def test_the_cut_keeps_every_width_and_counts_as_the_issue_does():
    cfg = published()
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_ffn_hidden_size"],
            cfg["moe_num_active_primary_experts"], cfg["router_width"],
            cfg["sliding_window_size"], cfg["max_position_embeddings"]) == (
                2560, 128, 28, 4, 768, 6, 64, 4096, 16384)
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout"}
    assert cfg["experts_held"] == [0, cfg["moe_num_primary_experts"]]
    assert cfg["vocab_size"] * 8 == cfg["reduced_from"]["vocab_size"]
    assert flops_smallthinker.param_count(cfg) == 370_547_200
    table = sum(int(np.prod(shape)) for _l, _n, shape, _i, _d
                in weights_lib.leaves(cfg))
    assert table == flops_smallthinker.param_count(cfg)
    whole = dict(cfg, num_hidden_layers=52, moe_num_primary_experts=64,
                 vocab_size=151936)
    assert round(flops_smallthinker.param_count(whole) / 1e9, 1) == 21.5
    # in-mask pairs a head at the cell's length: causal 134.2 M, window
    # 58.7 M (W (W + 1) / 2 + (S - W) W), 0.44 of it
    assert flops_smallthinker.pairs_in_mask(16384) == 134_225_920
    assert flops_smallthinker.pairs_in_mask(16384, 4096) == 58_722_304
    model_cfg = driver.model_config(cfg)
    assert (model_cfg.moe_num_primary_experts, model_cfg.experts_held) == (
        64, (0, 8))
