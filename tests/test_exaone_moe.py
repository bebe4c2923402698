"""K-EXAONE (window + full attention, SwiGLU experts with a shared expert)
against its plain reference, ``benchmark/reference/exaone_moe.py``, on seeded
weights at tiny widths (``benchmark/tests/tiny_exaone_moe.py``: hidden 64, 4
query heads over 2 KV heads of 16, a window of 8, five layers dense +
``S S F S``, 16 experts top-4 of width 48).

Everything runs in float32 on the CPU, the reference at ``highest``
precision, so the tolerances below are those of float32 sums taken in another
order (an online softmax against a whole one, a batched expert product
against a loop), not of a lower precision:

* ``TIGHT`` 2e-5 absolute on values of order 1: one layer, a few hundred
  float32 additions reordered;
* ``LOGITS`` 5e-4 absolute on logits of order 1: five blocks deep, through
  the paged cache and the window rows, the same reordering compounded. A
  window one key too wide or too narrow moves a logit by more than 1e-2
  (``test_window_edge_shows_in_the_logits``).
"""
from __future__ import annotations

import copy
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
from benchmark.lib import weights_exaone_moe as weights_lib  # noqa: E402
from benchmark.reference import exaone_moe as ref  # noqa: E402
from benchmark.tests.tiny_exaone_moe import EXAONE  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import serving  # noqa: E402

import served  # noqa: E402
from served import (close, models, rand, rec, recording,  # noqa: E402,F401
                    traced)

SEED = 5
TIGHT = 2e-5
LOGITS = 5e-4
CFG = EXAONE
WINDOW = CFG["sliding_window"]
#: chunks of 16 tokens: window rows 8 + 16 = 24 a lane, so a prompt of 40
#: wraps them, and three chunks prefill it
CASE = served.Case(
    "window", CFG, SEED, budget=16, atol=LOGITS,
    reference=lambda ids: ref.logits(CFG, SEED, ids, block=16))


def f32_weights(cfg, seed, layers=None):
    return served.f32_weights(weights_lib, cfg, seed, layers)


@pytest.fixture(scope="module")
def model():
    return served.model_of(CASE)


# ============================================================ expert layer
def uncut_cfg():
    cfg = copy.deepcopy(CFG)
    cfg["num_experts"], cfg["experts_held"] = 16, [0, 16]
    return cfg


def moe_layer(cfg, lw, held):
    lo, hi = held
    layer = nn.SwiGLUMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        cfg["router_width"], cfg["num_experts_per_tok"], experts_held=held,
        routed_scale=cfg["routed_scaling_factor"])
    put = {"gate_weight": lw["router"],
           "e_score_correction_bias": lw["e_score_correction_bias"],
           "w_gate": lw["w_gate"][lo:hi], "w_up": lw["w_up"][lo:hi],
           "w_down": lw["w_down"][lo:hi],
           "shared_gate.weight": lw["shared_gate"],
           "shared_up.weight": lw["shared_up"],
           "shared_down.weight": lw["shared_down"]}
    for name, p in layer.named_parameters():
        p._swap_payload(put[name])
    return layer


@pytest.fixture(scope="module")
def uncut():
    cfg = uncut_cfg()
    lw = {name: a for (_l, name), a in
          f32_weights(cfg, SEED, layers=[1]).items()}
    # a bias that steers the choice without entering the weights
    lw["e_score_correction_bias"] = rand((16,), 99, scale=0.05)
    return cfg, lw


def ref_moe(cfg, u, lw, held):
    with jax.default_matmul_precision("highest"):
        return ref.feed_forward(dict(cfg, experts_held=list(held)),
                                u.reshape(-1, u.shape[-1]),
                                lw).reshape(u.shape)


@pytest.mark.parametrize("held", [(0, 4), (12, 16), (0, 16), (2, 11)])
def test_expert_layer_is_the_per_token_loop(uncut, held):
    """The layer's held-experts product against a loop over tokens and
    their chosen experts in numpy (float64), and the load counters against
    the same loop."""
    cfg, lw = uncut
    layer = moe_layer(cfg, lw, held)
    u = rand((11, 64), 17)
    out, load = traced(layer, Tensor(u), with_load=True)
    w = {k: np.asarray(v, np.float64) for k, v in lw.items()}
    un = np.asarray(u, np.float64)
    lo, hi = held
    silu = lambda x: x / (1.0 + np.exp(-x))  # noqa: E731

    def expert(x, g, up, down):
        return (silu(x @ g) * (x @ up)) @ down

    want = np.zeros_like(un)
    counts = np.zeros(hi - lo + 2, np.int64)
    for t in range(11):
        s = 1.0 / (1.0 + np.exp(-(un[t] @ w["router"])))
        top = np.argsort(-(s + w["e_score_correction_bias"]))[:4]
        for e in top:
            counts[-1] += 1
            if lo <= e < hi:
                counts[e - lo] += 1
                counts[-2] += 1
                want[t] += (2.5 * s[e] / (s[top].sum() + 1e-20) * expert(
                    un[t], w["w_gate"][e], w["w_up"][e], w["w_down"][e]))
        want[t] += expert(un[t], w["shared_gate"], w["shared_up"],
                          w["shared_down"])
    close(out._data, want, TIGHT)
    assert np.asarray(load._data).tolist() == counts.tolist()


def test_shares_add_up_to_the_uncut_layer(uncut):
    """THE SHARE TEST: the routed parts of all 8 shares (experts 0-1, 2-3,
    ... 14-15: the deployment's 8-way split at tiny size), with what every
    chip computes alike (the shared expert) counted once, are the uncut
    reference layer; and each share is the reference's own share.
    Tolerance 8 x TIGHT on the sum: eight shares' roundings add."""
    cfg, lw = uncut
    u = rand((2, 9, 64), 23)
    whole = ref_moe(cfg, u, lw, (0, 16))
    total = shared = None
    for lo in range(0, 16, 2):
        layer = moe_layer(cfg, lw, (lo, lo + 2))
        shared = traced(layer.shared, Tensor(u.reshape(-1, 64)))._data
        out = traced(layer, Tensor(u))._data
        part = dict(lw, **{k: lw[k][lo:lo + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        close(out, ref_moe(cfg, u, part, (lo, lo + 2)), TIGHT)
        routed = out.reshape(-1, 64) - shared
        total = routed if total is None else total + routed
    close((total + shared).reshape(u.shape), whole, 8 * TIGHT)


def test_latent_experts_keep_their_numbers():
    """``experts_arrays`` generalised over the expert's form gives the
    relu^2 form bit for bit what it gave as ``einsum, relu^2, mask,
    einsum`` (``nn.LatentMoE``'s product)."""
    from paddle_tpu.nn.functional import experts as E
    x, c = rand((7, 12), 1), jnp.abs(rand((7, 3), 2))
    w1, w2 = rand((3, 12, 20), 3), rand((3, 20, 12), 4)
    h = jnp.einsum("nl,elf->enf", x, w1,
                   preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(h)) * c.T[:, :, None]
    want = jnp.einsum("enf,efl->nl", h, w2,
                      preferred_element_type=jnp.float32)
    assert (np.asarray(E.experts_arrays(x, c, (w1, w2)))
            == np.asarray(want)).all()


# ========================================================= the whole model
@pytest.mark.parametrize("tokens", [9, 41])
def test_whole_sequence_forward_is_the_reference(model, tokens):
    """``forward(ids)``, what a trainer or an offline scorer calls; 41
    tokens are five windows long."""
    ids = np.random.RandomState(tokens).randint(
        1, CFG["vocab_size"], (2, tokens)).astype(np.int32)
    got = traced(model, paddle.to_tensor(ids))._data
    for row in range(2):
        close(got[row], ref.logits(CFG, SEED, ids[row], block=16), LOGITS)


def test_whole_sequence_forward_is_differentiable():
    """Eager autograd reaches every parameter through both attention
    kinds and the expert product (the router's correction bias only steers
    a choice: its gradient is zero). Twelve tokens: past the window's
    edge."""
    served.whole_sequence_forward_is_differentiable(
        CASE, np.random.RandomState(1).randint(
            1, CFG["vocab_size"], (2, 12)).astype(np.int32))


def test_window_edge_shows_in_the_logits(model):
    """The reference at a window of 7 or 9 in place of 8 moves a logit by
    far more than ``LOGITS``: the comparisons below would see a mask that
    is off by one key."""
    ids = np.random.RandomState(3).randint(1, CFG["vocab_size"], 40)
    at8 = ref.logits(CFG, SEED, ids, block=8)
    for other in (7, 9):
        moved = ref.logits(dict(CFG, sliding_window=other), SEED, ids,
                           block=8)
        assert float(jnp.abs(moved - at8).max()) > 20 * LOGITS


# ====================================================== through the engine
@pytest.mark.parametrize("front", ["engine", "router"])
def test_served_logits_are_the_references(rec, front):
    """Prefill in one to five chunks of 16 (left-padded first chunk where
    the prompt is no multiple of 16), then decode through the cache, four
    requests of unequal length sharing the batch, the longest nine windows
    long so that its rows wrap three times: every logits row the programs
    sampled from against the reference's full forward over prompt + served
    tokens."""
    eng, prompts, before = served.served_logits_are_the_references(
        CASE, rec, front, width=16, new=10,
        probe=lambda eng: eng.expert_load()["pairs_selected"])
    rows = sum(len(p) + 10 - 1 for p in prompts)
    load = eng.expert_load()
    # padding rows and sentinel lanes are not counted: every real row
    # selects top-4 in each of the four expert layers. (A slot that is
    # free while others decode rides the step as an idle lane of length
    # 1, which the engine counts as a row: staggered prefill leaves a few.)
    assert load["layers"] == [1, 2, 3, 4]
    selected = [n - b for n, b in zip(load["pairs_selected"], before)]
    assert len(set(selected)) == 1 and selected[0] >= 4 * rows
    assert (selected[0] - 4 * rows) % 4 == 0
    assert selected[0] - 4 * rows < 4 * 4 * 10      # < every lane idle
    assert [sum(t) for t in load["tokens"]] == load["pairs_held"]


def test_a_reused_slot_sees_nothing_of_its_last_request(rec):
    """The second request is shorter than the rows the first left behind.
    The rows need no clearing: what a row holds follows from the sequence's
    own length."""
    served.a_reused_slot_starts_clean(CASE, rec, (45, 13))


def test_a_lane_mid_prefill_keeps_its_rows_while_others_decode(rec):
    """(Its lane rides those decode steps under the seq = 0 sentinel and
    must write no row.)"""
    served.a_lane_mid_prefill_keeps_what_it_holds(CASE, rec)


def test_evict_then_readmit_reproduces_the_logits(rec):
    """The window rows need no free and no snapshot: the re-prefill
    rewrites them."""
    served.evict_then_readmit_reproduces_the_logits(
        CASE, rec, usable=6, length=12, new=20, max_ticks=400)


def test_speculate_needs_its_rows_back():
    with pytest.raises(TypeError, match="state rollback.*rows back"):
        served.engine(CASE, speculate="ngram")


@pytest.mark.parametrize("context_blocks", [16, 512])
def test_window_bytes_do_not_depend_on_the_context(context_blocks):
    """Window layers hold ``sliding_window`` + one chunk of rows a lane
    whatever ``context`` is; the pools of the one full layer alone grow
    with ``num_blocks``, and a token costs one layer's K and V."""
    eng = served.engine(CASE, max_blocks_per_seq=context_blocks,
                        num_blocks=2 * context_blocks)
    h = eng.health()
    rows = WINDOW + eng.prefill_width
    # four window layers: K and V rows of 2 KV heads of 16, float32
    assert h["window_bytes_per_slot"] == 4 * rows * 2 * 2 * 16 * 4
    assert h["state_bytes_per_slot"] == 0
    # ONE full layer of five pages: K and V, 2 KV heads of 16, float32
    assert h["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert len(eng.kc) == len(eng.vc) == 1
    held = [s for s in eng.state if isinstance(s, dict)]
    assert len(held) == 4 and all(
        s["k"].shape == (4, rows, 2, 16) for s in held)
    assert sum(a.size * a.dtype.itemsize for s in held
               for a in s.values()) == 4 * h["window_bytes_per_slot"]


def test_a_layer_keeps_two_states(model):
    """A sparse layer declares its attention state and an expert counter;
    ``_cache_index`` gives each its place."""
    layout = model.paged_adapter().cache_layout(jnp.float32)
    assert layout[0] == ("window_kv", WINDOW)                 # dense layer
    assert [s[0] for s in layout[3]] == ["paged_kv", "accumulator"]
    assert [s[0] for s in layout[4]] == ["window_kv", "accumulator"]
    index = serving._cache_index(layout)
    assert index[3, "paged_kv"] == 0
    assert sorted(index) == sorted(
        [(0, "window_kv"), (1, "window_kv"), (1, "accumulator"),
         (2, "window_kv"), (2, "accumulator"), (3, "paged_kv"),
         (3, "accumulator"), (4, "window_kv"), (4, "accumulator")])
    # the window rows, slot states and counters share one list, in order
    assert [index[k] for k in [(0, "window_kv"), (1, "window_kv"),
                               (1, "accumulator"), (3, "accumulator"),
                               (4, "accumulator")]] == [0, 1, 2, 5, 7]
    with pytest.raises(ValueError, match="two 'paged_kv'"):
        serving._cache_index([(("paged_kv",), ("paged_kv",))])
    with pytest.raises(ValueError, match="unknown cache state kind"):
        serving._cache_index([("ring",)])


def test_scopes_are_in_the_lowered_programs():
    """``attn.window``, ``attn.full``, ``moe`` with its three parts,
    ``mlp``, ``embed``, ``lm_head`` and, around both attention kinds,
    ``paged_attention``, in the ``op_name`` of both serving programs."""
    eng = served.engine(CASE)
    args = eng._chunk_args(
        np.zeros((4, 1), np.int32), np.ones((4,), np.int32), eng.tables,
        np.zeros((4,), np.float32), np.ones((4,), np.float32),
        np.zeros((4,), np.int32), np.zeros((4,), np.int32))
    decode = eng._fns["decode"].lower(*args, sampling=False).as_text(
        debug_info=True)
    args = eng._chunk_args(
        np.zeros((1, 16), np.int32), np.full((1,), 16, np.int32),
        eng.tables[:1], np.zeros((1,), np.float32),
        np.ones((1,), np.float32), np.zeros((1,), np.int32),
        np.zeros((1,), np.int32)) + (jnp.zeros((1,), jnp.int32),)
    prefill = eng._fns["prefill"].lower(*args, sampling=False).as_text(
        debug_info=True)
    for text in (decode, prefill):
        for scope in ("embed", "attn.window", "attn.full", "mlp", "moe",
                      "moe/moe.router", "moe/moe.experts", "moe/moe.shared",
                      "lm_head", "attn.window/paged_attention",
                      "attn.full/paged_attention"):
            assert f"/{scope}/" in text, scope
