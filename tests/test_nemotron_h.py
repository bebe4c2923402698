"""Nemotron-H (Mamba-2 + attention + latent experts) against its plain
reference, ``benchmark/reference/nemotron_h.py``, on seeded weights at tiny
widths (``benchmark/tests/tiny_hybrid.py``: hidden 64, 8 Mamba heads of 8,
state 16, 2 groups, 16 experts top-4 in a latent of 32, pattern ``MEM*E``).

Everything runs in float32 on the CPU, the reference at ``highest``
precision, so the tolerances below are those of float32 sums taken in another
order (the chunked scan against the time-step recurrence, a batched expert
product against a loop), not of a lower precision:

* ``TIGHT`` 2e-5 absolute on values of order 1: one mixer or one layer, a
  few hundred float32 additions reordered;
* ``LOGITS`` 5e-4 absolute on logits of order 1: five blocks deep, through
  the paged cache, the same reordering compounded. A state that is dropped
  at a cut moves a mixer's output by more than 1e-2, a thousand times
  ``TIGHT`` (``test_dropping_the_carried_state_shows``).
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
from benchmark.lib import weights_nemotron_h as weights_lib  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402
from benchmark.tests.tiny_hybrid import NEMOTRON  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import serving  # noqa: E402

import served  # noqa: E402
from served import (close, models, rand, rec, recording,  # noqa: E402,F401
                    traced)

SEED = 5
TIGHT = 2e-5
LOGITS = 5e-4
CFG = NEMOTRON
#: chunks of 32 (the engine's widest for four lanes of 8-token blocks)
CASE = served.Case(
    "hybrid", CFG, SEED, budget=None, atol=LOGITS,
    reference=lambda ids: ref.logits(CFG, SEED, ids[None])[0])


def f32_weights(cfg, seed, layers=None):
    return served.f32_weights(weights_lib, cfg, seed, layers)


def layer_weights(layer):
    return {name: a for (_l, name), a in
            f32_weights(CFG, SEED, layers=[layer]).items()}


@pytest.fixture(scope="module")
def model():
    return served.model_of(CASE)


# ================================================================ Mamba-2
def mamba_ref(u, layer=0):
    with jax.default_matmul_precision("highest"):
        return ref.mixer(CFG, "M", u, layer_weights(layer))


def zero_state(mixer, batch):
    c = mixer.cfg
    return (Tensor(jnp.zeros((batch, c.conv_kernel - 1, c.conv_dim))),
            Tensor(jnp.zeros((batch, c.mamba_num_heads, c.mamba_head_dim,
                              c.ssm_state_size))))


@pytest.mark.parametrize("tokens", [1, 7, 8, 19, 32])
def test_chunked_scan_is_the_time_step_recurrence(model, tokens):
    """The mixer's whole-sequence path (chunks of 8, the last one padded
    inside the scan) against the reference's ``lax.scan`` over time."""
    mixer = model.model.layers[0].mixer
    u = rand((2, tokens, 64), tokens)
    close(traced(mixer, Tensor(u))._data, mamba_ref(u), TIGHT)


@pytest.mark.parametrize("cuts", [(11,), (8, 16), (1, 2, 3), (5, 6, 18)])
def test_a_chunk_continues_from_the_carried_state(model, cuts):
    """Fed in pieces with the (window, state) handed on, the mixer gives
    what it gives in one piece; a one-token piece takes the recurrence
    itself, so this is also the one-token update against the scan."""
    mixer = model.model.layers[2].mixer
    u = rand((2, 19, 64), 7)
    state, outs, lo = zero_state(mixer, 2), [], 0
    for hi in cuts + (19,):
        out, state = traced(mixer, Tensor(u[:, lo:hi]), state=state)
        outs.append(out._data)
        lo = hi
    close(jnp.concatenate(outs, axis=1), mamba_ref(u, layer=2), TIGHT)


def test_dropping_the_carried_state_shows(model):
    """The tolerance is tight enough to see the state: the same pieces
    with the second one started from zeros miss the reference by far."""
    mixer = model.model.layers[2].mixer
    u = rand((2, 19, 64), 7)
    out, _s = traced(mixer, Tensor(u[:, 11:]), state=zero_state(mixer, 2))
    miss = jnp.abs(out._data - mamba_ref(u, layer=2)[:, 11:]).max()
    assert float(miss) > 1e-2 > 100 * TIGHT


@pytest.mark.parametrize("pad", [1, 5, 13])
def test_left_padding_of_a_first_chunk_is_not_seen(model, pad):
    """A first chunk whose rows 0..pad-1 are padding (``valid`` false): the
    real rows come out as if the padding were not there, and the state
    handed on is the state after the real rows alone."""
    mixer = model.model.layers[0].mixer
    u = rand((1, 16, 64), pad, scale=2.0)      # the padding rows are NOT 0
    valid = Tensor(jnp.arange(16)[None, :] >= pad)
    out, state = traced(mixer, Tensor(u), state=zero_state(mixer, 1),
                        valid=valid)
    close(out._data[:, pad:], mamba_ref(u[:, pad:]), TIGHT)
    _o, alone = traced(mixer, Tensor(u[:, pad:]),
                       state=zero_state(mixer, 1))
    close(state[0]._data, alone[0]._data, 0)        # window: copies
    close(state[1]._data, alone[1]._data, TIGHT)


def test_one_token_update_is_the_scans_next_step(model):
    mixer = model.model.layers[0].mixer
    u = rand((3, 13, 64), 3)
    _o, before = traced(mixer, Tensor(u[:, :12]),
                        state=zero_state(mixer, 3))
    step_out, step_state = traced(mixer, Tensor(u[:, 12:]), state=before)
    scan_out, scan_state = traced(mixer, Tensor(u),
                                  state=zero_state(mixer, 3))
    close(step_out._data[:, 0], scan_out._data[:, 12], TIGHT)
    close(step_state[1]._data, scan_state[1]._data, TIGHT)
    close(step_state[0]._data, scan_state[0]._data, 0)


# ========================================================== latent experts
def uncut_cfg():
    cfg = copy.deepcopy(CFG)
    cfg["n_routed_experts"], cfg["experts_held"] = 16, [0, 16]
    return cfg


def moe_layer(cfg, lw, held):
    lo, hi = held
    layer = nn.LatentMoE(
        cfg["hidden_size"], cfg["moe_latent_size"],
        cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"], cfg["router_width"],
        cfg["num_experts_per_tok"], experts_held=held,
        routed_scale=cfg["routed_scaling_factor"])
    put = {"gate_weight": lw["gate"],
           "e_score_correction_bias": lw["e_score_correction_bias"],
           "latent_down.weight": lw["latent_down"],
           "latent_up.weight": lw["latent_up"],
           "w1": lw["w1"][lo:hi], "w2": lw["w2"][lo:hi],
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


def outside_token(lw, held):
    """An input whose four best experts all lie outside ``held``: pushed
    along the router columns of four experts outside it, away from the
    held ones."""
    lo, hi = held
    others = [e for e in range(16) if not lo <= e < hi][:4]
    g = lw["gate"]
    return 6.0 * (g[:, others].sum(1) - g[:, lo:hi].sum(1))


@pytest.mark.parametrize("held", [(0, 4), (4, 8), (8, 12), (12, 16),
                                  (0, 16), (2, 11)])
def test_expert_layer_is_the_per_token_loop(uncut, held):
    """The layer's held-experts product against a loop over tokens and
    their chosen experts in numpy, one token chosen so that its whole
    selection lands outside the held range (its routed part is exactly
    0), and the load counters against the same loop."""
    cfg, lw = uncut
    layer = moe_layer(cfg, lw, held)
    u = rand((11, 64), 17)
    u = u.at[3].set(outside_token(lw, held)) if held != (0, 16) else u
    out, load = traced(layer, Tensor(u), with_load=True)
    w = {k: np.asarray(v, np.float64) for k, v in lw.items()}
    un = np.asarray(u, np.float64)
    lo, hi = held
    want = np.zeros_like(un)
    counts = np.zeros(hi - lo + 2, np.int64)
    relu2 = lambda x: np.square(np.maximum(x, 0.0))  # noqa: E731
    for t in range(11):
        s = 1.0 / (1.0 + np.exp(-(un[t] @ w["gate"])))
        top = np.argsort(-(s + w["e_score_correction_bias"]))[:4]
        latent = un[t] @ w["latent_down"]
        routed = np.zeros(32)
        for e in top:
            counts[-1] += 1
            if lo <= e < hi:
                counts[e - lo] += 1
                counts[-2] += 1
                routed += (5.0 * s[e] / (s[top].sum() + 1e-20)
                           * (relu2(latent @ w["w1"][e]) @ w["w2"][e]))
        want[t] = routed @ w["latent_up"] \
            + relu2(un[t] @ w["shared_up"]) @ w["shared_down"]
        if t == 3 and held != (0, 16):
            assert not any(lo <= e < hi for e in top)
    close(out._data, want, TIGHT)
    assert np.asarray(load._data).tolist() == counts.tolist()


def test_shares_add_up_to_the_uncut_layer(uncut):
    """THE SHARE TEST: the routed parts of all four shares (experts 0-3,
    4-7, 8-11, 12-15), with what every chip computes alike (the shared
    expert) counted once, are the uncut reference layer; and each share is
    the reference's own share."""
    cfg, lw = uncut
    u = rand((2, 9, 64), 23)
    with jax.default_matmul_precision("highest"):
        whole = ref.mixer(cfg, "E", u, lw)
    total = None
    for lo in (0, 4, 8, 12):
        layer = moe_layer(cfg, lw, (lo, lo + 4))
        flat = Tensor(u.reshape(-1, 64))
        shared = traced(lambda x: layer.shared_down(
            nn.layer.latent_moe.relu2(layer.shared_up(x))), flat)._data
        out = traced(layer, Tensor(u))._data
        part = dict(lw, w1=lw["w1"][lo:lo + 4], w2=lw["w2"][lo:lo + 4])
        with jax.default_matmul_precision("highest"):
            close(out, ref.mixer(dict(cfg, experts_held=[lo, lo + 4]), "E",
                                 u, part), TIGHT)
        routed = out.reshape(-1, 64) - shared
        total = routed if total is None else total + routed
        last_shared = shared
    close((total + last_shared).reshape(u.shape), whole, 4 * TIGHT)


# ====================================================== the whole model
@pytest.mark.parametrize("tokens", [9, 33])
def test_whole_sequence_forward_is_the_reference(model, tokens):
    """``forward(ids)``, what a trainer or an offline scorer calls."""
    ids = np.random.RandomState(tokens).randint(1, CFG["vocab_size"],
                                                (2, tokens)).astype(np.int32)
    close(traced(model, paddle.to_tensor(ids))._data,
          ref.logits(CFG, SEED, ids), LOGITS)


def test_whole_sequence_forward_is_differentiable():
    """Eager autograd reaches every parameter through the scan, the
    convolution, the gated norm and the expert product (the router's
    correction bias only steers a choice: its gradient is zero). Twelve
    tokens: a whole chunk of 8 and a padded one."""
    served.whole_sequence_forward_is_differentiable(
        CASE, np.random.RandomState(1).randint(
            1, CFG["vocab_size"], (2, 12)).astype(np.int32))


# ====================================================== through the engine
@pytest.mark.parametrize("front", ["engine", "router"])
def test_served_logits_are_the_references(rec, front):
    """Prefill in one to three chunks of 32 (left-padded first chunk),
    then decode through the cache, four requests sharing the batch: every
    logits row the programs sampled from against the reference's full
    forward over prompt + served tokens."""
    eng, prompts, before = served.served_logits_are_the_references(
        CASE, rec, front, width=32, new=6,
        probe=lambda eng: eng.expert_load()["pairs_selected"])
    rows = sum(len(p) + 6 - 1 for p in prompts)
    load = eng.expert_load()
    # padding rows and sentinel lanes are not counted: every real row
    # selects top-4 in each of the two expert layers, no more
    assert [n - b for n, b in zip(load["pairs_selected"], before)] \
        == [4 * rows, 4 * rows]
    assert all(0 < h < s for h, s in zip(load["pairs_held"],
                                         load["pairs_selected"]))
    assert [sum(t) for t in load["tokens"]] == load["pairs_held"]


def test_a_reused_slot_starts_from_zero_state(rec):
    served.a_reused_slot_starts_clean(CASE, rec, (20, 13))


def test_a_lane_mid_prefill_keeps_its_state_while_others_decode(rec):
    served.a_lane_mid_prefill_keeps_what_it_holds(CASE, rec)


def test_a_memory_stalled_lane_keeps_its_state(rec):
    served.a_memory_stalled_lane_keeps_what_it_holds(CASE, rec)


def test_evict_then_readmit_reproduces_the_logits(rec):
    """The state needs no free and no snapshot: the re-prefill recomputes
    it."""
    served.evict_then_readmit_reproduces_the_logits(
        CASE, rec, usable=4, length=4, new=14, max_ticks=300)


def test_int8_kv_pages_serve_the_attention_layer(rec):
    """``kv_dtype="int8"`` quantises the one attention layer's K/V pages
    (one part in 127 of each head's largest value); the recurrent state is
    untouched. Tolerance 0.05 on logits of order 1: the int8 rounding of
    K and V through one attention layer of five blocks."""
    eng = served.engine(CASE, kv_dtype="int8")
    (p,) = served.prompts_of(CASE, (37,), seed=5)
    a = eng.add_request(p, max_new_tokens=5)
    out = eng.run_to_completion()
    served.check_served(CASE, rec, out, (p,), (a,), atol=0.05)
    assert eng.health()["kv_dtype"] == "int8"


def test_speculate_needs_a_state_rollback():
    with pytest.raises(TypeError, match="state rollback"):
        served.engine(CASE, speculate="ngram")


def test_health_counts_each_kind_of_state():
    eng = served.engine(CASE)
    h = eng.health()
    # one attention layer of five: K and V, 2 KV heads of 16, float32
    assert h["kv_bytes_per_token"] == 2 * 2 * 16 * 4 == eng.kv_bytes_per_token
    # two Mamba layers: a 3 x 128 window and an 8 x 8 x 16 state, float32
    assert h["state_bytes_per_slot"] == 2 * (3 * 128 + 8 * 8 * 16) * 4
    assert h["expert_load"]["layers"] == [1, 4]
    assert len(eng.kc) == len(eng.vc) == 1 and len(eng.state) == 4


# ============================================================ cache handle
@pytest.mark.parametrize("lanes", [None, [2, 0]])
def test_cache_handle_keeps_resets_and_updates(lanes):
    """``recur``: a lane with no real row gets its state back bit for bit,
    a lane whose chunk starts a sequence starts from zeros, any other goes
    on from its state; with ``lanes`` only those slots are touched."""
    state = {"s": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) + 1.0}
    rows = 3 if lanes is None else 2
    start = jnp.asarray([-1, 0, 5][:rows], jnp.int32)   # idle, first, later
    cache = serving._PagedCache(
        {0: ("slot_state", 0)}, [], [], [dict(state)],
        jnp.zeros((rows, 1), jnp.int32), start + 1, start,
        None if lanes is None else jnp.asarray(lanes, jnp.int32), 1)
    seen = {}

    def fn(st):
        seen["in"] = st["s"]
        return jnp.zeros(()), {"s": st["s"] + 100.0}

    cache.recur(0, fn)
    got = np.asarray(cache.states[0]["s"])
    before = np.asarray(state["s"])
    slots = list(range(3)) if lanes is None else lanes
    assert (got[slots[0]] == before[slots[0]]).all()            # kept
    assert (np.asarray(seen["in"])[1] == 0).all()               # reset
    assert (got[slots[1]] == 100.0).all()
    if lanes is None:
        assert (got[2] == before[2] + 100.0).all()              # continued
    else:
        assert (got[1] == before[1]).all()                      # not a lane
