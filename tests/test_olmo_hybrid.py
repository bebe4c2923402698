"""Olmo-Hybrid (gated delta-rule linear attention + full attention) against
its plain reference, ``benchmark/reference/olmo_hybrid.py``, on seeded
weights at tiny widths (``benchmark/tests/tiny_olmo_hybrid.py``: hidden 96,
six heads, a state of 8 x 64 a head packed two to a row, two periods
``L L L F``).

Everything runs in float32 on the CPU, the reference at ``highest``
precision, so the tolerances below are those of float32 sums taken in another
order (the chunked rule's triangular solve against the token loop, blockwise
softmax against a masked one), not of a lower precision:

* ``TIGHT`` 2e-5 absolute on values of order 1: one mixer, a few hundred
  float32 additions reordered;
* ``LOGITS`` 1e-3 absolute on logits of order 1-4: eight POST-norm blocks
  deep. A post-norm block renormalises each mixer's output, so a rounding
  difference is carried at full size from block to block (one block reads
  2e-6, four 5e-5, eight 2e-4: measured). A state that is dropped at a cut
  moves the logits by more than 1e-1
  (``test_dropping_the_carried_state_shows``).
"""
from __future__ import annotations

import dataclasses
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
from benchmark.drivers import serve_olmo_hybrid as driver  # noqa: E402
from benchmark.lib import weights_olmo_hybrid as weights_lib  # noqa: E402
from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from benchmark.tests.tiny_olmo_hybrid import CFG as TINY  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models import (OlmoHybridConfig,  # noqa: E402
                               OlmoHybridForCausalLM, olmo_hybrid_tiny)
from paddle_tpu.nn.functional import delta_rule as dr  # noqa: E402

import served  # noqa: E402
from served import close, models, rec, recording, traced  # noqa: E402,F401

SEED = 5
TIGHT = 2e-5
LOGITS = 1e-3
#: N(0, 0.1) matrices: logits of order 1-4, so a difference shows
CFG = dict(TINY, initializer_range=0.1)
LINEAR, FULL = "linear_attention", "full_attention"
#: chunks of 32 (the engine's widest for four lanes of 8-token blocks)
CASE = served.Case(
    "linear", CFG, SEED, budget=None, atol=LOGITS,
    reference=lambda ids: ref.logits(CFG, SEED, ids[None])[0])


def f32_weights(cfg, seed, layers=None):
    return served.f32_weights(weights_lib, cfg, seed, layers)


@pytest.fixture(scope="module")
def model():
    return served.model_of(CASE)


def ids_of(rows, tokens, seed=0):
    return np.random.RandomState(seed).randint(
        1, CFG["vocab_size"], (rows, tokens)).astype(np.int32)


# ============================================================ configuration
def test_tiny_preset_is_the_test_configuration():
    tiny, cfg = olmo_hybrid_tiny(), driver.model_config(TINY)
    for key in ("vocab_size hidden_size intermediate_size num_hidden_layers "
                "num_attention_heads linear_key_head_dim "
                "linear_value_head_dim layer_types chunk_size").split():
        assert getattr(tiny, key) == getattr(cfg, key), key
    assert tiny.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 2
    assert tiny.head_dim == 16 and tiny.conv_dim == 2 * 48 + 384
    assert tiny.heads_packed == 2


def test_published_defaults_are_the_published_config():
    cfg = OlmoHybridConfig()
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 8
    assert (cfg.head_dim, cfg.key_dim, cfg.value_dim, cfg.conv_dim) == (
        128, 2880, 5760, 11520)
    assert cfg.heads_packed == 2


@pytest.mark.parametrize("kw,named", [
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"layer_types": ("sliding_attention",) * 8}, "unknown layer kinds"),
    ({"layer_types": (LINEAR,) * 3}, "layer_types"),
    ({"linear_num_key_heads": 3}, "linear_num_key_heads"),
])
def test_what_is_not_built_is_refused_by_name(kw, named):
    with pytest.raises(ValueError, match=named):
        olmo_hybrid_tiny(**kw)


# ================================================================ the mixer
def layer_weights(layer, cfg=CFG):
    return {name: a for (_l, name), a in
            f32_weights(cfg, SEED, layers=[layer]).items()}


def mixer_ref(x, layer=0):
    with jax.default_matmul_precision("highest"):
        return ref.mixer(CFG, CFG["layer_types"][layer], x,
                         layer_weights(layer))


@pytest.mark.parametrize("layer", [0, 3], ids=["linear", "full"])
@pytest.mark.parametrize("tokens", [1, 7, 8, 19, 40])
def test_a_mixer_is_the_references(model, layer, tokens):
    x = jnp.asarray(np.random.RandomState(tokens).randn(2, tokens, 96),
                    jnp.float32)
    got = traced(model.model.layers[layer].mixer, Tensor(x))
    close(got._data, mixer_ref(x, layer), TIGHT)


@pytest.mark.parametrize("cuts", [(11,), (8, 16), (1, 2, 3), (5, 6, 30)])
def test_a_chunk_continues_from_the_carried_state(model, cuts):
    """The linear mixer fed in pieces (a one-token piece takes the step, a
    longer one the chunked form) is the mixer over the whole sequence."""
    mixer = model.model.layers[0].mixer
    x = jnp.asarray(np.random.RandomState(1).randn(2, 40, 96), jnp.float32)
    state = mixer.zero_state(2, jnp.float32)
    parts = []
    for lo, hi in zip((0,) + cuts, cuts + (40,)):
        out, state = traced(mixer, Tensor(x[:, lo:hi]), state=state)
        parts.append(out._data)
    close(jnp.concatenate(parts, axis=1), mixer_ref(x), TIGHT)


def test_dropping_the_carried_state_shows(model):
    mixer = model.model.layers[0].mixer
    x = jnp.asarray(np.random.RandomState(2).randn(1, 24, 96), jnp.float32)
    zero = mixer.zero_state(1, jnp.float32)
    _first, _state = traced(mixer, Tensor(x[:, :12]), state=zero)
    second, _ = traced(mixer, Tensor(x[:, 12:]), state=zero)  # state dropped
    assert np.abs(np.asarray(second._data)
                  - np.asarray(mixer_ref(x))[:, 12:]).max() > 1e-2


@pytest.mark.parametrize("pad", [1, 5, 13])
def test_left_padding_of_a_first_chunk_is_not_seen(model, pad):
    mixer = model.model.layers[0].mixer
    x = jnp.asarray(np.random.RandomState(3).randn(1, 11, 96), jnp.float32)
    padded = jnp.concatenate([jnp.ones((1, pad, 96)) * 7.0, x], axis=1)
    valid = jnp.arange(pad + 11)[None, :] >= pad
    out, state = traced(mixer, Tensor(padded), state=mixer.zero_state(
        1, jnp.float32), valid=Tensor(valid))
    want, want_state = traced(mixer, Tensor(x), state=mixer.zero_state(
        1, jnp.float32))
    close(out._data[:, pad:], want._data, TIGHT)
    close(state["s"], want_state["s"], TIGHT)
    close(state["conv"], want_state["conv"], TIGHT)


# ============================================================ whole forward
@pytest.mark.parametrize("tokens", [9, 50])
def test_whole_sequence_forward_is_the_reference(model, tokens):
    ids = ids_of(2, tokens, seed=tokens)
    got = traced(model, paddle.to_tensor(ids))._data
    want = ref.logits(CFG, SEED, ids)
    assert float(jnp.abs(want).max()) > 1.0
    close(got, want, LOGITS)


def test_int8_control_is_another_function():
    ids = ids_of(1, 30, seed=1)
    sound = ref.logits(CFG, SEED, ids)
    low = ref.logits(CFG, SEED, ids, "int8")
    assert float(jnp.abs(sound - low).max()) > 10 * LOGITS


# ====================================================== through the engine
@pytest.mark.parametrize("front", ["engine", "router", "serial"])
def test_served_logits_are_the_references(rec, front):
    """Prefill in one to three chunks of 32 (left-padded first chunk), then
    decode through the cache, four requests sharing the batch, chunks riding
    decode steps as one program where a tick holds both: every logits row
    the programs sampled from against the reference's full forward over
    prompt + served tokens. ``serial``: the schedule without the overlap
    and without the mixed step, the same rows."""
    eng, _prompts, _ = served.served_logits_are_the_references(
        CASE, rec, front, width=32, new=6)
    mixed = eng.health()["mixed_share"]
    assert (mixed == 0) if front == "serial" else (mixed > 0)


def test_mixed_and_serial_schedules_serve_the_same_tokens():
    prompts = served.prompts_of(CASE, (9, 50, 33, 70, 20, 41), seed=6)
    tokens = []
    for overlap in (True, False):
        eng = served.engine(CASE, budget=16, serial=not overlap)
        rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        out = eng.run_to_completion()
        tokens.append([out[r] for r in rids])
        assert (eng.health()["mixed_share"] > 0) == overlap
    assert tokens[0] == tokens[1]


def test_a_reused_slot_starts_from_zero_state(rec):
    served.a_reused_slot_starts_clean(CASE, rec, (20, 13))


def test_a_lane_mid_prefill_keeps_its_state_while_others_decode(rec):
    served.a_lane_mid_prefill_keeps_what_it_holds(CASE, rec)


def test_a_memory_stalled_lane_keeps_its_state(rec):
    served.a_memory_stalled_lane_keeps_what_it_holds(CASE, rec)


def test_a_preempted_request_re_prefills_to_the_same_tokens(rec):
    """The matrix state needs no free and no snapshot: the re-prefill
    recomputes it, through the chunked form where the first pass went
    through the step. The tokens are those of an engine with room."""
    prompts, tokens = served.evict_then_readmit_reproduces_the_logits(
        CASE, rec, usable=4, length=4, new=14, max_ticks=300)
    roomy = served.engine(CASE)
    again = [roomy.add_request(x, max_new_tokens=14) for x in prompts]
    out = roomy.run_to_completion()
    assert tokens == [out[r] for r in again]


def test_speculate_raises_naming_slot_state():
    with pytest.raises(TypeError, match="slot_state"):
        served.engine(CASE, speculate="ngram")


def test_layout_and_health_count_the_matrix_state(model):
    adapter = model.paged_adapter()
    layout = adapter.cache_layout(jnp.float32)
    kinds = [entry[0] for entry in layout]
    assert kinds == ["slot_state"] * 3 + ["paged_kv"] \
        + ["slot_state"] * 3 + ["paged_kv"]
    assert layout[0][1] == {"conv": ((3, 480), jnp.float32),
                            "s": ((3, 8, 128), jnp.float32)}
    # 6 K/V heads are padded to a whole tile of 16 in the pages
    assert adapter.num_kv_heads == 16 and adapter.pad_heads == 10
    eng = served.engine(CASE)
    h = eng.health()
    per_layer = (3 * 480 + 6 * 8 * 64) * 4
    assert h["state_bytes_per_slot"] == 6 * per_layer \
        == eng.state_bytes_per_slot
    assert h["kv_bytes_per_token"] == 2 * 2 * 16 * 16 * 4
    assert len(eng.kc) == len(eng.vc) == 2 and len(eng.state) == 6
    paddle.set_flags({"FLAGS_enable_metrics": True})
    try:
        served.engine(CASE)
        from paddle_tpu.inference import resilience
        assert resilience.M_STATE_BYTES.value() == 4 * 6 * per_layer
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})


def test_a_group_query_model_keeps_its_kv_heads():
    """Only a group of one is padded: with fewer K/V heads than query heads
    a pad K/V head would shift which query head reads which."""
    m = OlmoHybridForCausalLM(olmo_hybrid_tiny(num_key_value_heads=2))
    adapter = m.paged_adapter()
    assert adapter.num_kv_heads == 2 and adapter.pad_heads == 0
    eng = served.engine(CASE, model=m)
    rid = eng.add_request(served.prompts_of(CASE, (11,))[0],
                          max_new_tokens=4)
    assert len(eng.run_to_completion()[rid]) == 4


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["composite", "interpreted-kernels"])
def test_an_idle_lane_is_untouched_and_a_fresh_slot_zero_at_128_lanes(
        monkeypatch, kernels):
    """``max_batch`` 128, as the benchmark's cell runs (no other cell runs
    more than 64), every slot's state dirty as a long-lived engine's are. A
    budget of 8 prompt tokens a tick keeps the 45-token prompt mid-way for
    six ticks while the others decode: its lane rides those steps under the
    ``seq = 0`` sentinel and must get its state back as it was; a slot
    handed to a new request must start from zeros whatever the last tenant
    left. The served tokens are those of a clean four-lane engine. With the
    kernels interpreted the decode step's rule and its attention are the
    Pallas kernels' own code."""
    from paddle_tpu.ops.pallas import delta_rule as rule_kernel
    from paddle_tpu.ops.pallas import paged_attention as attn_kernel
    if kernels:
        monkeypatch.setattr(rule_kernel, "INTERPRET", True)
        monkeypatch.setattr(attn_kernel, "INTERPRET", True)
    # two attention heads of 128: the decode-attention kernel takes heads
    # that are whole lane tiles (the tiny preset's 16 go to the composite);
    # one layer of each kind
    cfg = dict(CFG, hidden_size=256, num_attention_heads=2,
               num_key_value_heads=2, num_hidden_layers=2,
               layer_types=[LINEAR, FULL])
    # a model of its own a case (the kernels' switch is read by the trace),
    # under both of the case's engines
    m = served.build(dataclasses.replace(CASE, cfg=cfg))
    prompts = served.prompts_of(CASE, (6, 45, 20), seed=8)
    tokens = []
    for lanes in (128, 4):
        eng = served.engine(CASE, model=m, max_batch=lanes, budget=8)
        if lanes == 128:
            eng.state = [{k: jnp.full_like(v, 3.0) for k, v in st.items()}
                         for st in eng.state]
        rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        overlapped, out = 0, {}
        while eng.has_work():
            overlapped += bool(eng._prefilling and eng._decode_lanes())
            out.update(eng.step())
        assert overlapped >= 3
        tokens.append([out[r] for r in rids])
        if kernels:
            assert eng.health()["decode_attention"] == "kernel"
    assert tokens[0] == tokens[1]


def test_decode_step_takes_the_kernels_where_they_run(monkeypatch):
    from paddle_tpu.ops.pallas import delta_rule as rule_kernel
    assert not dr.use_step_kernel((4, 3, 8, 128), 8, 2)     # a CPU, no switch
    monkeypatch.setattr(rule_kernel, "INTERPRET", True)
    assert dr.use_step_kernel((4, 3, 8, 128), 8, 2)
    paddle.set_flags({"FLAGS_use_pallas_kernels": False})
    try:
        assert not dr.use_step_kernel((4, 3, 8, 128), 8, 2)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})


@pytest.mark.parametrize("tokens", [1, 5])
def test_a_lane_that_is_fresh_and_idle_is_kept_not_zeroed(model, tokens):
    """The ``seq = 0`` sentinel lane reads as both (its start is -1): in the
    step and in the chunked form alike its window and its matrix come back
    as they were."""
    mixer = model.model.layers[0].mixer
    rng = np.random.RandomState(4)
    state = {k: jnp.asarray(rng.randn(*v.shape), v.dtype)
             for k, v in mixer.zero_state(2, jnp.float32).items()}
    qkv, alpha_log, beta, _gate = traced(
        mixer.project,
        Tensor(jnp.asarray(rng.randn(2, tokens, 96), jnp.float32)))
    valid = jnp.asarray([[False] * tokens, [True] * tokens])
    flags = jnp.asarray([True, False])
    _o, new = traced(mixer.scan, state, qkv._data, alpha_log._data,
                     beta._data, valid, fresh=flags, idle=flags)
    for name in ("conv", "s"):
        assert np.array_equal(np.asarray(new[name][0]),
                              np.asarray(state[name][0])), name
        assert not np.array_equal(np.asarray(new[name][1]),
                                  np.asarray(state[name][1])), name
