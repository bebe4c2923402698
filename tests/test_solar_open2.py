"""Solar Open 2 (Kimi delta attention + gated NoPE grouped-query attention,
a SwiGLU expert layer in every block) against its plain reference,
``benchmark/reference/solar_open2.py``, on seeded weights at tiny widths
(``benchmark/tests/tiny_solar_open2.py``: hidden 64, four linear heads of 16
x 16 behind three convolutions, four query heads over two K/V heads, one
period ``G K K K``, 4 of 16 experts held).

Everything runs in float32 on the CPU, the reference at ``highest``
precision, so the tolerances below are those of float32 sums taken in another
order (the chunked rule's pair terms and block inverse against the token
scan, blockwise softmax against a masked one, the masked expert product
against a loop over experts), not of a lower precision:

* ``TIGHT`` 2e-5 absolute on values of order 1: one mixer;
* ``LOGITS`` 2e-4 absolute on logits of order 1-4: four PRE-norm blocks (a
  rounding difference rides the residual, it is not renormalised to full
  size a block as in Olmo-Hybrid's post-norm). A state that is dropped at a
  cut moves a mixer's output by more than 1e-2
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
from benchmark.drivers import serve_solar_open2 as driver  # noqa: E402
from benchmark.lib import weights_solar_open2 as weights_lib  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from benchmark.tests.tiny_solar_open2 import CFG, KERNEL  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models import (SolarOpen2Config,  # noqa: E402
                               solar_open2_tiny)
from paddle_tpu.nn.functional import delta_rule as dr  # noqa: E402

import served  # noqa: E402
from served import close, models, rec, recording, traced  # noqa: E402,F401

SEED = 5
TIGHT = 2e-5
LOGITS = 2e-4
#: the reference's rows at once: sequences here are tens of tokens
BLOCK = 32
#: chunks of 32 (the engine's widest for four lanes of 8-token blocks)
CASE = served.Case(
    "kda", CFG, SEED, budget=None, atol=LOGITS,
    reference=lambda ids: ref.logits(CFG, SEED, ids, block=BLOCK))


@pytest.fixture(scope="module")
def model():
    return served.model_of(CASE)


def layer_weights(layer, cfg=CFG, seed=SEED):
    return {name: a for (_l, name), a in served.f32_weights(
        weights_lib, cfg, seed, layers=[layer]).items()}


def mixer_ref(x, layer):
    """The reference's mixer and ``W_o`` on the block's input, a sequence at
    a time (the reference takes ONE sequence)."""
    lw = layer_weights(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.mixer(CFG, layer, row, lw, BLOCK) @ lw["o"]
                          for row in x])


def block_mixer(model, layer):
    blk = model.model.layers[layer]
    return lambda x, **kw: blk.mixer(blk.input_layernorm(x), **kw)


# ============================================================ configuration
def test_tiny_preset_is_the_test_configuration():
    tiny, cfg = solar_open2_tiny(), driver.model_config(CFG)
    for key in ("hidden_size num_hidden_layers num_attention_heads "
                "num_key_value_heads head_dim gqa_layers linear_attn_config "
                "num_experts_per_tok moe_intermediate_size "
                "chunk_size").split():
        assert getattr(tiny, key) == getattr(cfg, key), key
    assert cfg.gqa_layers == (0,) and cfg.n_routed_experts == 16
    assert cfg.experts_held == (0, 4)
    assert (cfg.linear_heads, cfg.linear_head_dim, cfg.linear_dim,
            cfg.conv_taps) == (4, 16, 64, 4)


def test_published_defaults_are_the_published_config():
    cfg = SolarOpen2Config()
    assert cfg.gqa_layers == tuple(range(0, 48, 4))
    assert (cfg.linear_heads, cfg.linear_head_dim, cfg.linear_dim) == (
        64, 128, 8192)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.n_shared_experts) == (
        320, 8, 1280, 1)
    assert cfg.experts_held == (0, 320) and cfg.chunk_size == 64


@pytest.mark.parametrize("kw,named", [
    ({"use_rope": True}, "use_rope"),
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"use_gqa_gate": False}, "use_gqa_gate"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"gqa_layers": (0, 9)}, "gqa_layers"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                             "num_heads": 4, "num_kv_heads": 2}},
     "num_kv_heads"),
])
def test_what_is_not_built_is_refused_by_name(kw, named):
    with pytest.raises(ValueError, match=named):
        solar_open2_tiny(**kw)


# ================================================================ the mixers
@pytest.mark.parametrize("layer", [0, 1], ids=["gqa", "kda"])
@pytest.mark.parametrize("tokens", [1, 7, 8, 19, 40])
def test_a_mixer_is_the_references(model, layer, tokens):
    x = jnp.asarray(np.random.RandomState(tokens).randn(2, tokens, 64),
                    jnp.float32)
    got = traced(block_mixer(model, layer), Tensor(x))
    close(got._data, mixer_ref(x, layer), TIGHT)


@pytest.mark.parametrize("cuts", [(11,), (8, 16), (1, 2, 3), (5, 6, 30)])
def test_a_chunk_continues_from_the_carried_state(model, cuts):
    """The KDA mixer fed in pieces (a one-token piece takes the step, a
    longer one the chunked form) is the mixer over the whole sequence."""
    mixer = model.model.layers[1].mixer
    x = jnp.asarray(np.random.RandomState(1).randn(2, 40, 64), jnp.float32)
    state = mixer.zero_state(2, jnp.float32)
    parts = []
    for lo, hi in zip((0,) + cuts, cuts + (40,)):
        out, state = traced(block_mixer(model, 1), Tensor(x[:, lo:hi]),
                            state=state)
        parts.append(out._data)
    close(jnp.concatenate(parts, axis=1), mixer_ref(x, 1), TIGHT)


def test_dropping_the_carried_state_shows(model):
    mixer = model.model.layers[1].mixer
    x = jnp.asarray(np.random.RandomState(2).randn(1, 24, 64), jnp.float32)
    zero = mixer.zero_state(1, jnp.float32)
    second, _ = traced(block_mixer(model, 1), Tensor(x[:, 12:]), state=zero)
    assert np.abs(np.asarray(second._data)
                  - np.asarray(mixer_ref(x, 1))[:, 12:]).max() > 1e-2


@pytest.mark.parametrize("pad", [1, 5, 13])
def test_left_padding_of_a_first_chunk_is_not_seen(model, pad):
    mixer = model.model.layers[2].mixer
    x = jnp.asarray(np.random.RandomState(3).randn(1, 11, 64), jnp.float32)
    padded = jnp.concatenate([jnp.ones((1, pad, 64)) * 7.0, x], axis=1)
    valid = jnp.arange(pad + 11)[None, :] >= pad
    zero = mixer.zero_state(1, jnp.float32)
    out, state = traced(mixer, Tensor(padded), state=zero,
                        valid=Tensor(valid))
    want, want_state = traced(mixer, Tensor(x), state=zero)
    close(out._data[:, pad:], want._data, TIGHT)
    assert sorted(state) == ["conv_k", "conv_q", "conv_v", "s"]
    for name in state:
        close(state[name], want_state[name], TIGHT)


def test_the_decays_spread_over_the_unit_interval(model):
    """The seeded time constants (``assumed`` in the configuration's file):
    a token's decays a channel cover (0, 1), not one corner of it."""
    mixer = model.model.layers[1].mixer
    x = Tensor(jnp.asarray(np.random.RandomState(4).randn(1, 64, 64),
                           jnp.float32))
    _q, _k, _v, alpha_log, beta, _gate = traced(mixer.project, x)
    alpha = np.exp(np.asarray(alpha_log._data))
    assert alpha.shape == (1, 64, 4, 16) and (alpha > 0).all()
    assert alpha.max() <= 1.0 and alpha.min() < 0.9 and alpha.max() > 0.99
    assert np.ptp(alpha[0, 0, 0]) > 0.01       # a vector a head, not a number
    assert 0 <= float(beta._data.min()) and float(beta._data.max()) <= 2


# ============================================================ whole forward
@pytest.mark.parametrize("tokens", [9, 50])
def test_whole_sequence_forward_is_the_reference(model, tokens):
    ids = np.random.RandomState(tokens).randint(
        1, CFG["vocab_size"], (2, tokens)).astype(np.int32)
    got = traced(model, paddle.to_tensor(ids))._data
    for row in range(2):
        want = ref.logits(CFG, SEED, ids[row], block=BLOCK)
        assert float(jnp.abs(want).max()) > 1.0
        close(got[row], want, LOGITS)


def test_int8_control_is_another_function():
    ids = np.random.RandomState(1).randint(1, 251, 30).astype(np.int32)
    sound = ref.logits(CFG, SEED, ids, block=BLOCK)
    low = ref.logits(CFG, SEED, ids, "int8", block=BLOCK)
    assert float(jnp.abs(sound - low).max()) > 100 * LOGITS


def test_eight_shares_and_one_shared_expert_are_the_uncut_layer():
    """The share test: a chip's ``close_block`` computes its held experts'
    part for the tokens that chose them and the shared expert. The eight
    shares' routed parts plus the shared expert counted ONCE are the layer
    of a chip that holds every expert."""
    whole = dict(CFG, n_routed_experts=16, experts_held=[0, 16])
    lw = layer_weights(1, whole)
    x = jnp.asarray(np.random.RandomState(6).randn(24, 64), jnp.float32)
    mixed = jnp.zeros((24, lw["o"].shape[0]), jnp.float32)
    kw = dict(top_k=4, scale=1.0, normalize=True, eps=1e-5)
    with jax.default_matmul_precision("highest"):
        uncut = ref.close_block(x, mixed, lw, lo=0, **kw)
        no_routed = {**lw, **{n: lw[n][:1] * 0.0
                              for n in ("w_gate", "w_up", "w_down")}}
        shared_only = ref.close_block(x, mixed, no_routed, lo=0, **kw)
        routed = 0.0
        for lo in range(0, 16, 2):      # eight chips of two experts
            mine = {**lw, **{n: lw[n][lo:lo + 2]
                             for n in ("w_gate", "w_up", "w_down")}}
            routed = routed + (ref.close_block(x, mixed, mine, lo=lo, **kw)
                               - shared_only)
    assert float(jnp.abs(routed).max()) > 1e-2
    close(shared_only + routed, uncut, TIGHT)


def test_the_programs_share_is_the_references_share(model):
    """The program's expert layer told it holds experts 0-3 of 16 against
    the reference's block with those four: what the absent twelve would add
    is left out of both."""
    blk = model.model.layers[1]
    lw = layer_weights(1)
    x = jnp.asarray(np.random.RandomState(7).randn(1, 24, 64), jnp.float32)
    got = traced(lambda h: h + blk.mlp(blk.post_attention_layernorm(h)),
                 Tensor(x))
    with jax.default_matmul_precision("highest"):
        want = ref.close_block(
            x[0], jnp.zeros((24, lw["o"].shape[0])), lw, top_k=4, scale=1.0,
            normalize=True, lo=0, eps=1e-5)
    close(got._data[0], want, TIGHT)


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


def test_speculate_raises_naming_slot_state():
    with pytest.raises(TypeError, match="slot_state"):
        served.engine(CASE, speculate="ngram")


def test_layout_health_and_counters_hold_four_arrays_and_the_load(model):
    """A layer declares its mixer's state AND the expert-load accumulator:
    the byte accounting counts the four slot arrays of every KDA layer, the
    pages the one GQA layer, and ``expert_load`` reads a counter a layer."""
    layout = model.paged_adapter().cache_layout(jnp.float32)
    counter = ("accumulator", (4 + 2,), jnp.int32)
    assert [entry[0][0] for entry in layout] == ["paged_kv"] \
        + ["slot_state"] * 3
    assert all(entry[1] == counter for entry in layout)
    assert layout[1][0][1] == {
        "conv_q": ((3, 64), jnp.float32), "conv_k": ((3, 64), jnp.float32),
        "conv_v": ((3, 64), jnp.float32),
        "s": ((4, 16, 16), jnp.float32)}
    eng = served.engine(CASE)
    h = eng.health()
    per_layer = (3 * 3 * 64 + 4 * 16 * 16) * 4
    assert h["state_bytes_per_slot"] == 3 * per_layer \
        == eng.state_bytes_per_slot
    assert h["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert len(eng.kc) == len(eng.vc) == 1 and len(eng.state) == 3 + 4
    paddle.set_flags({"FLAGS_enable_metrics": True})
    try:
        served.engine(CASE)
        from paddle_tpu.inference import resilience
        assert resilience.M_STATE_BYTES.value() == 4 * 3 * per_layer
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})
    prompts = served.prompts_of(CASE, (9, 30), seed=2)
    for p in prompts:
        eng.add_request(p, max_new_tokens=5)
    eng.run_to_completion()
    load = eng.expert_load()
    assert load["layers"] == [0, 1, 2, 3]
    # the prompts' rows and the steps that fed a token back (a step launched
    # before its lane's last token was read counts too), top-4 each
    rows = 9 + 30 + 2 * 4
    selected = load["pairs_selected"]
    assert len(set(selected)) == 1 and selected[0] % 4 == 0
    assert rows * 4 <= selected[0] <= (rows + 2 * 5) * 4
    assert all(0 < held < selected[0] for held in load["pairs_held"])
    assert [sum(t) for t in load["tokens"]] == load["pairs_held"]


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["composite", "interpreted-kernels"])
def test_an_idle_lane_is_untouched_and_a_fresh_slot_zero(monkeypatch,
                                                         kernels):
    """Every slot's state dirty as a long-lived engine's are. A budget of 8
    prompt tokens a tick keeps the 45-token prompt mid-way for six ticks
    while the others decode: its lane rides those steps under the ``seq =
    0`` sentinel and must get its state back as it was; a slot handed to a
    new request must start from zeros whatever the last tenant left. The
    served tokens are those of a clean engine. With the kernels interpreted
    (the ``KERNEL`` preset's linear heads of 128 x 128 and attention heads
    of 128) the decode step's rule and its attention are the Pallas
    kernels' own code."""
    from paddle_tpu.ops.pallas import delta_rule as rule_kernel
    from paddle_tpu.ops.pallas import paged_attention as attn_kernel
    if kernels:
        monkeypatch.setattr(rule_kernel, "INTERPRET", True)
        monkeypatch.setattr(attn_kernel, "INTERPRET", True)
    lin = dict(KERNEL["linear_attn_config"], num_heads=2)
    cfg = dict(KERNEL, num_attention_heads=2, num_key_value_heads=2,
               num_hidden_layers=2, linear_attn_config=lin)
    # a model of its own a case (the kernels' switch is read by the trace),
    # under both of the case's engines
    case = dataclasses.replace(CASE, cfg=cfg)
    m = served.build(case)
    prompts = served.prompts_of(case, (6, 45, 20), seed=8)
    tokens = []
    for dirty in (True, False):
        eng = served.engine(case, model=m, budget=8, block_size=16,
                            num_blocks=32, max_blocks_per_seq=8)
        if dirty:
            eng.state = [
                {k: jnp.full_like(v, 3.0) for k, v in st.items()}
                if isinstance(st, dict) else st for st in eng.state]
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


def test_decode_step_takes_the_kernel_where_it_runs(monkeypatch):
    from paddle_tpu.ops.pallas import delta_rule as rule_kernel
    state = (4, 2, 128, 128)
    assert not dr.use_step_kernel(state, 128, 1, channel=True)   # a CPU
    monkeypatch.setattr(rule_kernel, "INTERPRET", True)
    assert dr.use_step_kernel(state, 128, 1, channel=True)
    # the tiny preset's 16 x 16 state is no whole lane tile: the jnp step
    assert not dr.use_step_kernel((4, 4, 16, 16), 16, 1, channel=True)


def test_scopes_and_the_chunk_plan_are_in_the_served_programs(model):
    """Every scope ``observability.trace.DEVICE_SCOPES`` lists for this
    model, in the ``op_name``s of the lowered whole-sequence forward (the
    names the served programs carry too), and the chunk plan's stamp, which
    says the form with a decay a channel, on the program's ``compile.trace``
    entry."""
    from paddle_tpu.observability import trace as obs_trace

    ids = jnp.zeros((1, 24), jnp.int32)
    obs_trace.startup_clear()

    def whole_forward(a):
        return model(Tensor(a))._data

    text = jax.jit(whole_forward).lower(ids).as_text(debug_info=True)
    for scope in ("embed", "attn.full", "attn.full/attn.full.gate",
                  "attn.linear", "attn.linear/attn.linear.proj",
                  "attn.linear/attn.linear.conv",
                  "attn.linear/attn.linear.rule",
                  "attn.linear/attn.linear.norm", "moe", "moe/moe.router",
                  "moe/moe.experts", "moe/moe.shared", "lm_head"):
        assert scope.split("/")[-1] in obs_trace.DEVICE_SCOPES, scope
        assert f"{scope}/" in text or f"{scope})" in text, scope
    (traced_entry,) = [e for e in obs_trace.startup_record()["entries"]
                       if e[0] == "compile.trace"
                       and e[5]["program"] == "whole_forward"]
    assert traced_entry[5]["delta_rule_chunk_channel[24,8]"] == dict(
        dr.chunk_plan(24, 8, "channel"), calls=3)
    assert "delta_rule_chunk[24,8]" not in traced_entry[5]
    obs_trace.startup_clear()


@pytest.mark.parametrize("rows", [
    pytest.param(64, id="kexaone-kanana-nemotron-decode-lanes"),
    pytest.param(256, id="a-prefill-chunk-or-solar-decode-lanes"),
    pytest.param(256 + 64, id="kexaone-kanana-chunk-with-step"),
    pytest.param(256 + 256, id="solar-chunk-with-step")])
def test_every_serving_call_takes_the_masked_expert_product(rows):
    """The pick between the two forms of the held experts' product is by
    rows alone (16 / 16 / 128 / 40 held in the four serving cells with
    experts): every serving program's call decides as it decided, masked,
    which the chip measured faster at 40 held too
    (``experts.GROUPED_MIN_ROWS``)."""
    from paddle_tpu.nn.functional import experts
    assert not experts.takes_grouped_form(rows)
    assert experts.takes_grouped_form(2 * 8192)     # the trained cell's step
