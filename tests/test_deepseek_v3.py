"""The DeepSeek-V3 decoder (latent attention, SwiGLU experts with a shared
expert) against its plain reference, ``benchmark/reference/deepseek_v3.py``,
on seeded weights at tiny widths (``benchmark/tests/tiny_deepseek_v3.py``:
hidden 64, 4 heads of 16 + 8 over a latent row of 32 + 8, three layers dense
+ two sparse, 16 experts top-4 of width 48, two shared experts).

Everything runs in float32 on the CPU, the reference at ``highest``
precision and in the MATERIALISED form, the program through its cache in the
ABSORBED one, so the tolerances below are those of float32 sums taken in
another order and of two algebraically equal products (``(W_uk^T q) . c``
against ``q . (W_uk c)``), not of a lower precision:

* ``TIGHT`` 2e-5 absolute on values of order 1: one layer, a few hundred
  float32 additions reordered;
* ``LOGITS`` 5e-4 absolute on logits of order 1: three blocks deep, through
  the latent pages, the same reordering compounded. The same model in
  bfloat16 misses the reference by more than 1e-2
  (``test_bfloat16_would_fail_the_tolerance``), and a rotary embedding that
  leaves the published pairs where they are by more than that
  (``test_the_rotary_layout_shows_in_the_logits``).
"""
from __future__ import annotations

import copy
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
from benchmark.lib import weights_deepseek_v3 as weights_lib  # noqa: E402
from benchmark.reference import deepseek_v3 as ref  # noqa: E402
from benchmark.tests.tiny_deepseek_v3 import DEEPSEEK  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import serving  # noqa: E402
from paddle_tpu.nn.functional import paged_attention as fpa  # noqa: E402

import served  # noqa: E402
from served import (close, models, rand, rec, recording,  # noqa: E402,F401
                    traced)

SEED = 5
TIGHT = 2e-5
LOGITS = 5e-4
CFG = DEEPSEEK
ROW = CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]
CASE = served.Case(
    "latent", CFG, SEED, budget=16, atol=LOGITS,
    reference=lambda ids: ref.logits(CFG, SEED, ids, block=16))


def f32_weights(cfg, seed, layers=None):
    return served.f32_weights(weights_lib, cfg, seed, layers)


@pytest.fixture(scope="module")
def model():
    return served.model_of(CASE)


# ======================================================== latent attention
def test_absorbed_form_is_the_materialised_one(model):
    """The same function two ways: K and V expanded for every head and
    attended as multi-head attention, against scores and weighted sums over
    the rows ``[c | k_r]`` with ``W_uk`` moved onto the query and ``W_uv``
    onto the output."""
    attn = model.model.layers[1].self_attn
    u = Tensor(rand((2, 19, 64), 3))
    close(traced(attn.forward_absorbed, u)._data, traced(attn, u)._data,
          TIGHT)


def test_attention_layer_is_the_references(model):
    """One layer's attention against the reference's ``qkv`` + plain
    attention + ``W_o`` on the layer's own weights."""
    lw = {name: a for (_l, name), a in
          f32_weights(CFG, SEED, layers=[1]).items()}
    x = rand((23, 64), 7)
    with jax.default_matmul_precision("highest"):
        q, k, v = ref.qkv(
            x, 0, dict(lw, input_norm=jnp.ones((64,))), heads=4,
            nope=CFG["qk_nope_head_dim"], rope=CFG["qk_rope_head_dim"],
            rank=CFG["kv_lora_rank"], eps=CFG["rms_norm_eps"],
            theta=float(CFG["rope_theta"]))
        want = ref._attend(q, k, v) @ lw["o"]
    # the reference norms its input; hand the layer the normed rows
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + CFG["rms_norm_eps"])
    got = traced(model.model.layers[1].self_attn, Tensor(u[None]))._data[0]
    close(got, want, TIGHT)


def test_the_rotary_layout_shows_in_the_logits(model):
    """A model that rotates the published pairs as if they were already
    half-split (``rope_interleave`` off) moves a logit by far more than
    ``LOGITS``: the comparisons below would see the wrong layout."""
    ids = np.random.RandomState(3).randint(1, CFG["vocab_size"], (1, 30))
    want = traced(model, paddle.to_tensor(ids.astype(np.int32)))._data
    other = served.build(dataclasses.replace(
        CASE, cfg=dict(CFG, rope_interleave=False)))
    moved = traced(other, paddle.to_tensor(ids.astype(np.int32)))._data
    assert float(jnp.abs(moved - want).max()) > 20 * LOGITS


# ============================================================ expert layer
def uncut_cfg():
    cfg = copy.deepcopy(CFG)
    cfg.update(n_routed_experts=128, router_width=128,
               experts_held=[0, 128], num_experts_per_tok=6)
    return cfg


def moe_layer(cfg, lw, held):
    lo, hi = held
    layer = nn.SwiGLUMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
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


def test_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST, at the published counts (128 experts, top 6, two
    shared experts as one of the summed width): the routed parts of all 8
    shares (experts 0-15, 16-31, ... 112-127: the deployment's split), with
    what every chip computes alike (the shared expert) counted once, are
    the uncut reference layer; and each share is the reference's own share.
    Tolerance 8 x TIGHT on the sum: eight shares' roundings add."""
    cfg = uncut_cfg()
    lw = {name: a for (_l, name), a in
          f32_weights(cfg, SEED, layers=[1]).items()}
    # a bias that steers the choice without entering the weights
    lw["e_score_correction_bias"] = rand((128,), 99, scale=0.05)
    u = rand((2, 9, 64), 23)

    def ref_moe(weights, held):
        with jax.default_matmul_precision("highest"):
            return ref.feed_forward(dict(cfg, experts_held=list(held)),
                                    u.reshape(-1, 64), weights)

    whole = ref_moe(lw, (0, 128))
    total = shared = None
    for lo in range(0, 128, 16):
        layer = moe_layer(cfg, lw, (lo, lo + 16))
        shared = traced(layer.shared, Tensor(u.reshape(-1, 64)))._data
        out = traced(layer, Tensor(u))._data.reshape(-1, 64)
        part = dict(lw, **{k: lw[k][lo:lo + 16]
                           for k in ("w_gate", "w_up", "w_down")})
        close(out, ref_moe(part, (lo, lo + 16)), TIGHT)
        routed = out - shared
        total = routed if total is None else total + routed
    close(total + shared, whole, 8 * TIGHT)


# ========================================================= the whole model
@pytest.mark.parametrize("tokens", [9, 41])
def test_whole_sequence_forward_is_the_reference(model, tokens):
    """``forward(ids)``, what a trainer or an offline scorer calls, in the
    published (materialised) form."""
    ids = np.random.RandomState(tokens).randint(
        1, CFG["vocab_size"], (2, tokens)).astype(np.int32)
    got = traced(model, paddle.to_tensor(ids))._data
    for row in range(2):
        close(got[row], ref.logits(CFG, SEED, ids[row], block=16), LOGITS)


def test_bfloat16_would_fail_the_tolerance():
    """The same model with its matrices in bfloat16 misses the float32
    reference by more than 20 x ``LOGITS``: the tolerance tells the two
    precisions apart."""
    ids = np.random.RandomState(9).randint(
        1, CFG["vocab_size"], (1, 24)).astype(np.int32)
    low = served.build(CASE, dtype=jnp.bfloat16)
    got = traced(low, paddle.to_tensor(ids))._data.astype(jnp.float32)
    want = ref.logits(CFG, SEED, ids[0], block=16)
    assert float(jnp.abs(got[0] - want).max()) > 20 * LOGITS


def test_whole_sequence_forward_is_differentiable():
    """Eager autograd reaches every parameter through latent attention and
    the expert product (the router's correction bias only steers a choice:
    its gradient is zero)."""
    served.whole_sequence_forward_is_differentiable(
        CASE, np.random.RandomState(1).randint(
            1, CFG["vocab_size"], (2, 12)).astype(np.int32))


# ====================================================== through the engine
@pytest.mark.parametrize("front", ["engine", "router"])
def test_served_logits_are_the_references(rec, front):
    """Prefill in one to five chunks of 16 (left-padded first chunk where
    the prompt is no multiple of 16), then decode through the latent pages,
    four requests of unequal length sharing the batch: every logits row the
    programs sampled from against the reference's full forward over prompt
    + served tokens."""
    eng, _prompts, _ = served.served_logits_are_the_references(
        CASE, rec, front, width=16, new=10)
    load = eng.expert_load()
    assert load["layers"] == [1, 2]
    assert [sum(t) for t in load["tokens"]] == load["pairs_held"]


@pytest.mark.parametrize("width", [1, 7, 256])
def test_chunk_widths_and_the_blockwise_prefix(monkeypatch, rec, width):
    """Prefill in chunks of 8 (a budget of 1 or 7 tokens rounds up to one
    block) or of 256 (the engine's widest chunk, the benchmark's: a
    150-token prompt in one left-padded chunk, a 300-token prompt in two),
    over a block table that spans more than ``BLOCKWISE_FROM`` tokens, so
    that every chunk and, off the kernel, every decode step attends
    blockwise over the lane's own pages, a group of 16 tokens at a time.
    Then decode; every logits row against the reference."""
    monkeypatch.setattr(fpa, "BLOCKWISE_FROM", 64)
    monkeypatch.setattr(fpa, "BLOCKWISE_GROUP_TOKENS", 16)
    seen = []
    inner = fpa._blockwise_rows
    monkeypatch.setattr(fpa, "_blockwise_rows",
                        lambda *a: (seen.append(a[0].shape), inner(*a))[1])
    # functions jitted at module level keep traces made under other values
    fpa._latent_write_and_attend.clear_cache()
    try:
        # a model of its own: its programs are traced under the patches
        eng = served.engine(CASE, model=served.build(CASE), budget=width,
                            max_batch=32 if width == 256 else 2,
                            num_blocks=128, max_blocks_per_seq=40)
        assert eng.prefill_width == (256 if width == 256 else 8)
        prompts = served.prompts_of(
            CASE, (150, 300 if width == 256 else 21), seed=width)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        out = eng.run_to_completion(max_ticks=400)
        jax.effects_barrier()
    finally:
        fpa._latent_write_and_attend.clear_cache()
    widths = {shape[1] for shape in seen}
    assert widths == {eng.prefill_width, 1}
    assert all(s[-1] == eng._latent_row(ROW) for s in seen)
    served.check_served(CASE, rec, out, prompts, rids)


def test_a_reused_slot_sees_nothing_of_its_last_request(rec):
    """The second request is shorter than the pages the first left behind
    (freed and handed out again)."""
    served.a_reused_slot_starts_clean(CASE, rec, (45, 13))


def test_evict_then_readmit_reproduces_the_logits(rec):
    """Its latent pages are freed and it is re-prefilled, as ``paged_kv``
    pages are."""
    served.evict_then_readmit_reproduces_the_logits(
        CASE, rec, usable=6, length=12, new=20, max_ticks=400)


def test_speculate_serves_the_same_tokens():
    """``speculate=`` over latent pages needs no rollback: a rejected
    draft's row sits past the sequence's length, where nothing reads it,
    and the next step writes over it (as with ``paged_kv``). The verify
    step (T = k + 1) attends through the composite; greedy tokens are the
    plain engine's."""
    prompts = served.prompts_of(CASE, (9, 30), seed=6)
    # a repeating prompt gives the n-gram proposer something to accept
    prompts.append((prompts[0][:4] * 6)[:22])
    plain = served.engine(CASE)
    rids = [plain.add_request(p, max_new_tokens=12) for p in prompts]
    want = plain.run_to_completion()
    spec = served.engine(CASE, speculate="ngram")
    sids = [spec.add_request(p, max_new_tokens=12) for p in prompts]
    got = spec.run_to_completion()
    assert [got[s] for s in sids] == [want[r] for r in rids]
    assert spec.spec_proposed > 0


def test_int8_pages_are_refused_by_name():
    with pytest.raises(TypeError, match="latent_kv.*no int8 form"):
        served.engine(CASE, kv_dtype="int8")


def test_latent_pages_are_counted(model):
    """One pool a layer, rows in whole 128-lane tiles (40 -> 128 columns),
    paged by the one block table; the engine's byte accounting and
    ``health()`` count them, and a token costs a row a layer."""
    from paddle_tpu.core import flags
    from paddle_tpu.inference import resilience
    prev = flags.get_flag("enable_metrics")
    paddle.set_flags({"FLAGS_enable_metrics": True})
    try:
        eng = served.engine(CASE, num_blocks=32)
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": prev})
    layout = model.paged_adapter().cache_layout(jnp.float32)
    assert layout[0] == ("latent_kv", ROW)                    # dense layer
    assert [s[0] for s in layout[1]] == ["latent_kv", "accumulator"]
    assert eng._cache_index == {
        (0, "latent_kv"): 0, (1, "latent_kv"): 1, (1, "accumulator"): 2,
        (2, "latent_kv"): 3, (2, "accumulator"): 4}
    assert eng.kc == [] and eng.vc == []
    pools = eng._latent_pools()
    assert [p.shape for p in pools] == [(32, 8, 128)] * 3
    h = eng.health()
    assert h["kv_bytes_per_token"] == 3 * 128 * 4
    assert h["latent_bytes"] == 3 * 32 * 8 * 128 * 4 == sum(
        p.size * p.dtype.itemsize for p in pools)
    assert resilience.M_LATENT_BYTES.value() == h["latent_bytes"]
    assert h["window_bytes_per_slot"] == h["state_bytes_per_slot"] == 0
    with pytest.raises(ValueError, match="two 'latent_kv'"):
        serving._cache_index([(("latent_kv", 8), ("latent_kv", 8))])
    with pytest.raises(ValueError, match="unknown cache state kind.*"
                       "'latent_kv'"):
        serving._cache_index([("ring",)])


def test_scopes_are_in_the_lowered_programs():
    """``attn.mla`` with ``attn.mla.proj`` and ``attn.mla.core`` inside it,
    ``moe`` with its three parts, ``mlp``, ``embed``, ``lm_head`` and,
    inside the core, ``paged_attention``, in the ``op_name`` of both
    serving programs."""
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
        np.zeros((1,), np.int32))
    prefill = eng._fns["prefill"].lower(*args, sampling=False).as_text(
        debug_info=True)
    for text in (decode, prefill):
        for scope in ("embed", "attn.mla", "attn.mla/attn.mla.proj",
                      "attn.mla/attn.mla.core",
                      "attn.mla/attn.mla.core/paged_attention", "mlp",
                      "moe", "moe/moe.router", "moe/moe.experts",
                      "moe/moe.shared", "lm_head"):
            assert f"/{scope}/" in text, scope
