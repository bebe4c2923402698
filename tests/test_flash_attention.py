"""Pallas flash-attention kernel tests (interpret mode on CPU).

The kernels themselves run through the Pallas interpreter so the exact
kernel code that executes on TPU is what is tested here (reference test
analog: test/legacy_test/test_flash_attention.py comparing against a plain
attention implementation).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops.pallas.flash_attention as fa


def _ref_attn(q, k, v, causal, scale):
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


@pytest.fixture(autouse=True)
def _interpret():
    old = fa.INTERPRET
    fa.INTERPRET = True
    yield
    fa.INTERPRET = old


def _rand_qkv(b=1, s=128, h=2, d=32, t=None, seed=0):
    rng = np.random.RandomState(seed)
    t = t or s
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _rand_qkv()
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = fa.flash_attention_fwd(q, k, v, causal=causal,
                                 block_q=64, block_k=64)
    ref = _ref_attn(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forward_unaligned_seq():
    # seq not a multiple of the block: exercises padding/masking
    q, k, v = _rand_qkv(s=100, t=100)
    out = fa.flash_attention_fwd(q, k, v, causal=True,
                                 block_q=64, block_k=64)
    ref = _ref_attn(q, k, v, True, 1.0 / math.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_cross_attention_lengths():
    q, k, v = _rand_qkv(s=64, t=128)
    out = fa.flash_attention_fwd(q, k, v, causal=True,
                                 block_q=64, block_k=64)
    ref = _ref_attn(q, k, v, True, 1.0 / math.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(causal):
    q, k, v = _rand_qkv(s=128)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        out = fa.flash_attention_fwd(q, k, v, causal=causal,
                                     block_q=64, block_k=64)
        return jnp.sum(out * jnp.cos(out))   # non-trivial cotangent

    def loss_ref(q, k, v):
        out = _ref_attn(q, k, v, causal, scale)
        return jnp.sum(out * jnp.cos(out))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_backward_unaligned_and_different_blocks():
    q, k, v = _rand_qkv(s=100, t=100)

    def loss(q, k, v):
        out = fa.flash_attention_fwd(q, k, v, causal=True,
                                     block_q=64, block_k=32)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        out = _ref_attn(q, k, v, True, 1.0 / math.sqrt(q.shape[-1]))
        return jnp.sum(out ** 2)

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_bf16_forward_backward():
    q, k, v = _rand_qkv(s=64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_fwd(
            q, k, v, causal=True, block_q=32, block_k=32)
            .astype(jnp.float32))

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attn(q, k, v, True, scale).astype(jnp.float32))

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, np.float32),
            atol=0.15, rtol=0.1)


def test_fully_masked_rows_causal_sq_gt_sk():
    # causal with seq_q > seq_k: the first (sq - sk) query rows attend zero
    # keys. FA convention: output 0 for those rows, independent of block
    # size; gradients must not leak probability mass from them.
    q, k, v = _rand_qkv(s=128, t=64)
    n_masked = 128 - 64
    outs = []
    for bq, bk in [(32, 32), (64, 64), (128, 64)]:
        out = np.asarray(fa.flash_attention_fwd(q, k, v, causal=True,
                                                block_q=bq, block_k=bk))
        np.testing.assert_allclose(out[:, :n_masked], 0.0, atol=1e-6)
        outs.append(out)
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_fwd(
            q, k, v, causal=True, block_q=32, block_k=32) ** 2)

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # masked q rows contribute nothing anywhere
    np.testing.assert_allclose(np.asarray(gq)[:, :n_masked], 0.0, atol=1e-6)
    assert np.all(np.isfinite(np.asarray(gk)))
    assert np.all(np.isfinite(np.asarray(gv)))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attn_unpadded_matches_per_sequence(causal):
    """Varlen packed attention == dense attention run per sequence."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.functional import flash_attn_unpadded

    rng = np.random.RandomState(0)
    lens = [5, 9, 3]
    total, h, d = sum(lens), 2, 16
    q = rng.randn(total, h, d).astype(np.float32)
    k = rng.randn(total, h, d).astype(np.float32)
    v = rng.randn(total, h, d).astype(np.float32)
    cu = np.cumsum([0] + lens).astype(np.int32)
    scale = 1.0 / math.sqrt(d)

    out, _ = flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(cu), paddle.to_tensor(cu),
        max(lens), max(lens), scale, causal=causal)
    got = out.numpy()

    for i, L in enumerate(lens):
        s, e = cu[i], cu[i + 1]
        ref = _ref_attn(jnp.asarray(q[None, s:e]), jnp.asarray(k[None, s:e]),
                        jnp.asarray(v[None, s:e]), causal, scale)
        np.testing.assert_allclose(got[s:e], np.asarray(ref)[0],
                                   atol=2e-5,
                                   err_msg=f"sequence {i} mismatch")


def test_flash_attn_unpadded_no_cross_sequence_leak():
    import paddle_tpu as paddle
    from paddle_tpu.nn.functional import flash_attn_unpadded

    rng = np.random.RandomState(1)
    lens = [4, 4]
    total, h, d = 8, 1, 8
    q = rng.randn(total, h, d).astype(np.float32)
    k = rng.randn(total, h, d).astype(np.float32)
    v = rng.randn(total, h, d).astype(np.float32)
    cu = np.cumsum([0] + lens).astype(np.int32)
    out1, _ = flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(cu), paddle.to_tensor(cu), 4, 4,
        1.0 / math.sqrt(d))
    # perturb sequence 2's K/V: sequence 1's output must not change
    k2, v2 = k.copy(), v.copy()
    k2[4:] += 100.0
    v2[4:] -= 100.0
    out2, _ = flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(k2), paddle.to_tensor(v2),
        paddle.to_tensor(cu), paddle.to_tensor(cu), 4, 4,
        1.0 / math.sqrt(d))
    np.testing.assert_allclose(out1.numpy()[:4], out2.numpy()[:4],
                               atol=1e-6)


def test_grad_under_jit():
    q, k, v = _rand_qkv(s=64)
    f = jax.jit(jax.grad(lambda q: jnp.sum(fa.flash_attention_fwd(
        q, k, v, causal=True, block_q=32, block_k=32) ** 2)))
    g = f(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_pallas_flash_is_partitioned_by_hand_under_a_mesh(monkeypatch):
    """Mosaic kernels cannot be auto-partitioned (on a TPU the lowering
    raises "wrap the call in a shard_map"): under a multi-device mesh the
    functional's Pallas path is a full-manual shard_map over batch x
    heads, leaving a dim that does not divide replicated. Here batch 8
    shards over dp=4 and 3 heads stay replicated over mp=2; values and
    gradients must match the XLA reference."""
    import importlib

    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod
    F = importlib.import_module("paddle_tpu.nn.functional.flash_attention")

    monkeypatch.setattr(mesh_mod, "_global_mesh",
                        mesh_mod.build_mesh({"dp": 4, "mp": 2}))
    q, k, v = _rand_qkv(b=8, s=128, h=3)
    g = _rand_qkv(b=8, s=128, h=3, seed=1)[0]

    def vg(attn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v) * g), argnums=(0, 1, 2)))

    (ref, ref_grads) = vg(lambda q, k, v: F._sdpa_xla(
        q, k, v, causal=True))(q, k, v)
    (got, got_grads) = vg(lambda q, k, v: F._flash_pallas(
        q, k, v, True))(q, k, v)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    for a, b in zip(got_grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert got_grads[0].sharding.spec == P("dp")


# (seq_q, seq_k, block_q, block_k or None for the causal tile, dtype,
#  SUB_BLOCK or None for the module's)
_CAUSAL_CASES = [
    pytest.param(1024, 1024, 1024, 1024, "bfloat16", None,
                 id="cells-shape-one-tile-bf16"),
    pytest.param(1024, 1024, 1024, 1024, "float32", None,
                 id="cells-shape-one-tile-f32"),
    pytest.param(384, 384, 1024, 1024, "float32", None,
                 id="s384-padded-to-two-bands"),
    pytest.param(384, 384, 1024, 1024, "float32", 128,
                 id="s384-three-bands-of-128"),
    pytest.param(1000, 1000, 512, 512, "float32", None,
                 id="s1000-ragged-tail-2x2-tiles"),
    pytest.param(1000, 1000, 1024, 1024, "bfloat16", None,
                 id="s1000-one-tile-padded-to-four-bands-bf16"),
    pytest.param(1100, 1100, None, None, "float32", None,
                 id="s1100-the-causal-tile-padded-to-five-bands"),
    pytest.param(200, 200, None, None, "float32", None,
                 id="s200-shorter-than-a-band-runs-whole"),
    pytest.param(512, 1024, 512, 1024, "float32", None,
                 id="sq-lt-sk-offset-512"),
    pytest.param(640, 1000, 256, 512, "float32", 128,
                 id="sq-lt-sk-offset-360-ragged"),
    pytest.param(1024, 512, 1024, 512, "float32", None,
                 id="sq-gt-sk-512-rows-see-nothing"),
    pytest.param(600, 300, 512, 512, "float32", 128,
                 id="sq-gt-sk-ragged-2-q-tiles"),
    pytest.param(2048, 2048, 512, 512, "bfloat16", None,
                 id="s2048-4x4-tiles-bf16"),
    pytest.param(1024, 1024, 256, 512, "float32", 128,
                 id="s1024-4x2-tiles-of-two-bands"),
]


@pytest.mark.parametrize("sq,sk,bq,bk,dtype,sub", _CAUSAL_CASES)
def test_causal_forward_and_backward_match_the_reference(
        monkeypatch, sq, sk, bq, bk, dtype, sub):
    """The causal kernels run only the part of a tile at or below the
    diagonal (bands of SUB_BLOCK rows, each against the keys it sees):
    values and gradients against ``_sdpa_xla`` for one tile and several,
    ragged tails, both offsets, both dtypes. Rows that see no key
    (seq_q > seq_k) give zeros and zero gradients; the reference gives
    them a uniform softmax, so the cotangent is zero there."""
    from paddle_tpu.nn.functional.flash_attention import _sdpa_xla

    if sub is not None:
        monkeypatch.setattr(fa, "SUB_BLOCK", sub)
    d = 64 if sq == sk == 1024 else 32
    q, k, v = (x.astype(dtype) for x in _rand_qkv(s=sq, t=sk, h=1, d=d))
    blind = max(sq - sk, 0)
    g = _rand_qkv(s=sq, h=1, d=d, seed=1)[0].at[:, :blind].set(0.0)

    def vg(attn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * g),
            argnums=(0, 1, 2)))

    out = fa.flash_attention_fwd(q, k, v, causal=True, block_q=bq,
                                 block_k=bk)
    ref = _sdpa_xla(q, k, v, causal=True)
    _, grads = vg(lambda q, k, v: fa.flash_attention_fwd(
        q, k, v, causal=True, block_q=bq, block_k=bk))(q, k, v)
    _, ref_grads = vg(lambda q, k, v: _sdpa_xla(q, k, v, causal=True))(
        q, k, v)

    f32 = dtype == "float32"
    tol = dict(atol=2e-5, rtol=2e-5) if f32 else dict(atol=0.15, rtol=0.1)
    gtol = dict(atol=5e-5, rtol=5e-4) if f32 else dict(atol=0.15, rtol=0.1)
    as32 = lambda x: np.asarray(x, np.float32)      # noqa: E731
    np.testing.assert_array_equal(as32(out)[:, :blind], 0.0)
    np.testing.assert_array_equal(as32(grads[0])[:, :blind], 0.0)
    np.testing.assert_allclose(as32(out)[:, blind:], as32(ref)[:, blind:],
                               **tol)
    for a, b, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(as32(a), as32(b), **gtol,
                                   err_msg=f"d{name}")


# (heads, head width): how the heads lie in a 128-lane block
_HEAD_LAYOUTS = [
    pytest.param(2, 128, id="d128-one-head-a-block"),
    pytest.param(4, 64, id="d64-two-heads-a-block"),
    pytest.param(4, 32, id="d32-four-heads-a-block"),
    pytest.param(3, 16, id="d16-three-heads-each-padded"),
    pytest.param(3, 64, id="d64-odd-head-count-each-padded"),
]
# (seq_q, seq_k, causal, block_q, block_k, dtype)
_HEAD_CALLS = [
    pytest.param(128, 128, True, 128, 128, "float32", id="causal-one-pass"),
    pytest.param(128, 128, True, 64, 64, "float32", id="causal-2x2-tiles"),
    pytest.param(128, 128, False, 64, 64, "float32", id="full-2x2-tiles"),
    pytest.param(100, 100, True, 64, 32, "float32", id="causal-ragged-100"),
    pytest.param(64, 160, False, 64, 64, "float32", id="full-sq-lt-sk-ragged"),
    pytest.param(128, 64, True, 64, 64, "float32", id="causal-sq-gt-sk"),
    pytest.param(256, 256, True, None, None, "bfloat16",
                 id="causal-bf16-the-modules-tile"),
]


@pytest.mark.parametrize("heads,d", _HEAD_LAYOUTS)
@pytest.mark.parametrize("sq,sk,causal,bq,bk,dtype", _HEAD_CALLS)
def test_every_head_layout_matches_the_reference(heads, d, sq, sk, causal, bq,
                                                 bk, dtype):
    """Forward and the three gradients against ``_sdpa_xla`` for every way
    the heads of a call lie in the kernels' lane blocks (one head of d
    lanes, 128 // d heads side by side, a head padded to a block of its
    own), batch 2: each head of a block with its own max, sum and
    accumulator, its own logsumexp row and delta."""
    from paddle_tpu.nn.functional.flash_attention import _sdpa_xla

    assert fa._head_layout(heads, d)[0] == {
        (2, 128): 1, (4, 64): 2, (4, 32): 4, (3, 16): 1, (3, 64): 1}[heads, d]
    q, k, v = (x.astype(dtype) for x in _rand_qkv(b=2, s=sq, t=sk, h=heads,
                                                  d=d))
    blind = max(sq - sk, 0) if causal else 0
    g = _rand_qkv(b=2, s=sq, h=heads, d=d, seed=1)[0].at[:, :blind].set(0.0)

    def vg(attn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * g),
            argnums=(0, 1, 2), has_aux=False))

    flash = lambda q, k, v: fa.flash_attention_fwd(   # noqa: E731
        q, k, v, causal=causal, block_q=bq, block_k=bk)
    out = jax.jit(flash)(q, k, v)
    ref = _sdpa_xla(q, k, v, causal=causal)
    _, grads = vg(flash)(q, k, v)
    _, ref_grads = vg(lambda q, k, v: _sdpa_xla(q, k, v, causal=causal))(
        q, k, v)
    f32 = dtype == "float32"
    tol = dict(atol=2e-5, rtol=2e-5) if f32 else dict(atol=0.15, rtol=0.1)
    gtol = dict(atol=5e-5, rtol=5e-4) if f32 else dict(atol=0.15, rtol=0.1)
    as32 = lambda x: np.asarray(x, np.float32)      # noqa: E731
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_array_equal(as32(out)[:, :blind], 0.0)
    np.testing.assert_allclose(as32(out)[:, blind:], as32(ref)[:, blind:],
                               **tol)
    for a, b, name in zip(grads, ref_grads, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(as32(a), as32(b), **gtol,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("sq,sk,causal,bq,bk", [
    (256, 256, True, 128, 128), (128, 128, True, 128, 128),
    (192, 256, False, 64, 128)], ids=["causal-2x2", "causal-one-pass", "full"])
def test_the_heads_loop_gives_what_its_unrolling_gives(monkeypatch, sq, sk,
                                                        causal, bq, bk):
    """A grid step of few bands unrolls the loop over its block's heads, one
    of many keeps it a loop with a traced head index (``_unroll_heads``: the
    S 4096 and S 8192 calls, whose unrolled kernels ran 45-51 % longer on
    the chip and compile in 18 s for 7.4): the same body, so the same values
    and gradients bit for bit; and the rule is the count of bands, with the
    shapes that were timed on either side of it."""
    def rule(s, causal, block):
        return fa._unroll_heads(fa._grid_classes(s, s, causal, block, block,
                                                 fa.SUB_BLOCK))

    assert rule(1024, True, 2048) and rule(2048, True, 2048)
    assert not rule(4096, True, 2048) and not rule(8192, True, 2048)
    assert rule(4096, False, 512) and rule(4096, False, 1024)
    q, k, v = _rand_qkv(b=2, s=sq, t=sk, h=4, d=64)

    def run(bands):
        monkeypatch.setattr(fa, "UNROLL_HEADS_BANDS", bands)
        fa._fwd_call.clear_cache()
        fa._bwd_call.clear_cache()
        text = str(jax.make_jaxpr(lambda q: fa.flash_attention_fwd(
            q, k, v, causal=causal, block_q=bq, block_k=bk))(q))
        return text, jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fa.flash_attention_fwd(
                q, k, v, causal=causal, block_q=bq, block_k=bk) ** 2),
            argnums=(0, 1, 2)))(q, k, v)

    try:
        loop_text, (loop, loop_grads) = run(0)
        flat_text, (flat, flat_grads) = run(64)
    finally:
        fa._fwd_call.clear_cache()
        fa._bwd_call.clear_cache()
    assert "unroll=1 " in loop_text.replace("\n", " ")
    assert "unroll=2 " in flat_text.replace("\n", " ")
    np.testing.assert_array_equal(np.asarray(loop), np.asarray(flat))
    for a, b in zip(loop_grads, flat_grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tpu_lowered_value_and_grad(shape):
    """StableHLO text of ``value_and_grad`` of the public entry, causal,
    bf16, lowered for a TPU (the Mosaic kernels as ``tpu_custom_call``)."""
    old, fa.INTERPRET = fa.INTERPRET, False
    try:
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fa.flash_attention_fwd(
                q, k, v, causal=True, block_q=1024,
                block_k=1024).astype(jnp.float32)),
            argnums=(0, 1, 2))).trace(x, x, x).lower(
                lowering_platforms=("tpu",)).as_text()
    finally:
        fa.INTERPRET = old


def _kernel_signatures(text):
    import re

    calls = {}
    for line in text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        name = re.search(r'kernel_name = "(\w+)"', line).group(1)
        operands, results = line.rsplit(" : ", 1)[1].split(" -> ")
        assert name not in calls, name
        calls[name] = (operands.count("tensor<"),
                       re.findall(r"x([a-z]+\d+)>", results))
    return calls


_SIGNATURES = {"flash_fwd": (3, ["bf16", "f32"]), "flash_dq": (6, ["bf16"]),
               "flash_dkv": (6, ["bf16", "bf16"])}


def test_the_three_kernels_keep_the_signatures_the_benchmark_reads():
    """benchmark/layer_metrics/flash_attn_roofline_pct.py::kernel_kind tells
    the three Mosaic calls of a trace apart by operands and results: forward
    3 -> (out, lse f32), dQ 6 -> 1, dK/dV 6 -> 2. A traced run that does not
    show all three kinds reads the metric None."""
    assert _kernel_signatures(
        _tpu_lowered_value_and_grad((1, 1024, 2, 64))) == _SIGNATURES


def test_heads_that_pack_reach_the_kernels_with_no_transpose_and_no_pad():
    """Two 64-wide heads are one 128-lane block of the (B, S, H*d) array the
    model holds: around the three calls the lowered program reshapes and
    nothing else (no ``transpose`` into (B*H, S, d), no ``pad`` of d to 128
    lanes; delta is the kernels' own, so no reduction either). Three heads
    of 16 are a block each, padded to 128 lanes: pads, and still no
    transpose."""
    packed = _tpu_lowered_value_and_grad((1, 1024, 2, 64))
    assert "stablehlo.transpose" not in packed
    assert "stablehlo.pad" not in packed
    assert "stablehlo.reduce" not in packed.replace(
        "stablehlo.reduce_precision", "")[packed.index("@tpu_custom_call"):
                                          packed.rindex("@tpu_custom_call")]
    padded = _tpu_lowered_value_and_grad((1, 1024, 3, 16))
    assert "stablehlo.transpose" not in padded
    assert "stablehlo.pad" in padded
    assert _kernel_signatures(padded) == _SIGNATURES


def _brute_force_share(sq, sk, bq, bk, band):
    """Visited (band x lane-group) cells of every tile over the padded
    square: a cell runs iff one of its scores is unmasked."""
    bq, bk = min(bq, max(sq, 8)), min(bk, max(sk, 8))
    # a tile longer than a band is whole bands, wider than the lanes whole
    # lane groups; a shorter one is one piece
    bq = -(-bq // band) * band if bq > band else bq
    bk = -(-bk // 128) * 128 if bk > 128 else bk
    sp_q, sp_k = -(-sq // bq) * bq, -(-sk // bk) * bk
    band, lanes = min(band, bq), min(128, bk)
    r = np.arange(sp_q)[:, None]
    c = np.arange(sp_k)[None, :]
    live = (c <= r + (sk - sq)) & (c < sk)
    area = 0
    for q0 in range(0, sp_q, bq):
        for k0 in range(0, sp_k, bk):
            for r0 in range(q0, q0 + bq, band):
                # a band runs keys [k0, last live lane group] in one piece
                groups = [c0 for c0 in range(k0, k0 + bk, lanes)
                          if live[r0:r0 + band, c0:c0 + lanes].any()]
                if groups:
                    area += band * (groups[-1] + lanes - k0)
    return area / (sp_q * sp_k)


def test_flash_plan_counts_what_the_kernels_run(monkeypatch):
    for bq, bk in set([(fa.CAUSAL_BLOCK,) * 2] + fa.FWD_TILE_CANDIDATES
                      + fa.BWD_TILE_CANDIDATES):
        plan = fa.flash_plan(1024, 1024, True, bq, bk)
        assert plan["executed_share"] <= 0.63, (bq, bk, plan)
        assert plan["tiles"] == [1024 // min(bq, 1024), 1024 // min(bk, 1024)]
        assert plan["sub_block"] == 256
        assert fa.flash_plan(1024, 1024, False, bq, bk) == {
            "tiles": plan["tiles"], "sub_block": None, "executed_share": 1.0,
            "heads_per_block": 1, "io_bytes": 262144, "stats_bytes": 4096}
    for band in (128, 256):
        monkeypatch.setattr(fa, "SUB_BLOCK", band)
        for sq, sk, bq, bk in [(1024, 1024, 1024, 1024), (1000, 1000, 512, 512),
                               (640, 1000, 256, 512), (600, 300, 512, 512),
                               (1024, 512, 1024, 512), (384, 384, 1024, 1024),
                               (2048, 2048, 512, 1024), (100, 100, 64, 32),
                               (2000, 2000, 2048, 2048), (1100, 900, 2048, 2048),
                               (700, 700, 300, 200), (8192, 8192, 1024, 1024)]:
            plan = fa.flash_plan(sq, sk, True, bq, bk)
            assert plan["executed_share"] == pytest.approx(
                _brute_force_share(sq, sk, bq, bk, band)), (band, sq, sk, bq, bk)
    monkeypatch.setattr(fa, "SUB_BLOCK", 128)
    assert fa.flash_plan(1024, 1024, True, 1024, 1024, 16, 64) == {
        "tiles": [1, 1], "sub_block": 128, "executed_share": 0.5625,
        "heads_per_block": 2, "io_bytes": 131072, "stats_bytes": 4096}


@pytest.mark.parametrize("call", [
    (1024, 1024, True, 2048, 2048), (8192, 8192, True, 2048, 2048),
    (700, 700, False, 1024, 1024), (1536, 1536, False, 1024, 1024),
    (640, 1000, True, 256, 512)], ids=lambda c: "x".join(map(str, c[:2])))
def test_flash_plan_says_what_the_row_statistics_occupy(call):
    """``stats_bytes``: a head's logsumexp in HBM as the kernels lay it out,
    a float32 a padded row in whole 128-lane tiles, the rows of a block's
    heads together ((.., 2, S) tiles as T(2,128): no sublane is padded);
    the kernel's own result has that shape. It is the one statistic that
    crosses HBM (delta is the backward kernels' own). A (.., S, 1) column
    would read 128 times as much."""
    sq, sk, causal, bq, bk = call
    sp_q = fa._geometry(sq, sk, bq, bk, fa.SUB_BLOCK if causal else None)[2]
    lanes = -(-sp_q // 128) * 128
    for heads, d, hpb in [(4, 32, 4), (6, 64, 2), (3, 128, 1), (3, 32, 1)]:
        assert fa.flash_plan(*call, heads, d)["stats_bytes"] == 4 * lanes
        x = jax.ShapeDtypeStruct((3, sq, heads, d), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((3, sk, heads, d), jnp.bfloat16)
        out, lse = jax.eval_shape(lambda q, k: fa._flash_fwd_bshd(
            q, k, k, causal=causal, scale=1.0, block_q=bq, block_k=bk), x, k)
        assert out.shape == x.shape
        assert (lse.shape, lse.dtype) == ((3, heads // hpb, hpb, sp_q),
                                          jnp.float32)
        assert fa._hbm_bytes(lse.shape) == 3 * heads * 4 * lanes
    assert fa._hbm_bytes((1, sp_q, 1)) == 128 * 4 * -(-sp_q // 8) * 8
    assert fa._hbm_bytes((1, 3, sp_q)) == 8 * 4 * lanes


@pytest.mark.parametrize("heads,d,itemsize,hpb,io_bytes", [
    (16, 64, 2, 2, 131072),     # the GPT-2 cells': half of the padded 262144
    (32, 64, 2, 2, 131072), (8, 128, 2, 1, 262144), (4, 256, 2, 1, 524288),
    (8, 32, 2, 4, 65536), (8, 32, 4, 4, 131072),
    # heads that do not tile 128 lanes: each padded to a block of its own
    (3, 16, 2, 1, 262144), (3, 64, 2, 1, 262144), (2, 32, 4, 1, 524288),
    (5, 96, 2, 1, 262144), (2, 192, 2, 1, 524288)])
def test_flash_plan_says_how_the_heads_share_a_lane_block(heads, d, itemsize,
                                                          hpb, io_bytes):
    """``heads_per_block`` and ``io_bytes`` (a head's q in HBM as the kernels
    read it, S 1024) follow from the head count and width alone."""
    plan = fa.flash_plan(1024, 1024, True, 2048, 2048, heads, d, itemsize)
    assert (plan["heads_per_block"], plan["io_bytes"]) == (hpb, io_bytes)
    assert fa._head_layout(heads, d) == (hpb, -(-d // 128) * 128
                                         if hpb == 1 else d)


@pytest.mark.parametrize("heads,d,probe", [
    (16, 64, 8), (9, 64, 7), (3, 16, 3), (12, 16, 7), (10, 32, 7),
    (12, 32, 8), (32, 8, 16), (16, 128, 8), (5, 96, 5)])
def test_the_autotuners_probe_lies_as_the_callers_heads_do(heads, d, probe):
    """``_tuned_blocks`` caches a winner under the caller's
    ``heads_per_block``, so the few heads it times lie the same way: an odd
    count of 64-wide heads is one padded head a block in the probe too."""
    assert fa._probe_heads(heads, d) == probe
    assert fa._head_layout(probe, d) == fa._head_layout(heads, d)


def test_no_causal_tile_longer_than_a_band_runs_whole():
    """A ragged length or a caller's pin never leaves a tile the diagonal
    crosses in one piece: a 2000 x 2000 tile's f32 scores alone are 16 MB
    of VMEM. The tile is padded to whole bands and lane groups, and the
    mask takes the tail."""
    assert fa._geometry(2000, 2000, 2048, 2048, 256) == (2048, 2048, 2048,
                                                         2048)
    assert fa._geometry(1100, 900, 2048, 2048, 256) == (1280, 1024, 1280,
                                                        1024)
    assert fa._geometry(700, 700, 300, 200, 256) == (512, 256, 1024, 768)
    assert fa._geometry(200, 100, 2048, 2048, 256) == (200, 100, 200, 100)
    for sq, sk, bq, bk in [(2000, 2000, 2048, 2048), (1100, 900, 2048, 2048),
                           (700, 700, 300, 200), (2047, 4000, 1000, 1000)]:
        for blocks, _keys, _n in fa._grid_classes(sq, sk, True, bq, bk, 256):
            assert all(r1 - r0 == 256 and c1 % 128 == 0 and mc0 % 128 == 0
                       for r0, r1, c1, mc0, _guard, *_window in blocks), (sq, sk)
    # a non-causal call's tiles are clamped to the sequence and no more
    assert fa._geometry(2000, 2000, 1024, 1024) == (1024, 1024, 2048, 2048)
    assert fa._geometry(700, 700, 1024, 1024) == (700, 700, 700, 700)


@pytest.mark.parametrize("heads,d,hpb", [(2, 64, 2), (2, 128, 1), (4, 32, 4),
                                         (3, 64, 1)])
def test_a_non_causal_call_runs_one_block_a_tile_whatever_the_band(
        monkeypatch, heads, d, hpb):
    """The bands are the causal mask's: a non-causal kernel is traced to
    the same program at any SUB_BLOCK, two matmuls a forward tile: the body
    of a head, traced once, in a loop over the block's heads."""
    x = jax.ShapeDtypeStruct((1, 1024, heads, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, heads // hpb, hpb, 1024), jnp.float32)

    def programs():
        kw = dict(causal=False, scale=0.125, block_q=512, block_k=1024)
        return (str(jax.make_jaxpr(lambda q, k, v: fa._flash_fwd_bshd(
                    q, k, v, **kw))(x, x, x)),
                str(jax.make_jaxpr(lambda *a: fa._flash_bwd_bshd(
                    *a, **kw))(x, x, x, x, lse, x)))

    monkeypatch.setattr(fa, "SUB_BLOCK", 128)
    fwd, bwd = programs()
    monkeypatch.setattr(fa, "SUB_BLOCK", 256)
    assert (fwd, bwd) == programs()
    assert fwd.count("dot_general") == 2 and bwd.count("dot_general") == 7
    # the loops over the heads: the forward's tile and its last step's
    # normalisation, dQ's tile, dK/dV's tile
    assert (fwd.count("scan["), bwd.count("scan[")) == (
        (2, 2) if hpb > 1 else (0, 0))
    assert "concatenate" not in fwd + bwd
    # only a head that is no whole share of a lane block is padded
    assert (" pad[" in fwd) == (d % 128 != 0 and hpb == 1)


# --------------------------------------------------------------------------
# A window: query i sees key j iff 0 <= i - j < window (ISSUE 47).
# --------------------------------------------------------------------------
def _window_share(sq, sk, bq, bk, band, window):
    """``_brute_force_share`` under a window: a band runs the keys from its
    first live lane group to its last, in one piece."""
    bq, bk = min(bq, max(sq, 8)), min(bk, max(sk, 8))
    bq = -(-bq // band) * band if bq > band else bq
    bk = -(-bk // 128) * 128 if bk > 128 else bk
    sp_q, sp_k = -(-sq // bq) * bq, -(-sk // bk) * bk
    band, lanes = min(band, bq), min(128, bk)
    back = np.arange(sp_q)[:, None] + (sk - sq) - np.arange(sp_k)[None, :]
    live = (back >= 0) & (back < window) & (np.arange(sp_k)[None, :] < sk)
    area = tiles = 0
    for q0 in range(0, sp_q, bq):
        for k0 in range(0, sp_k, bk):
            tiles += bool(live[q0:q0 + bq, k0:k0 + bk].any())
            for r0 in range(q0, q0 + bq, band):
                groups = [c0 for c0 in range(k0, k0 + bk, lanes)
                          if live[r0:r0 + band, c0:c0 + lanes].any()]
                if groups:
                    area += band * (groups[-1] + lanes - groups[0])
    return area / (sp_q * sp_k), tiles


_WINDOW_CASES = [
    # (sq, sk, window, block_q, block_k, band, dtype)
    pytest.param(512, 512, 24, 256, 256, 64, jnp.float32, id="under-a-band"),
    pytest.param(512, 512, 128, 128, 128, 64, jnp.float32, id="one-tile"),
    pytest.param(500, 500, 300, 128, 128, 64, jnp.float32,
                 id="tiles-ragged-tail"),
    pytest.param(384, 512, 200, 128, 256, 128, jnp.float32,
                 id="fewer-queries"),
    pytest.param(256, 256, 100, 512, 512, 64, jnp.float32, id="one-pass"),
    pytest.param(512, 512, 257, 128, 256, 128, jnp.bfloat16, id="bf16"),
]


@pytest.mark.parametrize("sq,sk,window,bq,bk,band,dtype", _WINDOW_CASES)
def test_a_window_matches_the_definition(monkeypatch, sq, sk, window, bq, bk,
                                         band, dtype):
    """The three kernels under a window against ``_sdpa_xla``'s mask, outputs
    and dQ / dK / dV: a window shorter than a band (both edges in one band's
    mask), one tile long, spanning tiles over a ragged tail, with fewer
    queries than keys, a row of tiles one tile long (the one-pass forward);
    and the plan says the window, the tiles that run (the rest are in no
    class of ``_grid_classes``) and the share of the square computed."""
    from paddle_tpu.nn.functional.flash_attention import _sdpa_xla
    monkeypatch.setattr(fa, "SUB_BLOCK", band)
    q, k, v = (a.astype(dtype) for a in _rand_qkv(s=sq, t=sk, h=2, d=32))
    g = _rand_qkv(s=sq, h=2, d=32, seed=1)[0].astype(dtype)

    def grads(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum((attend(q, k, v) * g).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    out, got = grads(lambda q, k, v: fa.flash_attention_fwd(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk))
    ref_out, ref = grads(lambda q, k, v: _sdpa_xla(
        q, k, v, causal=True, window=window))
    tol = (dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32
           else dict(atol=0.06, rtol=0.06))
    np.testing.assert_allclose(float(out), float(ref_out), rtol=5e-3
                               if dtype == jnp.bfloat16 else 1e-5)
    for a, b, name in zip(got, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol,
                                   err_msg=f"d{name}")
    plan = fa.flash_plan(sq, sk, True, bq, bk, 2, 32, 4, window)
    share, tiles = _window_share(sq, sk, bq, bk, band, window)
    assert plan["window"] == window and plan["tiles_run"] == tiles
    assert plan["executed_share"] == pytest.approx(share)
    assert tiles <= plan["tiles"][0] * plan["tiles"][1]


def test_a_window_no_shorter_than_the_keys_is_the_causal_call():
    """``window >= seq`` (or None) builds the causal call, kernel for
    kernel: the same program text, the same values and gradients bit for
    bit; a window on a non-causal call is refused."""
    q, k, v = _rand_qkv(s=256, h=2, d=32)

    def program(window):
        f = lambda q, k, v: jnp.sum(fa.flash_attention_fwd(  # noqa: E731
            q, k, v, causal=True, window=window, block_q=128,
            block_k=128) ** 2)
        return (str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)),
                jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v))

    causal_text, (causal, causal_grads) = program(None)
    for window in (256, 4096):
        text, (out, grads) = program(window)
        assert text == causal_text
        assert float(out) == float(causal)
        for a, b in zip(grads, causal_grads):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert program(255)[0] != causal_text
    with pytest.raises(ValueError):
        fa.flash_attention_fwd(q, k, v, causal=False, window=64)


def test_a_call_without_a_window_has_the_plan_it_had():
    """``flash_plan`` of the three cells that ran the kernels before the
    window (GPT-2 at S 1024, LFM2 at S 8192: the tiles, the bands, the
    share of the square, every byte count) and the name its stamp goes
    under; a windowed call says more, under a name of its own."""
    assert fa.flash_plan(1024, 1024, True, 2048, 2048, 16, 64, 2) == {
        "tiles": [1, 1], "sub_block": 256, "executed_share": 0.625,
        "heads_per_block": 2, "io_bytes": 131072, "stats_bytes": 4096}
    assert fa.flash_plan(8192, 8192, True, 2048, 2048, 32, 64, 2) == {
        "tiles": [4, 4], "sub_block": 256, "executed_share": 0.515625,
        "heads_per_block": 2, "io_bytes": 1048576, "stats_bytes": 32768}
    assert fa._plan_entry("fwd", 1024, 1024, True, 2048, 2048, 16, 64,
                          jnp.bfloat16)[0] == (
        "flash_fwd[1024x1024,causal,2048x2048]")
    # the new cell's two calls: one full layer, three of window 4096
    full = fa.flash_plan(16384, 16384, True, 2048, 2048, 28, 128, 2)
    name, windowed = fa._plan_entry("bwd", 16384, 16384, True, 2048, 2048,
                                    28, 128, jnp.bfloat16, 4096)
    assert name == "flash_bwd[16384x16384,window4096,2048x2048]"
    assert full["tiles"] == windowed["tiles"] == [8, 8]
    assert "window" not in full and "tiles_run" not in full
    assert (windowed["window"], windowed["tiles_run"]) == (4096, 21)
    # the pairs inside the band are 0.4375 of the causal triangle's; as the
    # kernels run them (bands of 256 rows, keys cut at 128 lanes) 0.458
    assert windowed["executed_share"] / full["executed_share"] == \
        pytest.approx(0.458, abs=0.002)
