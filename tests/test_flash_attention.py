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
