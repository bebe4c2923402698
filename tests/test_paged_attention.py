"""Paged (block-table) KV-cache attention tests.

Reference capability: block_multi_head_attention
(phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu). Oracle:
dense softmax attention over the ragged per-sequence history.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def _dense_attn(q, k, v, causal_offset):
    """q (T,H,D), k/v (S,KVH,D) -> (T,H,D) with causal mask at offset."""
    T, H, D = q.shape
    S, KVH, _ = k.shape
    g = H // KVH
    qg = q.reshape(T, KVH, g, D).astype(np.float64)
    s = np.einsum("tkgd,skd->tkgs", qg, k.astype(np.float64)) / np.sqrt(D)
    jpos = np.arange(S)[None, None, None, :]
    qpos = (causal_offset + np.arange(T)).reshape(T, 1, 1, 1)
    s = np.where(jpos <= qpos, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("tkgs,skd->tkgd", p, v.astype(np.float64)).reshape(
        T, H, D)


def _build_cache(rng, lens, bs, H, KVH, D, max_blocks, shuffle=True):
    """Random ragged KV histories scattered into a paged cache."""
    B = len(lens)
    nb = B * max_blocks + 3
    kc = np.zeros((nb, bs, KVH, D), np.float32)
    vc = np.zeros((nb, bs, KVH, D), np.float32)
    ids = np.arange(1, nb)  # keep block 0 unused to catch indexing bugs
    if shuffle:
        rng.shuffle(ids)
    tables = np.zeros((B, max_blocks), np.int32)
    ks, vs = [], []
    pos = 0
    for b in range(B):
        kseq = rng.randn(lens[b], KVH, D).astype(np.float32)
        vseq = rng.randn(lens[b], KVH, D).astype(np.float32)
        ks.append(kseq)
        vs.append(vseq)
        for blk_i in range(max_blocks):
            tables[b, blk_i] = ids[pos]
            lo = blk_i * bs
            chunk = kseq[lo:lo + bs]
            kc[ids[pos], :chunk.shape[0]] = chunk
            vc[ids[pos], :chunk.shape[0]] = vseq[lo:lo + bs]
            pos += 1
    return kc, vc, tables, ks, vs


class TestPagedAttention:
    def test_decode_matches_dense(self):
        rng = np.random.RandomState(0)
        B, H, KVH, D, bs, mb = 3, 4, 4, 16, 8, 4
        lens = [5, 17, 32]
        kc, vc, tables, ks, vs = _build_cache(rng, [l - 1 for l in lens],
                                              bs, H, KVH, D, mb)
        q = rng.randn(B, 1, H, D).astype(np.float32)
        nk = rng.randn(B, 1, KVH, D).astype(np.float32)
        nv = rng.randn(B, 1, KVH, D).astype(np.float32)
        out, kc2, vc2 = F.block_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables), paddle.to_tensor(np.asarray(lens)),
            new_k=paddle.to_tensor(nk), new_v=paddle.to_tensor(nv))
        for b in range(B):
            k_full = np.concatenate([ks[b], nk[b]], axis=0)
            v_full = np.concatenate([vs[b], nv[b]], axis=0)
            ref = _dense_attn(q[b], k_full, v_full, lens[b] - 1)
            np.testing.assert_allclose(out.numpy()[b], ref, atol=2e-5)

    def test_gqa_heads(self):
        rng = np.random.RandomState(1)
        B, H, KVH, D, bs, mb = 2, 8, 2, 8, 4, 3
        lens = [6, 11]
        kc, vc, tables, ks, vs = _build_cache(rng, lens, bs, H, KVH, D, mb)
        q = rng.randn(B, 1, H, D).astype(np.float32)
        out, _, _ = F.block_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables), paddle.to_tensor(np.asarray(lens)))
        for b in range(B):
            ref = _dense_attn(q[b], ks[b][:lens[b]], vs[b][:lens[b]],
                              lens[b] - 1)
            np.testing.assert_allclose(out.numpy()[b], ref, atol=2e-5)

    def test_chunked_prefill_causal(self):
        # T=4 new tokens appended to a 6-token history; each new token must
        # only see history + itself/earlier new tokens
        rng = np.random.RandomState(2)
        B, H, KVH, D, bs, mb = 1, 2, 2, 8, 4, 4
        hist = 6
        T = 4
        kc, vc, tables, ks, vs = _build_cache(rng, [hist], bs, H, KVH, D, mb)
        q = rng.randn(B, T, H, D).astype(np.float32)
        nk = rng.randn(B, T, KVH, D).astype(np.float32)
        nv = rng.randn(B, T, KVH, D).astype(np.float32)
        out, kc2, vc2 = F.block_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables),
            paddle.to_tensor(np.asarray([hist + T])),
            new_k=paddle.to_tensor(nk), new_v=paddle.to_tensor(nv))
        k_full = np.concatenate([ks[0], nk[0]], axis=0)
        v_full = np.concatenate([vs[0], nv[0]], axis=0)
        ref = _dense_attn(q[0], k_full, v_full, hist)
        np.testing.assert_allclose(out.numpy()[0], ref, atol=2e-5)

    def test_cache_write_positions(self):
        # new KV must land exactly at [len-T, len) in logical order
        rng = np.random.RandomState(3)
        B, H, KVH, D, bs, mb = 1, 2, 2, 4, 4, 3
        kc, vc, tables, ks, vs = _build_cache(rng, [5], bs, H, KVH, D, mb,
                                              shuffle=True)
        nk = np.full((1, 2, KVH, D), 7.0, np.float32)
        nv = np.full((1, 2, KVH, D), 9.0, np.float32)
        q = rng.randn(1, 2, H, D).astype(np.float32)
        _, kc2, vc2 = F.block_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables), paddle.to_tensor(np.asarray([7])),
            new_k=paddle.to_tensor(nk), new_v=paddle.to_tensor(nv))
        kc2 = kc2.numpy()
        # logical positions 5, 6 -> block idx 1, offsets 1, 2
        blk = tables[0, 1]
        np.testing.assert_allclose(kc2[blk, 1], 7.0)
        np.testing.assert_allclose(kc2[blk, 2], 7.0)
        # history untouched
        np.testing.assert_allclose(kc2[tables[0, 0]], kc[tables[0, 0]])

    def test_jitted_decode_loop_matches_full_context(self):
        """Greedy paged decode step-by-step == one dense pass (serving
        steady state: the step jits once, caches donated)."""
        import jax
        rng = np.random.RandomState(4)
        H, KVH, D, bs, mb = 2, 2, 8, 4, 4
        S = 10
        ks = rng.randn(S, KVH, D).astype(np.float32)
        vs = rng.randn(S, KVH, D).astype(np.float32)
        qs = rng.randn(S, H, D).astype(np.float32)
        kc = np.zeros((mb + 1, bs, KVH, D), np.float32)
        vc = np.zeros_like(kc)
        tables = np.arange(1, mb + 1, dtype=np.int32)[None]

        kc_t, vc_t = paddle.to_tensor(kc), paddle.to_tensor(vc)
        outs = []
        for t in range(S):
            out, kc_t, vc_t = F.block_multihead_attention(
                paddle.to_tensor(qs[None, t:t + 1]), kc_t, vc_t,
                paddle.to_tensor(tables),
                paddle.to_tensor(np.asarray([t + 1])),
                new_k=paddle.to_tensor(ks[None, t:t + 1]),
                new_v=paddle.to_tensor(vs[None, t:t + 1]))
            outs.append(out.numpy()[0, 0])
        stepped = np.stack(outs)
        ref = _dense_attn(qs, ks, vs, 0)
        np.testing.assert_allclose(stepped, ref, atol=2e-5)

    def test_padded_row_no_corruption_and_zero_output(self):
        # seq_len=0 row with new KV of T=1... pos=-1 must NOT wrap into a
        # live block; its output must be 0, not NaN
        rng = np.random.RandomState(5)
        H, KVH, D, bs, mb = 2, 2, 4, 4, 2
        kc = rng.randn(5, bs, KVH, D).astype(np.float32)
        vc = rng.randn(5, bs, KVH, D).astype(np.float32)
        tables = np.array([[1, 2], [3, 4]], np.int32)
        lens = np.array([0, 3])  # row 0 is padding
        q = rng.randn(2, 1, H, D).astype(np.float32)
        nk = np.full((2, 1, KVH, D), 55.0, np.float32)
        nv = np.full((2, 1, KVH, D), 66.0, np.float32)
        out, kc2, vc2 = F.block_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables), paddle.to_tensor(lens),
            new_k=paddle.to_tensor(nk), new_v=paddle.to_tensor(nv))
        o = out.numpy()
        assert np.isfinite(o).all()
        np.testing.assert_allclose(o[0], 0.0)          # padded row -> 0
        kc2 = kc2.numpy()
        # row 1 wrote at logical pos 2 -> block 3 offset 2
        np.testing.assert_allclose(kc2[3, 2], 55.0)
        # no other slot of any block got the 55 write (no wrap into
        # blocks 1/2/4 from the padded row)
        mask = np.ones_like(kc2, bool)
        mask[3, 2] = False
        assert not np.any(kc2[mask] == 55.0)

    def test_tensor_parallel_paged_decode(self):
        """Serving composition: KV-cache heads sharded over the mp axis,
        one jitted decode step with sharded caches (the multi-chip
        serving layout), parity vs the unsharded computation."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import mesh as mesh_mod

        if len(jax.devices()) < 4:
            pytest.skip("needs >= 4 devices")
        mesh = mesh_mod.build_mesh({"dp": 2, "mp": 2},
                                   devices=jax.devices()[:4])
        # save WITHOUT the lazy-create side effect of get_mesh()
        prev = mesh_mod._global_mesh
        mesh_mod.set_mesh(mesh)
        try:
            rng = np.random.RandomState(7)
            B, H, KVH, D, bs, mb = 2, 4, 4, 8, 4, 3
            lens = np.array([5, 9])
            kc, vc, tables, ks, vs = _build_cache(rng, lens, bs, H, KVH,
                                                  D, mb)
            q = rng.randn(B, 1, H, D).astype(np.float32)
            # reference (unsharded) output
            ref, _, _ = F.block_multihead_attention(
                paddle.to_tensor(q), paddle.to_tensor(kc),
                paddle.to_tensor(vc), paddle.to_tensor(tables),
                paddle.to_tensor(lens))
            # shard caches + queries over mp (head axis), batch over dp
            kv_sh = NamedSharding(mesh, P(None, None, "mp", None))
            q_sh = NamedSharding(mesh, P("dp", None, "mp", None))
            kc_d = jax.device_put(kc, kv_sh)
            vc_d = jax.device_put(vc, kv_sh)
            q_d = jax.device_put(q, q_sh)
            out, _, _ = F.block_multihead_attention(
                paddle.Tensor(q_d), paddle.Tensor(kc_d),
                paddle.Tensor(vc_d), paddle.to_tensor(tables),
                paddle.to_tensor(lens))
            np.testing.assert_allclose(out.numpy(), ref.numpy(),
                                       atol=2e-5)
        finally:
            mesh_mod.set_mesh(prev)  # restore exactly, including None


# --------------------------------------------------------------------------
# The decode kernel (ops/pallas/paged_attention.py) through the Pallas
# interpreter: the same public function, T = 1 with new_k / new_v, shapes the
# kernel was written for (D = 128, block 16).
# --------------------------------------------------------------------------
from paddle_tpu.ops.pallas import paged_attention as PK  # noqa: E402

# (H, KVH): Mistral's grouping (group 4), the hybrid's (group 16), a group
# of ONE (multi-head attention: the Olmo-Hybrid adapter's 30 heads a side,
# padded to a whole tile of 32 in the pages) and a head count that is no
# whole sublane tile (6: the query block is padded to 8 or 16, the pad rows
# see no K/V row and are never written out)
GROUPINGS = [pytest.param((32, 8), id="g4-kvh8"),
             pytest.param((32, 2), id="g16-kvh2"),
             pytest.param((32, 32), id="g1-kvh32"),
             pytest.param((6, 2), id="g3-h6")]


@pytest.fixture
def kernel_on(monkeypatch):
    monkeypatch.setattr(PK, "INTERPRET", True)


def _decode(q, kc, vc, tables, lens, nk, nv, dtype="float32", **kw):
    """One write-path decode call; numpy float32 back."""
    t = lambda a: paddle.to_tensor(np.asarray(a)).astype(dtype)  # noqa: E731
    out, kc2, vc2 = F.block_multihead_attention(
        t(q), t(kc), t(vc), paddle.to_tensor(tables),
        paddle.to_tensor(np.asarray(lens, np.int32)),
        new_k=t(nk), new_v=t(nv), **kw)
    return tuple(np.asarray(x.astype("float32").numpy())
                 for x in (out, kc2, vc2))


def _runs_kernel(fn, *args) -> bool:
    """What ``fn``'s one attention call is lowered to for these arguments."""
    import jax
    from paddle_tpu.nn.functional.paged_attention import log_paths
    with log_paths() as seen:
        jax.jit(fn).lower(*args)
    assert len(seen) == 1, seen
    return seen[0] == "kernel"


def _decode_case(rng, lens, H, KVH, mb, D=128, bs=16):
    """Histories of ``len - 1`` tokens in shuffled pages plus the step's new
    token; returns the call's inputs and each lane's full K / V."""
    hist = [max(l - 1, 0) for l in lens]
    kc, vc, tables, ks, vs = _build_cache(rng, hist, bs, H, KVH, D, mb)
    B = len(lens)
    q = rng.randn(B, 1, H, D).astype(np.float32)
    nk = rng.randn(B, 1, KVH, D).astype(np.float32)
    nv = rng.randn(B, 1, KVH, D).astype(np.float32)
    full = [(np.concatenate([ks[b], nk[b]]), np.concatenate([vs[b], nv[b]]))
            for b in range(B)]
    return q, kc, vc, tables, nk, nv, full


class TestDecodeKernel:
    @pytest.mark.parametrize("dtype,atol", [("float32", 2e-5),
                                            ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("heads", GROUPINGS)
    def test_matches_composite_and_dense(self, monkeypatch, heads, dtype,
                                         atol):
        """Lanes of length 1, exactly one block, one block + 1, the whole
        table, 0 (the sentinel of mid-prefill and stalled lanes) and a
        length that ends mid-chunk."""
        H, KVH = heads
        mb, bs = 12, 16
        lens = [1, bs, bs + 1, mb * bs, 0, 150]
        q, kc, vc, tables, nk, nv, full = _decode_case(
            np.random.RandomState(10), lens, H, KVH, mb)
        if dtype == "bfloat16":     # the values the pools really hold
            import jax.numpy as jnp
            r = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16)  # noqa: E731
                                     .astype(jnp.float32))
            q, kc, vc, nk, nv = map(r, (q, kc, vc, nk, nv))
            full = [(r(k), r(v)) for k, v in full]
        want = _decode(q, kc, vc, tables, lens, nk, nv, dtype)
        monkeypatch.setattr(PK, "INTERPRET", True)
        got = _decode(q, kc, vc, tables, lens, nk, nv, dtype)
        # pools bit for bit what the composite leaves, the sentinel lane's
        # blocks untouched
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_array_equal(got[2], want[2])
        np.testing.assert_array_equal(got[1][tables[4]], kc[tables[4]])
        np.testing.assert_allclose(got[0], want[0], atol=atol)
        np.testing.assert_array_equal(got[0][4], 0.0)
        for b, L in enumerate(lens):
            if L:
                ref = _dense_attn(q[b], full[b][0], full[b][1], L - 1)
                np.testing.assert_allclose(got[0][b], ref, atol=atol)

    @pytest.mark.parametrize("heads", GROUPINGS)
    def test_entries_past_the_length_are_never_read(self, kernel_on, heads):
        """Table entries past a lane's pages point at another lane's live
        blocks and at a block of NaN; the sentinel lane's whole table does:
        neither may reach an output (0 x NaN is NaN)."""
        H, KVH = heads
        mb, bs = 6, 16
        lens = [20, 70, 0]
        q, kc, vc, tables, nk, nv, full = _decode_case(
            np.random.RandomState(11), lens, H, KVH, mb)
        poison = max(set(range(kc.shape[0])) - set(tables.ravel().tolist()))
        kc[poison] = vc[poison] = np.nan
        tables[0, 2:] = [tables[1, 0], tables[1, 1], poison, poison]
        tables[1, 5] = poison
        tables[2, :] = poison
        out, _, _ = _decode(q, kc, vc, tables, lens, nk, nv)
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[2], 0.0)
        for b in (0, 1):
            ref = _dense_attn(q[b], full[b][0], full[b][1], lens[b] - 1)
            np.testing.assert_allclose(out[b], ref, atol=2e-5)

    @pytest.mark.parametrize("heads", GROUPINGS)
    def test_new_token_is_visible_to_its_own_query(self, kernel_on, heads):
        """A lane of length 1 attends to nothing but the token this call
        writes: each query head gets its kv head's new V back."""
        H, KVH = heads
        q, kc, vc, tables, nk, nv, _ = _decode_case(
            np.random.RandomState(12), [1, 1], H, KVH, 2)
        out, _, _ = _decode(q, kc, vc, tables, [1, 1], nk, nv)
        want = np.repeat(nv[:, 0], H // KVH, axis=1).reshape(2, 1, H, 128)
        np.testing.assert_allclose(out, want, atol=1e-6)

    @pytest.mark.parametrize("heads", GROUPINGS)
    def test_jitted_decode_loop_matches_full_context(self, kernel_on, heads):
        """``test_jitted_decode_loop_matches_full_context`` with the kernel
        on: one jitted step, pools donated, a token a call across two page
        boundaries."""
        import jax
        rng = np.random.RandomState(13)
        H, KVH = heads
        D, bs, mb, S = 128, 16, 3, 35
        ks = rng.randn(S, KVH, D).astype(np.float32)
        vs = rng.randn(S, KVH, D).astype(np.float32)
        qs = rng.randn(S, H, D).astype(np.float32)
        tables = np.arange(1, mb + 1, dtype=np.int32)[None]

        def step(q, kc, vc, n, nk, nv):
            out, kc, vc = F.block_multihead_attention(
                paddle.Tensor(q), paddle.Tensor(kc), paddle.Tensor(vc),
                paddle.to_tensor(tables), paddle.Tensor(n),
                new_k=paddle.Tensor(nk), new_v=paddle.Tensor(nv))
            return out._data, kc._data, vc._data

        kc = np.zeros((mb + 1, bs, KVH, D), np.float32)
        assert _runs_kernel(step, qs[None, :1], kc, kc, np.asarray([1]),
                            ks[None, :1], vs[None, :1])
        jitted = jax.jit(step, donate_argnums=(1, 2))
        kc_d, vc_d = jax.numpy.asarray(kc), jax.numpy.asarray(kc)
        outs = []
        for t in range(S):
            out, kc_d, vc_d = jitted(qs[None, t:t + 1], kc_d, vc_d,
                                     np.asarray([t + 1], np.int32),
                                     ks[None, t:t + 1], vs[None, t:t + 1])
            outs.append(np.asarray(out)[0, 0])
        np.testing.assert_allclose(np.stack(outs), _dense_attn(qs, ks, vs, 0),
                                   atol=2e-5)

    @pytest.mark.parametrize("case,kernel", [
        ("decode", True), ("decode-bf16", True),
        ("verify-t2", False), ("int8-pages", False), ("read-only", False),
        ("head-dim-64", False), ("block-4", False),
        ("pools-of-another-dtype", False), ("no-tpu-no-interpreter", False),
        ("pallas-kernels-off", False), ("pools-over-two-devices", False),
        ("manual-over-two-devices", True), ("group-of-one", True),
        ("heads-no-whole-tile", True), ("kv-heads-no-whole-tile", False)])
    def test_dispatch_rule(self, monkeypatch, case, kernel):
        """What the call can see decides: its shapes, dtypes and backend,
        and, where it is lowered, whether the compiler would have to
        partition it (it cannot partition a Mosaic kernel)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core import flags

        if case != "no-tpu-no-interpreter":
            monkeypatch.setattr(PK, "INTERPRET", True)
        B, T, H, KVH, D, bs, mb = 2, 1, 32, 8, 128, 16, 2
        dt = pool_dt = jnp.float32
        kw = {}
        if case == "decode-bf16":
            dt = pool_dt = jnp.bfloat16
        elif case == "verify-t2":
            T = 2
        elif case == "head-dim-64":
            D = 64
        elif case == "block-4":
            bs, KVH = 4, 1              # a page of 4 rows: not a whole tile
        elif case == "pools-of-another-dtype":
            pool_dt = jnp.bfloat16
        elif case == "pallas-kernels-off":
            flags.set_flags({"use_pallas_kernels": False})
        elif case == "group-of-one":
            H = KVH = 16
        elif case == "heads-no-whole-tile":
            H, KVH = 30, 2              # the query block is padded to 32
        elif case == "kv-heads-no-whole-tile":
            # 30 K/V heads are laid out in 32: reading the pool as page rows
            # would copy it every step, so the caller pads such a pool
            H = KVH = 30
        nb = B * mb + 1
        q = jnp.ones((B, T, H, D), dt)
        kc = jnp.zeros((nb, bs, KVH, D), pool_dt)
        new = jnp.ones((B, T, KVH, D), pool_dt)
        tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(B, mb)
        lens = jnp.asarray([5, 9], jnp.int32)
        if case == "int8-pages":
            kc = kc.astype(jnp.int8)
            scales = jnp.ones((nb, bs, KVH), jnp.float32)
            kw = dict(k_scale=paddle.Tensor(scales),
                      v_scale=paddle.Tensor(scales))
        if case.endswith("over-two-devices"):
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
            heads = P(None, None, "mp")
            # a sharding on an argument is all the caller's jit shows; the
            # tracers inside carry none
            kc = jax.device_put(kc, NamedSharding(mesh, heads))

        def call(q, kc, new):
            news = {} if case == "read-only" else dict(
                new_k=paddle.Tensor(new), new_v=paddle.Tensor(new))
            return F.block_multihead_attention(
                paddle.Tensor(q), paddle.Tensor(kc), paddle.Tensor(kc),
                paddle.Tensor(tables), paddle.Tensor(lens), **news,
                **kw)[0]._data

        if case == "manual-over-two-devices":
            # the way to run the kernel over a mesh: a region that is manual
            # over every axis, each device on the heads it holds
            from paddle_tpu.distributed.shard_map_compat import shard_map
            call = shard_map(call, mesh, (heads, heads, heads), heads)
        try:
            assert _runs_kernel(call, q, kc, new) is kernel
        finally:
            if case == "pallas-kernels-off":
                flags.set_flags({"use_pallas_kernels": True})

    @pytest.mark.parametrize("dtype,atol", [("float32", 2e-5),
                                            ("bfloat16", 2e-2)])
    def test_thirty_heads_a_side_through_pages_of_thirty_two(
            self, monkeypatch, dtype, atol):
        """Multi-head attention at 30 query and 30 K/V heads: 30 K/V heads
        are no whole sublane tile, so the caller keeps pages of 32 (two of
        zeros, q's pad heads zeros too) and drops the pad heads' output. The
        kernel over the padded pools equals the composite over the 30-head
        pools it stands for."""
        H, pad, mb = 30, 2, 6
        lens = [1, 16, 17, 0, 70]
        q, kc, vc, tables, nk, nv, _ = _decode_case(
            np.random.RandomState(15), lens, H, H, mb)
        want = _decode(q, kc, vc, tables, lens, nk, nv, dtype)[0]
        wider = lambda a: np.pad(  # noqa: E731
            a, [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, 0)])
        monkeypatch.setattr(PK, "INTERPRET", True)
        args = [wider(a) for a in (q, kc, vc)] + [tables, lens] \
            + [wider(a) for a in (nk, nv)]
        import jax.numpy as jnp
        assert PK.supports((5, 1, 32, 128), jnp.dtype(dtype),
                           (kc.shape[0], 16, 32, 128), jnp.dtype(dtype))
        assert not PK.supports((5, 1, 30, 128), jnp.dtype(dtype),
                               (kc.shape[0], 16, 30, 128), jnp.dtype(dtype))
        got = _decode(*args, dtype)[0]
        np.testing.assert_allclose(got[:, :, :H], want, atol=atol)
        np.testing.assert_array_equal(got[3], 0.0)

    def test_differentiated_call_gets_the_composites_gradient(
            self, monkeypatch):
        """The kernel carries the composite's gradient: a caller that
        backpropagates through a write-path decode call gets what it got."""
        rng = np.random.RandomState(14)
        lens = [33, 7]
        q, kc, vc, tables, nk, nv, _ = _decode_case(rng, lens, 32, 8, 3)
        w = rng.randn(*q.shape).astype(np.float32)

        def grads():
            qt, nkt, nvt = (paddle.to_tensor(a, stop_gradient=False)
                            for a in (q, nk, nv))
            out, _, _ = F.block_multihead_attention(
                qt, paddle.to_tensor(kc), paddle.to_tensor(vc),
                paddle.to_tensor(tables),
                paddle.to_tensor(np.asarray(lens, np.int32)),
                new_k=nkt, new_v=nvt)
            (out * paddle.to_tensor(w)).sum().backward()
            return [np.asarray(t.grad.numpy()) for t in (qt, nkt, nvt)]

        want = grads()
        monkeypatch.setattr(PK, "INTERPRET", True)
        got = grads()
        for g, ref in zip(got, want):
            assert np.abs(ref).max() > 0
            np.testing.assert_allclose(g, ref, atol=1e-5)


def test_kernel_modules_import_pallas_without_the_gpu_interpreter():
    """``ops.pallas.import_pallas`` in a fresh process (the test process may
    hold Pallas already): every kernel module brings Pallas in without
    Mosaic GPU, no blocked entry stays behind, a kernel runs, and a process
    that holds Pallas already gets it as it is."""
    import os
    import subprocess
    import sys
    code = (
        "import sys\n"
        "import jax.numpy as jnp\n"
        "from paddle_tpu.ops.pallas import (flash_attention, fused_ops,\n"
        "                                   import_pallas)\n"
        "from paddle_tpu.ops.pallas import paged_attention as pk\n"
        "assert 'jax.experimental.mosaic.gpu' not in sys.modules\n"
        "assert 'jax._src.pallas.mosaic_gpu.interpret' not in sys.modules\n"
        "assert import_pallas() == (pk.pl, pk.pltpu)\n"
        "assert flash_attention.pl is fused_ops.pl is pk.pl\n"
        "pk.INTERPRET = True\n"
        "q = jnp.ones((1, 1, 8, 128)); kc = jnp.ones((3, 8, 1, 128))\n"
        "out = pk.paged_decode_attention(\n"
        "    q, kc, kc, jnp.asarray([[1, 2]], jnp.int32),\n"
        "    jnp.asarray([9], jnp.int32))\n"
        "assert float(abs(out - 1).max()) < 1e-6\n"
        "import jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root))
    assert done.returncode == 0, done.stderr[-2000:]


# ------------------------------------------------- a table 18 432 tokens wide
def test_kernel_walks_a_table_as_wide_as_the_longest_context(kernel_on):
    """The K-EXAONE cell's block table, 1152 entries of 16 tokens a lane (a
    scalar-prefetch operand 72 times the Mistral cells'): a lane that fills
    it, one that ends mid-page deep inside it, the sentinel and a lane of
    one token, against dense attention."""
    H, KVH, mb, bs = 16, 2, 1152, 16
    lens = [mb * bs, 4097, 0, 1]
    q, kc, vc, tables, nk, nv, full = _decode_case(
        np.random.RandomState(12), lens, H, KVH, mb)
    import jax.numpy as jnp
    args = [jnp.asarray(a) for a in (q, kc, vc, tables,
                                     np.asarray(lens, np.int32), nk, nv)]
    assert _runs_kernel(
        lambda *a: F.block_multihead_attention(
            *map(paddle.Tensor, a[:5]), new_k=paddle.Tensor(a[5]),
            new_v=paddle.Tensor(a[6]))[0]._data, *args)
    got = _decode(q, kc, vc, tables, lens, nk, nv)
    np.testing.assert_array_equal(got[0][2], 0.0)
    for b, L in enumerate(lens):
        if L:
            ref = _dense_attn(q[b], full[b][0], full[b][1], L - 1)
            np.testing.assert_allclose(got[0][b], ref, atol=2e-5)


# ------------------------------------------------------ blockwise composite
class TestBlockwiseComposite:
    """The composite taken over page groups with an online softmax against
    the composite over the whole table (``_gather_attend``), on shapes both
    take. Tolerance 2e-5 on outputs of order 1: float32 sums in another
    order."""

    @pytest.mark.parametrize("T", [1, 5, 16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_is_the_composite(self, monkeypatch, T, causal):
        import jax.numpy as jnp
        from paddle_tpu.nn.functional import paged_attention as fpa
        # groups of 2 pages over a table of 7: the last group is padded
        monkeypatch.setattr(fpa, "BLOCKWISE_GROUP_TOKENS", 16)
        rng = np.random.RandomState(20)
        H, KVH, D, bs, mb = 4, 2, 16, 8, 7
        # one token, a length inside the first group, group edges, the
        # whole table, fewer tokens than the chunk (padded rows), none
        lens = [1, 9, 16, 17, mb * bs, max(T - 2, 0), 0]
        kc, vc, tables, _ks, _vs = _build_cache(rng, [mb * bs] * len(lens),
                                                bs, H, KVH, D, mb)
        q = rng.randn(len(lens), T, H, D).astype(np.float32)
        args = (jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                jnp.asarray(tables), jnp.asarray(lens, jnp.int32))
        want = fpa._gather_attend(*args, None, None, causal, 0.25)
        got = fpa._blockwise_attend(*args, causal, 0.25)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert np.isfinite(np.asarray(got)).all()
        if T > 2 and causal:    # a row before the first token: zeros
            np.testing.assert_array_equal(np.asarray(got)[5, :2], 0.0)

    def test_a_wide_table_takes_it_and_a_narrow_one_does_not(
            self, monkeypatch):
        """The write path of a table that spans more than
        ``BLOCKWISE_FROM`` tokens never builds scores over the table; a
        narrower one, int8 pages and read-only attention keep the
        composite. Both against dense attention."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.nn.functional import paged_attention as fpa
        calls = []
        inner = fpa._blockwise_rows
        monkeypatch.setattr(
            fpa, "_blockwise_rows",
            lambda *a: (calls.append(a[3].shape), inner(*a))[1])
        rng = np.random.RandomState(21)
        H, KVH, D, bs, T = 4, 2, 16, 8, 6
        for mb, wide in ((fpa.BLOCKWISE_FROM // bs + 1, True), (6, False)):
            lens = [T, 20 + T, mb * bs if not wide else 300]
            hist = [n - T for n in lens]
            kc, vc, tables, ks, vs = _build_cache(rng, hist, bs, H, KVH, D,
                                                  mb)
            q = rng.randn(3, T, H, D).astype(np.float32)
            nk = rng.randn(3, T, KVH, D).astype(np.float32)
            nv = rng.randn(3, T, KVH, D).astype(np.float32)
            out, _kc, _vc = F.block_multihead_attention(
                *map(paddle.to_tensor, (q, kc, vc, tables)),
                paddle.to_tensor(np.asarray(lens, np.int32)),
                new_k=paddle.to_tensor(nk), new_v=paddle.to_tensor(nv))
            assert bool(calls) == wide
            for b, n in enumerate(lens):
                ref = _dense_attn(q[b], np.concatenate([ks[b], nk[b]]),
                                  np.concatenate([vs[b], nv[b]]), n - T)
                np.testing.assert_allclose(out.numpy()[b], ref, atol=2e-5)
            if wide:        # read-only attention stays differentiable
                calls.clear()
                qt = paddle.to_tensor(q)
                qt.stop_gradient = False
                out, _kc, _vc = F.block_multihead_attention(
                    qt, *map(paddle.to_tensor, (kc, vc, tables)),
                    paddle.to_tensor(np.asarray(hist, np.int32)))
                out.sum().backward()
                assert not calls and np.isfinite(qt.grad.numpy()).all()
        # differentiated, the blockwise path is the composite's rule
        args = (jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                jnp.asarray(tables), jnp.asarray(lens, jnp.int32))
        g = jax.grad(lambda qa: fpa._blockwise_attend(
            qa, *args[1:], True, 0.25).sum())(args[0])
        w = jax.grad(lambda qa: fpa._gather_attend(
            qa, *args[1:], None, None, True, 0.25).sum())(args[0])
        np.testing.assert_allclose(g, w, atol=1e-5)


# ------------------------------------------------------------- window rows
def _window_dense(q, k, v, first, window):
    """q (T,H,D) at positions first.., k/v (S,KVH,D) at positions 0..:
    key j is seen from query i iff 0 <= i - j < window."""
    T, H, D = q.shape
    S, KVH, _ = k.shape
    qg = q.reshape(T, KVH, H // KVH, D).astype(np.float64)
    s = np.einsum("tkgd,skd->tkgs", qg, k.astype(np.float64)) / np.sqrt(D)
    back = (first + np.arange(T))[:, None] - np.arange(S)[None, :]
    seen = ((back >= 0) & (back < window))[:, None, None, :]
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("tkgs,skd->tkgd", p, v.astype(np.float64)).reshape(
        T, H, D)


class TestWindowRing:
    """``window_ring_attention``: rows written round-robin by position,
    attended under ``0 <= i - j < window``, against dense attention over
    the whole history under the same mask."""

    H, KVH, D, WINDOW = 4, 2, 16, 128

    def _feed(self, rng, total, chunk, rows, lens_of=None):
        """Feed ``total`` tokens in chunks of ``chunk`` through a ring of
        ``rows`` rows; returns the outputs by position and the history."""
        H, KVH, D = self.H, self.KVH, self.D
        k = rng.randn(total, KVH, D).astype(np.float32)
        v = rng.randn(total, KVH, D).astype(np.float32)
        q = rng.randn(total, H, D).astype(np.float32)
        kr = paddle.to_tensor(np.full((1, rows, KVH, D), 7.0, np.float32))
        vr = paddle.to_tensor(np.full((1, rows, KVH, D), 7.0, np.float32))
        outs = []
        for lo in range(0, total, chunk):
            hi = lo + chunk
            out, kr, vr = F.window_ring_attention(
                paddle.to_tensor(q[None, lo:hi]), kr, vr,
                paddle.to_tensor(np.asarray([hi], np.int32)),
                paddle.to_tensor(k[None, lo:hi]),
                paddle.to_tensor(v[None, lo:hi]), window=self.WINDOW)
            outs.append(out.numpy()[0])
        return np.concatenate(outs), q, k, v

    @pytest.mark.parametrize("chunk,rows", [(1, 128), (64, 191), (64, 192),
                                            (100, 384)])
    def test_matches_dense_under_the_window_mask(self, chunk, rows):
        """Decode steps over a ring of exactly a window, chunks over the
        smallest ring that holds them (window + chunk - 1) and larger
        ones; about 400 tokens, so the rows wrap. The ring starts full of
        7s: a row the sequence did not write is never seen."""
        total = chunk * (400 // chunk)
        got, q, k, v = self._feed(np.random.RandomState(30), total, chunk,
                                  rows)
        want = _window_dense(q, k, v, 0, self.WINDOW)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_the_window_ends_between_127_and_128_back(self):
        """Query i sees key i - 127 and not key i - 128: changing V at
        either shows, or does not, in query i's output."""
        rng = np.random.RandomState(31)
        H, KVH, D = self.H, self.KVH, self.D
        i, rows = 300, 192
        k = rng.randn(i + 1, KVH, D).astype(np.float32)
        v = rng.randn(i + 1, KVH, D).astype(np.float32)
        q = rng.randn(1, 1, H, D).astype(np.float32)

        def last_output(values):
            kr = paddle.to_tensor(np.zeros((1, rows, KVH, D), np.float32))
            vr = paddle.to_tensor(np.zeros((1, rows, KVH, D), np.float32))
            for lo in range(0, i, 60):         # the history, in chunks
                hi = lo + 60
                _o, kr, vr = F.window_ring_attention(
                    paddle.to_tensor(np.zeros((1, 60, H, D), np.float32)),
                    kr, vr, paddle.to_tensor(np.asarray([hi], np.int32)),
                    paddle.to_tensor(k[None, lo:hi]),
                    paddle.to_tensor(values[None, lo:hi]),
                    window=self.WINDOW)
            out, _k, _v = F.window_ring_attention(
                paddle.to_tensor(q), kr, vr,
                paddle.to_tensor(np.asarray([i + 1], np.int32)),
                paddle.to_tensor(k[None, i:]),
                paddle.to_tensor(values[None, i:]), window=self.WINDOW)
            return out.numpy()

        base = last_output(v)
        for back, shows in ((127, True), (128, False), (0, True)):
            moved = v.copy()
            moved[i - back] += 50.0
            assert (np.abs(last_output(moved) - base).max() > 1e-3) == shows

    def test_padding_and_sentinel_lanes_write_nothing(self):
        """A first chunk left-padded to its width writes only its real
        rows and yields zeros for the padding; a lane under the ``seq = 0``
        sentinel gets its rows back bit for bit and yields zeros."""
        rng = np.random.RandomState(32)
        H, KVH, D, T, rows = self.H, self.KVH, self.D, 16, 160
        before = rng.randn(2, rows, KVH, D).astype(np.float32)
        q = rng.randn(2, T, H, D).astype(np.float32)
        nk = rng.randn(2, T, KVH, D).astype(np.float32)
        nv = rng.randn(2, T, KVH, D).astype(np.float32)
        out, kr, vr = F.window_ring_attention(
            *map(paddle.to_tensor, (q, before, before)),
            paddle.to_tensor(np.asarray([5, 0], np.int32)),   # 11 padded
            paddle.to_tensor(nk), paddle.to_tensor(nv), window=self.WINDOW)
        kr, vr, out = kr.numpy(), vr.numpy(), out.numpy()
        np.testing.assert_array_equal(kr[1], before[1])
        np.testing.assert_array_equal(vr[1], before[1])
        np.testing.assert_array_equal(kr[0, 5:], before[0, 5:])
        np.testing.assert_array_equal(kr[0, :5], nk[0, 11:])
        np.testing.assert_array_equal(out[1], 0.0)
        np.testing.assert_array_equal(out[0, :11], 0.0)
        want = _window_dense(q[0, 11:], nk[0, 11:], nv[0, 11:], 0,
                             self.WINDOW)
        np.testing.assert_allclose(out[0, 11:], want, atol=2e-5)

    def test_a_ring_too_small_for_the_chunk_is_refused(self):
        z = lambda *s: paddle.to_tensor(np.zeros(s, np.float32))  # noqa: E731
        with pytest.raises(ValueError, match="cannot hold a window"):
            F.window_ring_attention(
                z(1, 64, 4, 16), z(1, 190, 2, 16), z(1, 190, 2, 16),
                paddle.to_tensor(np.asarray([64], np.int32)),
                z(1, 64, 2, 16), z(1, 64, 2, 16), window=128)


# ------------------------------------------------------------- latent pages
def _latent_dense(q, rows, dv, scale, first):
    """q (T, H, D) at positions first.., rows (S, D): every head attends
    the one K/V head whose keys are the rows and whose values are their
    first ``dv`` columns, causally; float64."""
    T = q.shape[0]
    S = rows.shape[0]
    s = np.einsum("thd,sd->ths", q.astype(np.float64),
                  rows.astype(np.float64)) * scale
    seen = (np.arange(S)[None, :] <= (first + np.arange(T))[:, None])
    s = np.where(seen[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("ths,sc->thc", p, rows[:, :dv].astype(np.float64))


def _latent_case(rng, lens, T, H, D, mb, bs=16):
    """Histories of ``len - T`` rows in shuffled pages of ONE pool plus the
    chunk's new rows; returns the call's inputs and each lane's rows."""
    B = len(lens)
    nb = B * mb + 2
    pool = rng.randn(nb, bs, D).astype(np.float32)
    tables = (rng.permutation(nb - 1)[:B * mb] + 1).reshape(B, mb).astype(
        np.int32)
    new = rng.randn(B, T, D).astype(np.float32)
    q = rng.randn(B, T, H, D).astype(np.float32)
    full = []
    for b, n in enumerate(lens):
        hist = max(n - T, 0)
        rows = pool[tables[b]].reshape(mb * bs, D)[:hist]
        full.append(np.concatenate([rows, new[b, max(T - n, 0):]]))
    return q, pool, tables, new, full


def _latent(q, pool, tables, lens, new, dv, scale, dtype="float32"):
    t = lambda a: paddle.to_tensor(np.asarray(a)).astype(dtype)  # noqa: E731
    out, pool2 = F.latent_paged_attention(
        t(q), t(pool), paddle.to_tensor(tables),
        paddle.to_tensor(np.asarray(lens, np.int32)), t(new), dv, scale)
    return tuple(np.asarray(x.astype("float32").numpy())
                 for x in (out, pool2))


class TestLatentPages:
    """``latent_paged_attention``: one pool whose rows are keys and, in
    their first ``value_dim`` columns, values, under every query head.
    Tolerances as the K/V pages': 2e-5 in float32 (sums in another order);
    2e-2 in bfloat16 on outputs of order 1 (the composite rounds the
    probabilities to bfloat16 once, the kernel splits them in two)."""

    @pytest.mark.parametrize("dtype,atol", [("float32", 2e-5),
                                            ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("dims", [(256, 128), (640, 512)],
                             ids=["row256-v128", "row640-v512"])
    def test_kernel_matches_composite_and_dense(self, monkeypatch, dims,
                                                dtype, atol):
        """Ragged lengths: 1, exactly one block, one block + 1, the whole
        table (a lane at the table's end), 0 (an empty lane: the sentinel
        of mid-prefill and stalled slots) and a length that ends
        mid-chunk."""
        D, dv = dims
        H, mb, bs, scale = 16, 12, 16, 0.07
        lens = [1, bs, bs + 1, mb * bs, 0, 150]
        q, pool, tables, new, full = _latent_case(
            np.random.RandomState(30), lens, 1, H, D, mb)
        if dtype == "bfloat16":     # the values the pool really holds
            import jax.numpy as jnp
            r = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16)  # noqa: E731
                                     .astype(jnp.float32))
            q, pool, new = map(r, (q, pool, new))
            full = [r(rows) for rows in full]
        want = _latent(q, pool, tables, lens, new, dv, scale, dtype)
        monkeypatch.setattr(PK, "INTERPRET", True)
        import jax.numpy as jnp
        assert _runs_kernel(
            lambda *a: F.latent_paged_attention(
                *map(paddle.Tensor, a), dv, scale)[0]._data,
            *[jnp.asarray(a).astype(dtype) for a in (q, pool)],
            jnp.asarray(tables), jnp.asarray(lens, jnp.int32),
            jnp.asarray(new).astype(dtype))
        got = _latent(q, pool, tables, lens, new, dv, scale, dtype)
        assert got[0].shape == (len(lens), 1, H, dv)
        # the pool bit for bit what the composite leaves, the empty lane's
        # blocks untouched
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_array_equal(got[1][tables[4]], pool[tables[4]])
        np.testing.assert_allclose(got[0], want[0], atol=atol)
        np.testing.assert_array_equal(got[0][4], 0.0)
        for b, n in enumerate(lens):
            if n:
                ref = _latent_dense(q[b], full[b], dv, scale, n - 1)
                np.testing.assert_allclose(got[0][b], ref, atol=atol)

    def test_entries_past_the_length_are_never_read(self, kernel_on):
        """Table entries past a lane's pages point at another lane's live
        blocks and at a block of NaN; the empty lane's whole table does:
        neither may reach an output (0 x NaN is NaN), through the buffer
        that serves as K and as V."""
        D, dv, H, mb, scale = 256, 128, 8, 6, 0.1
        lens = [20, 70, 0]
        q, pool, tables, new, full = _latent_case(
            np.random.RandomState(31), lens, 1, H, D, mb)
        pool[0] = np.nan                 # block 0 is in no table
        tables[0, 2:] = [tables[1, 0], tables[1, 1], 0, 0]
        tables[1, 5] = 0
        tables[2, :] = 0
        out, _pool = _latent(q, pool, tables, lens, new, dv, scale)
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[2], 0.0)
        for b in (0, 1):
            ref = _latent_dense(q[b], full[b], dv, scale, lens[b] - 1)
            np.testing.assert_allclose(out[b], ref, atol=2e-5)

    @pytest.mark.parametrize("T", [5, 16])
    @pytest.mark.parametrize("wide", [False, True], ids=["gather",
                                                         "blockwise"])
    def test_a_chunk_attends_causally_over_its_prefix(self, monkeypatch, T,
                                                      wide):
        """T > 1 (chunked prefill, a speculative verify): the gathered
        composite, and over a table wider than ``BLOCKWISE_FROM`` the
        blockwise one (groups of 2 pages, the last group padded), against
        dense attention; rows of a chunk before the sequence's first token
        (left padding) write nothing and give zeros."""
        from paddle_tpu.nn.functional import paged_attention as fpa
        D, dv, H, bs, mb, scale = 48, 32, 4, 8, 7, 0.2
        calls = []
        inner = fpa._blockwise_rows
        monkeypatch.setattr(
            fpa, "_blockwise_rows",
            lambda *a: (calls.append(a[0].shape), inner(*a))[1])
        monkeypatch.setattr(fpa, "BLOCKWISE_GROUP_TOKENS", 16)
        if wide:
            monkeypatch.setattr(fpa, "BLOCKWISE_FROM", mb * bs - 1)
        fpa._latent_write_and_attend.clear_cache()
        lens = [T, 9 + T, mb * bs, max(T - 2, 1), 0]
        q, pool, tables, new, full = _latent_case(
            np.random.RandomState(32 + T), lens, T, H, D, mb, bs)
        try:
            out, pool2 = _latent(q, pool, tables, lens, new, dv, scale)
        finally:
            fpa._latent_write_and_attend.clear_cache()
        assert bool(calls) == wide
        for b, n in enumerate(lens):
            pad = max(T - n, 0)
            np.testing.assert_array_equal(out[b, :pad], 0.0)
            if n:
                ref = _latent_dense(q[b, pad:], full[b], dv, scale,
                                    n - T + pad)
                np.testing.assert_allclose(out[b, pad:], ref, atol=2e-5)
        # the chunk's rows are where the table says, and nowhere else
        touched = np.zeros(pool.shape[:2], bool)
        for b, n in enumerate(lens):
            for t in range(max(T - n, 0), T):
                pos = n - T + t
                blk, off = tables[b, pos // bs], pos % bs
                np.testing.assert_array_equal(pool2[blk, off], new[b, t])
                touched[blk, off] = True
        np.testing.assert_array_equal(pool2[~touched], pool[~touched])

    @pytest.mark.parametrize("case,kernel", [
        ("decode", True), ("decode-bf16", True), ("verify-t2", False),
        ("row-576", False), ("values-96", False), ("block-4", False),
        ("pool-of-another-dtype", False), ("no-tpu-no-interpreter", False)])
    def test_dispatch_rule(self, monkeypatch, case, kernel):
        """What the call can see decides: one query a lane, a float pool of
        the queries' dtype, rows and values in whole lane tiles, pages in
        whole sublane tiles, on a TPU (or under the interpreter)."""
        import jax.numpy as jnp
        if case != "no-tpu-no-interpreter":
            monkeypatch.setattr(PK, "INTERPRET", True)
        B, T, H, D, dv, bs, mb = 2, 1, 32, 640, 512, 16, 2
        dt = pool_dt = jnp.float32
        if case == "decode-bf16":
            dt = pool_dt = jnp.bfloat16
        elif case == "verify-t2":
            T = 2
        elif case == "row-576":
            D = 576                     # the engine pads it to 640
        elif case == "values-96":
            dv = 96
        elif case == "block-4":
            bs = 4
        elif case == "pool-of-another-dtype":
            pool_dt = jnp.bfloat16
        nb = B * mb + 1
        q = jnp.ones((B, T, H, D), dt)
        pool = jnp.zeros((nb, bs, D), pool_dt)
        new = jnp.ones((B, T, D), pool_dt)
        tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(B, mb)
        lens = jnp.asarray([5, 9], jnp.int32)

        def call(q, pool, new):
            return F.latent_paged_attention(
                paddle.Tensor(q), paddle.Tensor(pool), paddle.Tensor(tables),
                paddle.Tensor(lens), paddle.Tensor(new), dv, 0.1)[0]._data

        assert _runs_kernel(call, q, pool, new) is kernel

    def test_differentiated_call_gets_the_composites_gradient(
            self, monkeypatch):
        """Kernel or not, a caller that backpropagates through a call gets
        the gathered composite's gradient, in the queries and the new
        rows."""
        rng = np.random.RandomState(34)
        D, dv, H, mb = 256, 128, 8, 3
        lens = [33, 7]
        q, pool, tables, new, _full = _latent_case(rng, lens, 1, H, D, mb)
        w = rng.randn(2, 1, H, dv).astype(np.float32)

        def grads():
            qt, nt = (paddle.to_tensor(a, stop_gradient=False)
                      for a in (q, new))
            out, _pool = F.latent_paged_attention(
                qt, paddle.to_tensor(pool), paddle.to_tensor(tables),
                paddle.to_tensor(np.asarray(lens, np.int32)), nt, dv, 0.1)
            (out * paddle.to_tensor(w)).sum().backward()
            return qt.grad.numpy(), nt.grad.numpy()

        want = grads()
        monkeypatch.setattr(PK, "INTERPRET", True)
        got = grads()
        for g, wnt in zip(got, want):
            assert np.abs(wnt).max() > 0
            np.testing.assert_allclose(g, wnt, atol=2e-5)
