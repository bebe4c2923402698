"""Round-2 coverage batch B: LLaMA, inference predictor, sparse, audio,
custom ops.
"""
import math

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


class TestLlama:
    def _tiny(self, **kw):
        from paddle_tpu.models import llama_tiny
        kw.setdefault("use_flash_attention", False)
        return llama_tiny(**kw)

    def test_trains(self):
        from paddle_tpu.models import LlamaForCausalLM
        paddle.seed(0)
        m = LlamaForCausalLM(self._tiny())
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        ids = paddle.to_tensor(
            np.random.randint(0, 512, (2, 32)).astype(np.int64))
        losses = []
        for _ in range(4):
            _, loss = m(ids, labels=ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]

    def test_gqa_shapes_and_grads(self):
        from paddle_tpu.models import LlamaForCausalLM
        paddle.seed(1)
        m = LlamaForCausalLM(self._tiny(num_kv_heads=2))
        attn = m.model.layers[0].self_attn
        # kv projections are narrower than q under GQA
        assert attn.k_proj.weight.shape[-1] < attn.q_proj.weight.shape[-1]
        ids = paddle.to_tensor(
            np.random.randint(0, 512, (2, 16)).astype(np.int64))
        _, loss = m(ids, labels=ids)
        loss.backward()
        assert all(p.grad is not None for p in m.parameters()
                   if not p.stop_gradient)

    def test_rope_properties(self):
        from paddle_tpu.models.llama import rotary_embedding
        x = paddle.to_tensor(np.random.randn(1, 8, 2, 16)
                             .astype(np.float32))
        out = rotary_embedding(x)
        # norms preserved per (pos, head) pair rotation
        np.testing.assert_allclose(
            np.linalg.norm(out.numpy(), axis=-1),
            np.linalg.norm(x.numpy(), axis=-1), atol=1e-5)
        # position 0 is identity
        np.testing.assert_allclose(out.numpy()[:, 0], x.numpy()[:, 0],
                                   atol=1e-6)

    @pytest.mark.slow
    def test_ring_attention_with_tp(self):
        """LLaMA with context_parallel='ring' + mp TP on a sep x mp mesh:
        loss matches the dense single-config model on the same weights.

        Slow-marked (~15s, 870s tier-1 budget): ring==dense equality
        stays in tier-1 via test_moe_sep's ring_flash_attention parity
        and TP via test_fleet_tp's gpt_mp2-matches-serial."""
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.models import LlamaForCausalLM

        old = mesh_mod._global_mesh
        try:
            mesh_mod.set_mesh(mesh_mod.build_mesh({"sep": 4, "mp": 2}))
            paddle.seed(9)
            m = LlamaForCausalLM(self._tiny(context_parallel="ring",
                                            mp_degree=2))
            ids = paddle.to_tensor(
                np.random.randint(0, 512, (2, 32)).astype(np.int64))
            _, loss = m(ids, labels=ids)
            loss.backward()
            assert all(p.grad is not None for p in m.parameters()
                       if not p.stop_gradient)

            dense = LlamaForCausalLM(self._tiny())
            dense.set_state_dict(m.state_dict())
            _, ref = dense(ids, labels=ids)
            np.testing.assert_allclose(float(loss.numpy()),
                                       float(ref.numpy()), rtol=1e-4)
        finally:
            mesh_mod._global_mesh = old

    def test_kv_cache_decode_matches_no_cache(self):
        from paddle_tpu.models import LlamaForCausalLM
        paddle.seed(5)
        ids = paddle.to_tensor(
            np.random.randint(0, 512, (2, 8)).astype(np.int64))
        for kv in (4, 2):     # MHA and GQA
            m = LlamaForCausalLM(self._tiny(num_kv_heads=kv))
            # three tokens: one from the prefill, two from the cache
            a = m.generate(ids, max_new_tokens=3, use_cache=False).numpy()
            b = m.generate(ids, max_new_tokens=3, use_cache=True).numpy()
            np.testing.assert_array_equal(a, b)

    def test_sampled_decode_rng_parity(self):
        """temperature>0: same seed -> identical samples on both paths
        (the per-token key stream is shared)."""
        from paddle_tpu.models import LlamaForCausalLM
        paddle.seed(6)
        m = LlamaForCausalLM(self._tiny())
        ids = paddle.to_tensor(
            np.random.randint(0, 512, (1, 8)).astype(np.int64))
        paddle.seed(123)
        a = m.generate(ids, max_new_tokens=5, temperature=1.0,
                       use_cache=False).numpy()
        paddle.seed(123)
        b = m.generate(ids, max_new_tokens=5, temperature=1.0,
                       use_cache=True).numpy()
        np.testing.assert_array_equal(a, b)

    def test_generate_greedy_deterministic(self):
        from paddle_tpu.models import LlamaForCausalLM
        paddle.seed(2)
        m = LlamaForCausalLM(self._tiny())
        ids = paddle.to_tensor(
            np.random.randint(0, 512, (1, 4)).astype(np.int64))
        a = m.generate(ids, max_new_tokens=5).numpy()
        b = m.generate(ids, max_new_tokens=5).numpy()
        np.testing.assert_array_equal(a, b)
        assert a.shape == (1, 9)


class TestInferencePredictor:
    def test_round_trip(self, tmp_path):
        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.static import InputSpec
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        x = np.random.randn(2, 8).astype(np.float32)
        ref = net(paddle.to_tensor(x)).numpy()
        prefix = str(tmp_path / "model")
        paddle.jit.save(net, prefix,
                        input_spec=[InputSpec([-1, 8], "float32")])

        pred = create_predictor(Config(prefix))
        h = pred.get_input_handle(pred.get_input_names()[0])
        h.copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle(pred.get_output_names()[0])
        np.testing.assert_allclose(out.copy_to_cpu(), ref, atol=1e-6)

    def test_multi_input_model(self, tmp_path):
        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.static import InputSpec

        class TwoIn(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 4)

            def forward(self, a, b):
                return self.fc(a + b)

        paddle.seed(1)
        net = TwoIn()
        a = np.random.randn(2, 8).astype(np.float32)
        b = np.random.randn(2, 8).astype(np.float32)
        ref = net(paddle.to_tensor(a), paddle.to_tensor(b)).numpy()
        prefix = str(tmp_path / "two")
        paddle.jit.save(net, prefix,
                        input_spec=[InputSpec([-1, 8], "float32"),
                                    InputSpec([-1, 8], "float32")])
        pred = create_predictor(Config(prefix))
        names = pred.get_input_names()
        assert len(names) == 2
        pred.get_input_handle(names[0]).copy_from_cpu(a)
        with pytest.raises(RuntimeError, match="never set"):
            pred.run()
        pred.get_input_handle(names[1]).copy_from_cpu(b)
        pred.run()
        out = pred.get_output_handle("output_0").copy_to_cpu()
        np.testing.assert_allclose(out, ref, atol=1e-6)

    def test_custom_op_attrs_with_custom_backward(self):
        import jax.numpy as jnp

        from paddle_tpu.utils import register_custom_op

        def fwd(a, alpha=1.0):
            return a * alpha

        def bwd(res, g):
            (arrays, out) = res
            return (g * 7.0,)

        op = register_custom_op("my_attr_scaled", fwd, backward=bwd)
        x = paddle.to_tensor(np.ones(2, np.float32), stop_gradient=False)
        out = op(x, alpha=3.0)
        np.testing.assert_allclose(np.asarray(out._data), [3.0, 3.0])
        paddle.ops.sum(out).backward()
        np.testing.assert_allclose(np.asarray(x.grad._data), [7.0, 7.0])

    def test_params_only_rejected(self, tmp_path):
        from paddle_tpu.framework.io import save as fio_save
        from paddle_tpu.inference import Config, create_predictor
        net = nn.Linear(4, 4)
        prefix = str(tmp_path / "weights")
        fio_save(net.state_dict(), prefix + ".pdparams")
        with pytest.raises(ValueError, match="pdmodel"):
            create_predictor(Config(prefix))


class TestSparse:
    def test_coo_round_trip(self):
        import paddle_tpu.sparse as sparse
        idx = np.array([[0, 1, 2], [1, 0, 2]])
        vals = np.array([1.0, 2.0, 3.0], np.float32)
        s = sparse.sparse_coo_tensor(idx, vals, (3, 3))
        assert s.nnz() == 3
        dense = s.to_dense().numpy()
        expect = np.zeros((3, 3), np.float32)
        expect[0, 1], expect[1, 0], expect[2, 2] = 1, 2, 3
        np.testing.assert_array_equal(dense, expect)
        np.testing.assert_array_equal(np.asarray(s.indices()._data), idx)

    def test_csr_round_trip(self):
        import paddle_tpu.sparse as sparse
        # [[1, 0, 2], [0, 0, 3], [4, 0, 0]]
        s = sparse.sparse_csr_tensor(
            [0, 2, 3, 4], [0, 2, 2, 0],
            np.array([1.0, 2.0, 3.0, 4.0], np.float32), (3, 3))
        dense = s.to_dense().numpy()
        expect = np.array([[1, 0, 2], [0, 0, 3], [4, 0, 0]], np.float32)
        np.testing.assert_array_equal(dense, expect)

    def test_spmm_matches_dense(self):
        import paddle_tpu.sparse as sparse
        rng = np.random.RandomState(0)
        dense_m = (rng.rand(8, 8) > 0.7) * rng.randn(8, 8)
        dense_m = dense_m.astype(np.float32)
        idx = np.nonzero(dense_m)
        s = sparse.sparse_coo_tensor(np.stack(idx), dense_m[idx], (8, 8))
        y = rng.randn(8, 4).astype(np.float32)
        out = sparse.matmul(s, paddle.to_tensor(y))
        np.testing.assert_allclose(np.asarray(out._data), dense_m @ y,
                                   atol=1e-5)

    def test_gradients_flow_through_sparse_ops(self):
        import paddle_tpu.sparse as sparse
        vals = paddle.to_tensor(np.array([-1.0, 2.0, 3.0], np.float32),
                                stop_gradient=False)
        s = sparse.sparse_coo_tensor([[0, 1, 2], [1, 0, 2]], vals, (3, 3))
        y = paddle.to_tensor(np.ones((3, 2), np.float32))
        out = sparse.matmul(sparse.relu(s), y)
        paddle.ops.sum(out).backward()
        # d/dvals of sum(relu(vals) @ ones): relu' * 2 per value
        np.testing.assert_allclose(np.asarray(vals.grad._data),
                                   [0.0, 2.0, 2.0])

    def test_sparse_add_gradients_to_both(self):
        import paddle_tpu.sparse as sparse
        va = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                              stop_gradient=False)
        vb = paddle.to_tensor(np.array([5.0], np.float32),
                              stop_gradient=False)
        a = sparse.sparse_coo_tensor([[0, 1], [0, 1]], va, (2, 2))
        b = sparse.sparse_coo_tensor([[0], [0]], vb, (2, 2))
        out = sparse.add(a, b).to_dense()
        paddle.ops.sum(out * out).backward()
        # dense result [[6,0],[0,2]]: d/dva = 2*[6,2], d/dvb = 2*[6]
        np.testing.assert_allclose(np.asarray(va.grad._data), [12.0, 4.0])
        np.testing.assert_allclose(np.asarray(vb.grad._data), [12.0])

    def test_sparse_add_and_relu(self):
        import paddle_tpu.sparse as sparse
        s1 = sparse.sparse_coo_tensor([[0, 1], [0, 1]],
                                      np.array([-1.0, 2.0], np.float32),
                                      (2, 2))
        s2 = sparse.sparse_coo_tensor([[0], [0]],
                                      np.array([5.0], np.float32), (2, 2))
        out = sparse.add(s1, s2).to_dense().numpy()
        np.testing.assert_array_equal(out, [[4, 0], [0, 2]])
        r = sparse.relu(s1).to_dense().numpy()
        np.testing.assert_array_equal(r, [[0, 0], [0, 2]])


class TestAudio:
    def test_mel_spectrogram_shapes(self):
        from paddle_tpu.audio.features import (LogMelSpectrogram,
                                               MelSpectrogram, MFCC,
                                               Spectrogram)
        x = paddle.to_tensor(np.random.randn(2, 2048).astype(np.float32))
        spec = Spectrogram(n_fft=256)(x)
        assert spec.shape[1] == 129
        mel = MelSpectrogram(sr=16000, n_fft=256, n_mels=40)(x)
        assert mel.shape[1] == 40
        logmel = LogMelSpectrogram(sr=16000, n_fft=256, n_mels=40)(x)
        assert logmel.shape == mel.shape
        mfcc = MFCC(sr=16000, n_mfcc=13, n_fft=256, n_mels=40)(x)
        assert mfcc.shape[1] == 13

    def test_fbank_rows_nonzero(self):
        from paddle_tpu.audio.functional import compute_fbank_matrix
        fb = np.asarray(compute_fbank_matrix(16000, 512, 64)._data)
        assert fb.shape == (64, 257)
        assert (fb.sum(axis=1) > 0).all()

    def test_window(self):
        from paddle_tpu.audio.functional import get_window
        w = np.asarray(get_window("hann", 16)._data)
        np.testing.assert_allclose(w, np.hanning(17)[:16], atol=1e-6)


class TestCustomOp:
    def test_autodiff_backward(self):
        import jax.numpy as jnp

        from paddle_tpu.utils import register_custom_op
        op = register_custom_op("my_square_sum",
                                lambda a: jnp.sum(a * a))
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                             stop_gradient=False)
        out = op(x)
        out.backward()
        np.testing.assert_allclose(np.asarray(x.grad._data), [2.0, 4.0])

    def test_custom_backward(self):
        import jax.numpy as jnp

        from paddle_tpu.utils import register_custom_op

        def fwd(a):
            return a * 2.0

        def bwd(res, g):
            return (g * 100.0,)     # deliberately not the true grad

        op = register_custom_op("my_scaled", fwd, backward=bwd)
        x = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
        paddle.ops.sum(op(x)).backward()
        np.testing.assert_allclose(np.asarray(x.grad._data),
                                   np.full(3, 100.0))

    def test_registered_in_registry(self):
        from paddle_tpu.ops.registry import OPS
        assert "my_square_sum" in OPS and OPS["my_square_sum"].category \
            == "custom"

    def test_duplicate_rejected(self):
        from paddle_tpu.utils import register_custom_op
        with pytest.raises(ValueError, match="already registered"):
            register_custom_op("matmul", lambda a: a)
