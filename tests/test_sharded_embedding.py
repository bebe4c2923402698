"""Mesh-sharded giant-embedding subsystem
(paddle_tpu/distributed/embedding/): dedup lookups, row-sharded
optimizer state, the host-PS parity bridge, the DLRM workload on a
virtual (data, fsdp) mesh with the liveness capacity proof, and the
dense serving path behind the Router.

The PS bridge is the tier-1 contract ISSUE 20 pins: the host-resident
``DistributedEmbedding`` (overflow tier) and the on-chip
``ShardedEmbedding`` (default tier) must produce identical forward
values and row gradients on the same table.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.embedding import (
    RowShardedAdagrad, RowShardedAdam, ShardedEmbedding, dedup_stats,
    exchange_bytes, naive_gather_bytes, sharded_embedding_bag,
    sharded_embedding_lookup)


@pytest.fixture()
def mesh24():
    """(data=2, fsdp=4) over the virtual 8-device CPU platform."""
    prev = mesh_mod._global_mesh
    mesh_mod._global_mesh = None
    m = mesh_mod.build_mesh({"data": 2, "fsdp": 4})
    mesh_mod.set_mesh(m)
    yield m
    mesh_mod._global_mesh = prev


# ------------------------------------------------------ dedup lookups
class TestDedupLookup:
    def _table(self, vocab=64, dim=8, seed=0):
        paddle.seed(seed)
        return ShardedEmbedding(vocab, dim)

    def test_lookup_matches_plain_embedding(self):
        emb = self._table()
        ids = paddle.to_tensor(
            np.array([[3, 3, 7], [1, 3, 1]], np.int64))
        got = emb(ids)
        ref = F.embedding(ids, emb.weight)
        np.testing.assert_allclose(got.numpy(), ref.numpy())

    def test_dedup_grad_matches_no_dedup(self):
        """The unique→gather→inverse-gather composition must be grad-
        transparent: duplicate ids still sum their row grads."""
        ids = paddle.to_tensor(np.array([5, 5, 5, 2], np.int64))
        grads = {}
        for dedup in (True, False):
            emb = self._table(seed=7)
            out = sharded_embedding_lookup(ids, emb.weight, dedup=dedup)
            (out * out).sum().backward()
            grads[dedup] = np.asarray(emb.weight.grad.numpy())
        np.testing.assert_allclose(grads[True], grads[False],
                                   rtol=1e-6, atol=1e-7)
        assert np.abs(grads[True][5]).sum() > 0  # 3x-summed row

    def test_bag_sum_and_mean(self):
        emb = self._table()
        ids_np = np.array([[1, 2, 2], [4, 0, 1]], np.int64)
        ids = paddle.to_tensor(ids_np)
        W = np.asarray(emb.weight.numpy())
        got_sum = emb.bag(ids, mode="sum").numpy()
        np.testing.assert_allclose(got_sum, W[ids_np].sum(axis=1),
                                   rtol=1e-6)
        got_mean = emb.bag(ids, mode="mean").numpy()
        np.testing.assert_allclose(got_mean, W[ids_np].mean(axis=1),
                                   rtol=1e-6)

    def test_padding_idx_rows_are_zero(self):
        paddle.seed(0)
        emb = ShardedEmbedding(16, 4, padding_idx=0)
        ids = paddle.to_tensor(np.array([0, 3, 0], np.int64))
        out = emb(ids).numpy()
        np.testing.assert_allclose(out[0], 0.0)
        np.testing.assert_allclose(out[2], 0.0)
        assert np.abs(out[1]).sum() > 0

    def test_dedup_capacity_overflow_raises_eagerly(self):
        emb = self._table()
        ids = paddle.to_tensor(np.arange(8, dtype=np.int64))
        with pytest.raises(ValueError, match="capacity"):
            sharded_embedding_lookup(ids, emb.weight, dedup_capacity=4)

    def test_lookup_under_jit_fixed_capacity(self):
        emb = self._table(seed=3)
        ids_np = np.array([9, 9, 1, 4], np.int64)

        def f(ids_a):
            return sharded_embedding_lookup(
                paddle.Tensor(ids_a), emb.weight,
                dedup_capacity=4)._data

        got = jax.jit(f)(jnp.asarray(ids_np))
        ref = np.asarray(emb.weight.numpy())[ids_np]
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6)

    def test_dedup_metrics_and_wire_model(self):
        stats = dedup_stats(np.array([1, 1, 1, 2], np.int64))
        assert stats["n_ids"] == 4 and stats["n_unique"] == 2
        assert stats["unique_ratio"] == 0.5
        # ring wire model: dedup moves fewer bytes than per-id gather
        assert exchange_bytes(2, 8, 4) < naive_gather_bytes(4, 8, 4)
        assert exchange_bytes(2, 8, 1) == 0    # single shard: no wire

    def test_unique_ratio_gauge_rides_lookups(self):
        from paddle_tpu.observability import metrics as M
        prev = paddle.get_flags(["FLAGS_enable_metrics"])[
            "FLAGS_enable_metrics"]
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            emb = self._table()
            ids = paddle.to_tensor(
                np.array([3, 3, 3, 3, 1, 1, 2, 2], np.int64))
            emb(ids)
            g = M.REGISTRY.get("paddle_tpu_embedding_unique_ratio")
            assert g is not None
            assert abs(g.value() - 3 / 8) < 1e-6
        finally:
            paddle.set_flags({"FLAGS_enable_metrics": prev})


# ------------------------------------------------- sharded placement
class TestShardedPlacement:
    def test_shard_over_fsdp_axes(self, mesh24):
        paddle.seed(0)
        emb = ShardedEmbedding(64, 8, mesh=mesh24)
        assert emb.vocab_shards == 4           # fsdp=4; tp absent
        spec = emb.weight._spmd_spec
        assert spec is not None and spec[1] is None
        axes = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        assert "fsdp" in axes

    def test_sharded_lookup_matches_replicated(self, mesh24):
        paddle.seed(5)
        repl = ShardedEmbedding(64, 8)
        paddle.seed(5)
        shard = ShardedEmbedding(64, 8, mesh=mesh24)
        ids = paddle.to_tensor(
            np.array([[11, 11, 60], [1, 0, 11]], np.int64))
        np.testing.assert_allclose(shard(ids).numpy(),
                                   repl(ids).numpy(), rtol=1e-6)
        # grads agree too (the Partial pending reduce resolves here)
        shard(ids).sum().backward()
        repl(ids).sum().backward()
        np.testing.assert_allclose(
            np.asarray(shard.weight.grad.numpy()),
            np.asarray(repl.weight.grad.numpy()), rtol=1e-6, atol=1e-7)


# ------------------------------------------------- host-PS parity
@pytest.fixture()
def cluster():
    """Two in-process PS shards + a client (test_ps.py's fixture)."""
    from paddle_tpu.distributed.ps import PsClient, PsServer
    servers = [PsServer(i, 2, token="t0").start() for i in range(2)]
    client = PsClient([s.endpoint for s in servers], token="t0")
    yield servers, client
    client.close()
    for s in servers:
        s.stop()


class TestPsParityBridge:
    def test_ps_and_sharded_embedding_parity(self, cluster):
        """ISSUE-20 tier-1 contract: same table → identical forward
        values AND identical row gradients from both tiers. The PS
        table uses the 'sum' accessor so the pushed row grads read
        back as (after - before)."""
        from paddle_tpu.distributed.ps import DistributedEmbedding
        _, client = cluster
        vocab, dim = 32, 8
        ps_emb = DistributedEmbedding(
            11, dim, client=client, accessor="sum",
            initializer="uniform", init_range=0.1)
        all_ids = list(range(vocab))
        W0 = client.pull_sparse(11, all_ids)   # materialize init rows
        paddle.seed(0)
        sh_emb = ShardedEmbedding(vocab, dim)
        sh_emb.weight._swap_payload(jnp.asarray(W0))

        ids = paddle.to_tensor(
            np.array([[1, 2, 2], [5, 1, 7]], np.int64))
        out_ps = ps_emb(ids)
        out_sh = sh_emb(ids)
        np.testing.assert_allclose(out_ps.numpy(), out_sh.numpy(),
                                   rtol=1e-6, atol=1e-7)

        (out_ps * out_ps).sum().backward()
        (out_sh * out_sh).sum().backward()
        pushed = client.pull_sparse(11, all_ids) - W0  # sum accessor
        np.testing.assert_allclose(
            pushed, np.asarray(sh_emb.weight.grad.numpy()),
            rtol=1e-5, atol=1e-6)


# ------------------------------------------- row-sharded optimizers
class TestRowShardedOptimizers:
    def _grad_rows(self, dim=6):
        ids = np.array([4, 9, 4, 0], np.int64)      # duplicate id 4
        rng = np.random.RandomState(1)
        return ids, rng.randn(len(ids), dim).astype(np.float32)

    def test_adagrad_sparse_matches_dense(self):
        paddle.seed(2)
        dim = 6
        a = ShardedEmbedding(16, dim)
        paddle.seed(2)
        b = ShardedEmbedding(16, dim)
        ids, g_rows = self._grad_rows(dim)
        dense_g = np.zeros((16, dim), np.float32)
        np.add.at(dense_g, ids, g_rows)

        opt_a = RowShardedAdagrad(a.weight, lr=0.1)
        opt_a.step(jnp.asarray(dense_g))
        opt_b = RowShardedAdagrad(b.weight, lr=0.1)
        opt_b.step_rows(jnp.asarray(ids), jnp.asarray(g_rows))
        np.testing.assert_allclose(np.asarray(a.weight.numpy()),
                                   np.asarray(b.weight.numpy()),
                                   rtol=1e-5, atol=1e-6)

    def test_adam_sparse_touches_only_used_rows(self):
        paddle.seed(3)
        emb = ShardedEmbedding(16, 6)
        before = np.asarray(emb.weight.numpy()).copy()
        ids, g_rows = self._grad_rows(6)
        opt = RowShardedAdam(emb.weight, lr=0.01)
        opt.step_rows(jnp.asarray(ids), jnp.asarray(g_rows))
        after = np.asarray(emb.weight.numpy())
        touched = sorted(set(ids.tolist()))
        untouched = [i for i in range(16) if i not in touched]
        np.testing.assert_allclose(after[untouched], before[untouched])
        for i in touched:
            assert np.abs(after[i] - before[i]).sum() > 0

    def test_slots_inherit_table_sharding(self, mesh24):
        paddle.seed(4)
        emb = ShardedEmbedding(64, 8, mesh=mesh24)
        opt = RowShardedAdam(emb.weight)
        table_sh = emb.weight._data.sharding
        for slot in opt.slots():
            assert slot.sharding == table_sh
        # slot bytes scale with the table (global accounting)
        assert opt.slot_nbytes() == 2 * 64 * 8 * 4


# --------------------------------------------------- DLRM on the mesh
class TestDLRMOnMesh:
    def _data(self, cfg, batch=8, seed=0):
        rng = np.random.RandomState(seed)
        dense = rng.randn(batch, cfg.n_dense).astype(np.float32)
        ids = (rng.zipf(1.5, (batch, cfg.n_sparse, cfg.bag_size)) - 1) \
            % cfg.num_embeddings
        labels = rng.randint(0, 2, (batch,)).astype(np.float32)
        return dense, ids.astype(np.int64), labels

    def test_sharded_training_loss_parity(self, mesh24):
        """Replicated vs (data, fsdp)-sharded DLRM: same weights, same
        batches, 3 plain-SGD steps — losses agree to rtol 1e-3 (the
        ISSUE-20 acceptance bar)."""
        from paddle_tpu.models import DLRM, dlrm_tiny
        cfg = dlrm_tiny(num_embeddings=256)
        paddle.seed(11)
        repl = DLRM(cfg)
        state = {k: np.asarray(v.numpy())
                 for k, v in repl.state_dict().items()}
        paddle.seed(11)
        shard = DLRM(cfg, mesh=mesh24)
        shard.set_state_dict(state)
        shard.shard_(mesh24)          # re-pin after the payload swap

        dense_np, ids_np, labels_np = self._data(cfg)
        for step in range(3):
            losses = []
            for model in (repl, shard):
                d = paddle.to_tensor(dense_np)
                i = paddle.to_tensor(ids_np)
                y = paddle.to_tensor(labels_np)
                loss = model.loss(d, i, y)
                loss.backward()
                for p in model.parameters():
                    if p.grad is not None:
                        p._swap_payload(p._data - 0.1 * p.grad._data)
                        p.clear_grad()
                losses.append(float(loss.numpy()))
            assert losses[0] == pytest.approx(losses[1], rel=1e-3), (
                step, losses)

    def test_pod_capacity_proof_and_zero_fallbacks(self, mesh24):
        """The liveness analyzer proves the point of sharding: on the
        8-chip pod there is a per-chip budget the replicated DLRM
        exceeds and the row-sharded one fits under — with the table
        placement surviving propagation (zero replicate-fallbacks on
        the embedding path)."""
        from paddle_tpu import static
        from paddle_tpu.distributed.spmd.propagate import \
            propagate_program
        from paddle_tpu.models import DLRM, DLRMConfig
        from paddle_tpu.static import liveness
        from jax.sharding import PartitionSpec as P

        cfg = DLRMConfig(num_embeddings=16384, embedding_dim=32,
                         n_dense=4, n_sparse=4, bag_size=2,
                         bottom_mlp=(16,), top_mlp=(16,))
        paddle.seed(0)
        model = DLRM(cfg, mesh=mesh24)
        batch = 8
        prog = static.Program()
        with static.program_guard(prog):
            d = static.data("dense", [batch, cfg.n_dense], "float32")
            i = static.data("ids",
                            [batch, cfg.n_sparse, cfg.bag_size],
                            "int64")
            y = static.data("labels", [batch], "float32")
            out = model.loss(d, i, y)
        fetch = [id(out)]
        in_specs = {"dense": P("data"), "ids": P("data"),
                    "labels": P("data")}
        plan = propagate_program(prog, mesh24, in_specs)
        # the embedding path must not fall back to replication
        for op in ("embedding", "embedding_bag", "scatter_add"):
            assert op not in plan.fallback_ops, plan.fallback_ops
        # the table's fsdp placement survived into the plan env
        table = model.embedding.weight
        vid = next(v for v, t in prog._captured.items()
                   if t is table)
        spec0 = plan.env[vid][0]
        axes = spec0 if isinstance(spec0, tuple) else (spec0,)
        assert "fsdp" in axes

        sh = liveness.peak_report(prog, fetch_ids=fetch, plan=plan,
                                  mesh=mesh24)
        repl = liveness.peak_report(prog, fetch_ids=fetch)
        table_bytes = cfg.num_embeddings * cfg.embedding_dim * 4
        # replicated peak carries the full table; sharded sheds >= half
        assert repl["peak_bytes"] >= table_bytes
        assert sh["peak_bytes"] <= repl["peak_bytes"] - table_bytes / 2
        # a budget between the peaks: the table provably exceeds one
        # chip's share replicated, and fits row-sharded
        budget = (sh["peak_bytes"] * repl["peak_bytes"]) ** 0.5
        assert repl["peak_bytes"] > budget > sh["peak_bytes"]

    def test_pod_proof_is_device_independent(self):
        """The same proof runs against a duck-typed pod mesh (axis
        sizes only), so a 1-device host can run it."""
        from paddle_tpu import static
        from paddle_tpu.distributed.spmd.propagate import \
            propagate_program
        from paddle_tpu.models import DLRM, dlrm_tiny
        from paddle_tpu.static import liveness
        from jax.sharding import PartitionSpec as P

        cfg = dlrm_tiny(num_embeddings=8192, embedding_dim=32)
        paddle.seed(0)
        model = DLRM(cfg)                  # no real mesh at all
        pod = types.SimpleNamespace(shape={"data": 2, "fsdp": 4})
        prog = static.Program()
        with static.program_guard(prog):
            d = static.data("dense", [4, cfg.n_dense], "float32")
            i = static.data("ids", [4, cfg.n_sparse, cfg.bag_size],
                            "int64")
            y = static.data("labels", [4], "float32")
            out = model.loss(d, i, y)
        table = model.embedding.weight
        plan = propagate_program(
            prog, pod, {"dense": P("data"), "ids": P("data"),
                        "labels": P("data")},
            param_specs=lambda t: ("fsdp", None) if t is table
            else None)
        sh = liveness.peak_report(prog, fetch_ids=[id(out)], plan=plan,
                                  mesh=pod)
        repl = liveness.peak_report(prog, fetch_ids=[id(out)])
        assert sh["peak_bytes"] < repl["peak_bytes"]


# ------------------------------------------------- dense serving path
class TestDenseServing:
    def _engine(self, max_batch=4):
        from paddle_tpu.inference.serving import PagedEngine
        from paddle_tpu.models import DLRM, dlrm_tiny
        paddle.seed(0)
        model = DLRM(dlrm_tiny())
        return model, PagedEngine(model, max_batch=max_batch)

    def test_score_token_matches_serve_dense(self):
        model, eng = self._engine()
        ids = [3, 1, 4, 1, 5, 9, 2, 6][: model.serve_dense_width]
        rid = eng.add_request(ids, max_new_tokens=1)
        out = eng.run_to_completion()
        flat = paddle.to_tensor(
            np.asarray([ids], np.int64))
        ref = float(np.asarray(model.serve_dense(flat)._data)[0])
        assert out[rid] == [int(round(ref * 10000))]
        assert eng.kv_bytes_per_token == 0

    def test_warmup_batching_and_outcomes(self):
        from paddle_tpu.inference.serving import RequestStatus
        model, eng = self._engine(max_batch=4)
        eng.warmup()
        assert eng.lifecycle.ready()
        rids = [eng.add_request([1 + i] * model.serve_dense_width)
                for i in range(6)]          # > max_batch: two ticks
        out = eng.run_to_completion()
        assert set(rids) <= set(out)
        for rid in rids:
            oc = eng.outcomes[rid]
            assert oc.status == RequestStatus.FINISHED
            assert len(oc.tokens) == 1

    def test_prompt_wider_than_model_rejected(self):
        model, eng = self._engine()
        with pytest.raises(ValueError, match="serve width"):
            eng.add_request([1] * (model.serve_dense_width + 1))

    def test_dlrm_behind_router(self):
        from paddle_tpu.serving.router import Router
        model, eng = self._engine()
        router = Router([eng]).warmup()
        rids = [router.add_request([2 + i] * model.serve_dense_width,
                                   max_new_tokens=1)
                for i in range(5)]
        out = router.run_to_completion()
        assert set(rids) <= set(out)
        assert all(len(v) == 1 for v in out.values())
        assert router.health()["per_replica"][0]["kv_bytes_per_token"] == 0

    def test_llm_engines_unaffected(self):
        """The dense seam must not change the LM path's arch pick."""
        from paddle_tpu.inference.serving import _pick_arch, _GPTArch
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        paddle.seed(0)
        gpt = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
            max_seq_len=32, use_flash_attention=False))
        assert isinstance(_pick_arch(gpt), _GPTArch)
