"""Async runtime (round 17): device prefetch, buffer donation,
decomposed ZeRO gathers, async loss fetch.

Covers the tentpole contracts — DevicePrefetcher ordering/teardown
(including worker-process reaping through a wrapped multiprocess
DataLoader iterator), to_static/Engine donation safety (framework error
on stale reads, pcc separation, the Engine's always-donated step against
an undonated jit of the same function), stage-2/3
decomposed gathers + the stage-3 lookahead schedule, the hapi non-finite
degradation path under the async pipeline, and the fleet_trace
transfer/compute span-overlap report.
"""
import gc
import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402

import paddle_tpu as paddle                            # noqa: E402
from paddle_tpu import nn                              # noqa: E402
from paddle_tpu.core.donation import DonatedBufferError  # noqa: E402
from paddle_tpu.core.tensor import Tensor              # noqa: E402
from paddle_tpu.io import DataLoader, Dataset, DevicePrefetcher  # noqa: E402


class _Range(Dataset):
    def __init__(self, n=64, width=4):
        self.n = n
        self.width = width

    def __getitem__(self, i):
        return np.full((self.width,), i, np.float32)

    def __len__(self):
        return self.n


def _alive(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split()[2] != "Z"
    except OSError:
        return False


def _wait_dead(pids, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if not any(_alive(p) for p in pids):
            return True
        time.sleep(0.1)
    return False


# =========================================================================
# DevicePrefetcher
# =========================================================================
class TestDevicePrefetcher:
    def test_order_and_values_match_plain_iteration(self):
        loader = DataLoader(_Range(32), batch_size=4)
        plain = [b.numpy() for b in loader]
        pre = [b.numpy() for b in DevicePrefetcher(iter(loader))]
        assert len(plain) == len(pre)
        for a, b in zip(plain, pre):
            np.testing.assert_array_equal(a, b)

    def test_depth_flag_and_counters(self):
        pf = DevicePrefetcher(iter(range(10)), depth=3,
                              place_fn=lambda x: x)
        out = list(pf)
        assert out == list(range(10))
        assert pf.depth == 3
        assert pf.hits + 1 >= 1          # counters exist and accumulate
        assert pf.stall_seconds >= 0.0

    def test_exhaustion_closes(self):
        pf = DevicePrefetcher(iter([1, 2]), place_fn=lambda x: x)
        assert list(pf) == [1, 2]
        assert pf.closed
        with pytest.raises(StopIteration):
            next(pf)

    def test_close_idempotent_and_context_manager(self):
        with DevicePrefetcher(iter([1, 2, 3]),
                              place_fn=lambda x: x) as pf:
            assert next(pf) == 1
        assert pf.closed
        pf.close()                        # second close is a no-op

    def test_inner_error_propagates(self):
        def gen():
            yield 1
            raise ValueError("producer blew up")

        pf = DevicePrefetcher(gen(), place_fn=lambda x: x)
        assert next(pf) == 1
        with pytest.raises(ValueError, match="producer blew up"):
            for _ in range(5):
                next(pf)

    def test_place_fn_runs_on_producer_thread(self):
        import threading
        seen = []

        def place(x):
            seen.append(threading.current_thread().name)
            return x

        list(DevicePrefetcher(iter([1, 2]), place_fn=place))
        assert seen and all(n == "paddle_tpu-prefetch" for n in seen)

    # ---- satellite: shutdown propagation to multiprocess workers ----
    def test_abandoned_prefetcher_reaps_dataloader_workers(self):
        loader = DataLoader(_Range(64), batch_size=4, num_workers=2)
        pids = []

        def consume():
            it = iter(loader)
            pids.extend(w.pid for w in it._workers)
            pf = DevicePrefetcher(it)
            next(pf)
            next(pf)
            # abandon mid-epoch WITHOUT closing: the finalize path must
            # reap the prefetch thread AND the worker processes

        consume()
        gc.collect()
        assert _wait_dead(pids), (
            "DataLoader workers orphaned after a prefetching iterator "
            "was abandoned mid-epoch")

    def test_explicit_close_propagates_to_workers(self):
        loader = DataLoader(_Range(64), batch_size=4, num_workers=2)
        it = iter(loader)
        pids = [w.pid for w in it._workers]
        pf = DevicePrefetcher(it)
        next(pf)
        pf.close()
        assert _wait_dead(pids), (
            "DataLoader workers survived DevicePrefetcher.close()")

    def test_consumer_exception_mid_epoch_reaps_workers(self):
        loader = DataLoader(_Range(64), batch_size=4, num_workers=2)
        pids = []

        def consume():
            it = iter(loader)
            pids.extend(w.pid for w in it._workers)
            for i, _b in enumerate(DevicePrefetcher(it)):
                if i == 2:
                    raise ValueError("consumer blew up")

        with pytest.raises(ValueError):
            consume()
        gc.collect()
        assert _wait_dead(pids), (
            "workers orphaned after consumer exception under prefetch")


# =========================================================================
# Donation — to_static
# =========================================================================
class TestToStaticDonation:
    def _model(self):
        paddle.seed(11)
        return nn.Linear(6, 6)

    def test_donated_call_rebinds_params_and_deletes_old(self):
        lin = self._model()
        step = paddle.jit.to_static(lin.forward, donate=True,
                                    full_graph=True)
        x = paddle.to_tensor(np.ones((2, 6), np.float32))
        old_w = lin.weight._data
        out1 = step(x)
        assert old_w.is_deleted()
        assert not lin.weight._data.is_deleted()
        out2 = step(x)      # params rebound: repeated calls work
        np.testing.assert_allclose(out1.numpy(), out2.numpy(), rtol=1e-6)

    def test_stale_read_raises_framework_error(self):
        lin = self._model()
        step = paddle.jit.to_static(lin.forward, donate=True,
                                    full_graph=True)
        x = paddle.to_tensor(np.ones((2, 6), np.float32))
        stale = Tensor(lin.weight._data)
        step(x)
        with pytest.raises(DonatedBufferError,
                           match="donated"):
            stale.numpy()
        with pytest.raises(DonatedBufferError):
            stale.item(0, 0)

    def test_aliased_params_raise_clear_error(self):
        lin = self._model()
        lin2 = nn.Linear(6, 6)
        lin2.weight._data = lin.weight._data   # shared buffer

        class Both(nn.Layer):
            def __init__(self):
                super().__init__()
                self.a = lin
                self.b = lin2

            def forward(self, x):
                return self.b(self.a(x))

        m = Both()
        step = paddle.jit.to_static(m.forward, donate=True,
                                    full_graph=True)
        with pytest.raises(DonatedBufferError, match="share one"):
            step(paddle.to_tensor(np.ones((2, 6), np.float32)))

    def test_flag_off_path_bit_exact(self):
        """donate=False (the default): identical results AND no buffer
        ever deleted — the seed behavior."""
        x = paddle.to_tensor(np.random.RandomState(3).randn(
            4, 6).astype(np.float32))
        lin_a = self._model()
        base = paddle.jit.to_static(lin_a.forward, full_graph=True)(x)
        assert not lin_a.weight._data.is_deleted()
        lin_b = self._model()
        don = paddle.jit.to_static(lin_b.forward, donate=True,
                                   full_graph=True)(x)
        np.testing.assert_array_equal(base.numpy(), don.numpy())

    def test_pcc_key_separates_donated(self):
        lin = self._model()
        f_plain = paddle.jit.to_static(lin.forward, full_graph=True)
        f_don = paddle.jit.to_static(lin.forward, donate=True,
                                     full_graph=True)
        x = paddle.to_tensor(np.ones((2, 6), np.float32))
        sig = ((), (), ((tuple(x.shape), "float32"),))
        params = lin.parameters()
        assert f_plain._pcc_key(sig, params) != f_don._pcc_key(sig,
                                                               params)

    def test_pcc_roundtrip_no_cross_hit(self, tmp_path):
        """A donated program published to the persistent cache must only
        be served to donated wrappers; a fresh undonated wrapper of the
        same function sees a miss (and vice versa)."""
        from paddle_tpu.core import flags as flags_mod

        prev = {k: flags_mod.get_flag(k)
                for k in ("compile_cache", "compile_cache_dir")}
        paddle.set_flags({"FLAGS_compile_cache": True,
                          "FLAGS_compile_cache_dir": str(tmp_path)})
        try:
            x = paddle.to_tensor(np.ones((2, 6), np.float32))

            lin = self._model()
            f_don = paddle.jit.to_static(lin.forward, donate=True,
                                         full_graph=True)
            out_don = f_don(x)            # compiles + publishes donated

            # fresh process-equivalent: new StaticFunction objects over
            # a model with the same weights
            lin2 = self._model()
            f_plain = paddle.jit.to_static(lin2.forward,
                                           full_graph=True)
            out_plain = f_plain(x)        # must NOT hit the donated entry
            assert not lin2.weight._data.is_deleted()
            np.testing.assert_allclose(out_plain.numpy(),
                                       out_don.numpy(), rtol=1e-6)

            lin3 = self._model()
            f_don2 = paddle.jit.to_static(lin3.forward, donate=True,
                                          full_graph=True)
            old = lin3.weight._data
            out2 = f_don2(x)              # donated wrapper may hit —
            assert old.is_deleted()       # and donation still happens
            assert not lin3.weight._data.is_deleted()
            np.testing.assert_allclose(out2.numpy(), out_don.numpy(),
                                       rtol=1e-6)
        finally:
            paddle.set_flags({f"FLAGS_{k}": v for k, v in prev.items()})

    def test_entry_guard_rejects_predeleted_params(self):
        lin = self._model()
        step = paddle.jit.to_static(lin.forward, donate=True,
                                    full_graph=True)
        x = paddle.to_tensor(np.ones((2, 6), np.float32))
        step(x)
        # sabotage: rebind a param to a deleted buffer (simulates a
        # caller feeding stale donated state back in)
        donated = [p._data for p in lin.parameters()]
        fresh = step(x)                   # fine: params are live
        lin.weight._data = donated[0] if donated[0].is_deleted() else \
            lin.weight._data
        if lin.weight._data.is_deleted():
            with pytest.raises(DonatedBufferError, match="entry"):
                step(x)
        del fresh


# =========================================================================
# Donation — Engine + async loss + prefetch parity
# =========================================================================
class _XY(Dataset):
    def __init__(self, n=48):
        self.n = n

    def __getitem__(self, i):
        rng = np.random.RandomState(i)
        return (rng.randn(8).astype(np.float32),
                rng.randn(2).astype(np.float32))

    def __len__(self):
        return self.n


def _undonated(engine):
    """The same step function jitted WITHOUT donation: what the Engine
    compiled before its step owned its state. The yardstick of the
    donated step's histories."""
    engine.prepare()
    engine._train_step = jax.jit(engine._train_step.__wrapped__)
    return engine


def _mse(o, t):
    return paddle.ops.mean((o - t) ** 2)


class TestEngineAsync:
    def _build(self, undonated=False, **kw):
        from paddle_tpu.distributed.auto_parallel.engine import Engine
        from paddle_tpu.optimizer import Adam

        paddle.seed(5)
        np.random.seed(5)                   # the loader's shuffle
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
        opt = Adam(learning_rate=1e-3, parameters=m.parameters())
        e = Engine(m, loss=_mse, optimizer=opt, **kw)
        return (_undonated(e) if undonated else e), m, opt

    def _run(self, epochs=1, **kw):
        e, m, opt = self._build(**kw)
        hist = e.fit(_XY(), epochs=epochs, batch_size=8)
        return hist, m, opt

    @staticmethod
    def _state(m, opt):
        """Every parameter and accumulator leaf, read through its owner."""
        params = list(m.parameters())
        leaves = [p._data for p in params]
        for p in params:
            leaves += jax.tree_util.tree_leaves(opt._accumulators[id(p)])
        assert all(not a.is_deleted() for a in leaves)
        return [np.asarray(a) for a in leaves]

    @pytest.mark.parametrize("prefetch", [False, True])
    def test_parity_with_the_undonated_step(self, prefetch):
        base, m0, opt0 = self._run(undonated=True, prefetch=False)
        want = self._state(m0, opt0)
        hist, m, opt = self._run(prefetch=prefetch)
        assert hist == pytest.approx(base, rel=1e-5)
        got = self._state(m, opt)
        assert len(got) == len(want) == 4 * 3   # 4 params, m and v each
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
        assert opt._step_count == opt0._step_count == 6

    def test_default_engine_donates_the_prefit_buffers(self):
        """No ``donate=`` argument, no flag: one step and the arrays the
        Parameters held before ``fit`` are gone; the Parameters are not."""
        from paddle_tpu.core import flags as flags_mod

        with pytest.raises(KeyError):
            flags_mod.get_flag("donate_buffers")
        with pytest.raises(TypeError):
            self._build(donate=False)
        e, m, opt = self._build()
        e.fit(_XY(8), epochs=1, batch_size=8)      # places + one step
        before = [p._data for p in m.parameters()]
        e.fit(_XY(8), epochs=1, batch_size=8)      # ONE step
        assert all(a.is_deleted() for a in before)
        with pytest.raises(DonatedBufferError, match="Engine"):
            Tensor(before[0]).numpy()
        self._state(m, opt)                        # live, readable
        for p in m.parameters():
            p.numpy()

    def test_history_finite_and_per_epoch(self):
        hist, _, _ = self._run(epochs=2, prefetch=True)
        assert len(hist) == 2
        assert all(np.isfinite(h) for h in hist)

    def test_abort_mid_fit_writes_back_live_params(self):
        class Exploding(_XY):
            fetches = 0

            def __getitem__(self, i):       # the sampler shuffles: count
                self.fetches += 1
                if self.fetches > 24:       # three whole batches, then
                    raise RuntimeError("loader died mid-epoch")
                return super().__getitem__(i)

        e, m, opt = self._build()
        start = [np.asarray(p._data) for p in m.parameters()]
        with pytest.raises(RuntimeError, match="loader died"):
            e.fit(Exploding(), epochs=1, batch_size=8)
        # the first step donated the pre-fit payloads; the finally-block
        # writeback must leave every Parameter and accumulator on the
        # LATEST live buffer (steps ran before the loader died)
        got = self._state(m, opt)
        assert opt._step_count == 3
        assert any(not np.array_equal(g, s0)
                   for g, s0 in zip(got, start))
        for p in m.parameters():
            p.numpy()                      # readable, no DonatedBufferError

    def test_two_fits_in_a_row_on_one_engine(self):
        """The benchmark's ``check_calls`` pattern: fit, clear the
        accumulators written back, fit again on the same Engine."""
        def both_calls(undonated):
            eng, model, o = self._build(undonated)
            eng.fit(_XY(16), epochs=1, batch_size=8)
            o._accumulators.clear()
            eng.fit(_XY(16), epochs=2, batch_size=8)
            return eng, model, o

        u, m_u, opt_u = both_calls(True)
        e, m, opt = both_calls(False)
        assert len(e.history) == 3
        assert e.history == pytest.approx(u.history, rel=1e-5)
        assert e._train_step._cache_size() == 1     # one step program
        for g, w in zip(self._state(m, opt), self._state(m_u, opt_u)):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)

    def test_replicated_state_is_what_is_donated_on_a_mesh(self):
        """dp over 4 devices: the step is given (and consumes) the copies
        replicated over the mesh, and the Parameters end up on them."""
        from paddle_tpu.distributed import mesh as mesh_mod

        old = mesh_mod._global_mesh
        mesh_mod.set_mesh(mesh_mod.build_mesh(devices=jax.devices()[:4]))
        try:
            e, m, opt = self._build()
            init = [p._data for p in m.parameters()]
            assert all(len(a.sharding.device_set) == 1 for a in init)
            e.prepare()
            step, given = e._train_step, []

            def spy(pa, opt_state, lr, x, y):
                given.append(jax.tree_util.tree_leaves((pa, opt_state)))
                return step(pa, opt_state, lr, x, y)

            e._train_step = spy
            e.fit(_XY(16), epochs=1, batch_size=8)
            assert len(given) == 2
            for leaves in given:
                assert all(a.is_deleted() for a in leaves)
            # model init's arrays may share the first device's buffer
            # with the copies: dead or alive, never XLA's own error
            for a in init:
                try:
                    Tensor(a).numpy()
                except DonatedBufferError as err:
                    assert "Engine" in str(err)
            for p in m.parameters():
                assert len(p._data.sharding.device_set) == 4
                assert p._data.sharding.is_fully_replicated
            self._state(m, opt)
            assert step._cache_size() == 1
        finally:
            mesh_mod._global_mesh = old

    def test_engine_census_recorded(self):
        from paddle_tpu.observability.perf import memory as mem

        mem.reset_high_water()
        self._run(prefetch=True)
        assert mem.high_water("engine_step_donated")["total"] > 0


# =========================================================================
# hapi Model.fit under the async pipeline (satellite)
# =========================================================================
class TestHapiAsyncNonfinite:
    def test_nonfinite_loss_skips_step_under_prefetch(self):
        from paddle_tpu.core import flags as flags_mod
        from paddle_tpu.fault import inject
        from paddle_tpu.hapi.model import Model
        from paddle_tpu.optimizer import SGD

        assert flags_mod.get_flag("prefetch"), \
            "prefetch must be ON by default in hapi fit"
        paddle.seed(9)
        net = nn.Linear(4, 2)
        model = Model(net)
        model.prepare(
            optimizer=SGD(learning_rate=0.1,
                          parameters=net.parameters()),
            loss=lambda o, t: paddle.ops.mean((o - t) ** 2))

        class DS(Dataset):
            def __getitem__(self, i):
                rng = np.random.RandomState(i)
                return (rng.randn(4).astype(np.float32),
                        rng.randn(2).astype(np.float32))

            def __len__(self):
                return 16

        inject.arm("grads.nan_at_step", step=2)
        try:
            before = None
            hist = None
            w_before_nan = None
            # the concrete-loss materialization happens inside
            # train_batch, BEFORE the optimizer step — a NaN loss under
            # the async pipeline must still be caught
            hist = model.fit(DS(), epochs=1, batch_size=4, verbose=0)
        finally:
            inject.disarm("grads.nan_at_step")
        assert model._nonfinite_steps == 1
        # weights stayed finite: the poisoned grads never applied
        assert np.isfinite(net.weight.numpy()).all()
        assert hist is not None

    def test_fit_prefetch_off_flag(self):
        from paddle_tpu.hapi.model import Model
        from paddle_tpu.optimizer import SGD

        prev = paddle.get_flags("FLAGS_prefetch")["FLAGS_prefetch"]
        paddle.set_flags({"FLAGS_prefetch": False})
        try:
            paddle.seed(9)
            net = nn.Linear(4, 2)
            model = Model(net)
            model.prepare(
                optimizer=SGD(learning_rate=0.1,
                              parameters=net.parameters()),
                loss=lambda o, t: paddle.ops.mean((o - t) ** 2))

            class DS(Dataset):
                def __getitem__(self, i):
                    rng = np.random.RandomState(i)
                    return (rng.randn(4).astype(np.float32),
                            rng.randn(2).astype(np.float32))

                def __len__(self):
                    return 16

            hist = model.fit(DS(), epochs=1, batch_size=4, verbose=0)
            assert hist
        finally:
            paddle.set_flags({"FLAGS_prefetch": prev})


# =========================================================================
# Decomposed gathers
# =========================================================================
class TestDecomposedGather:
    def test_plan_groups_budget_and_order(self):
        from paddle_tpu.distributed.sharding import plan_groups

        paddle.seed(1)
        params = [nn.Linear(32, 32).weight for _ in range(6)]
        nbytes = int(params[0]._data.nbytes)
        groups = plan_groups(params, max_bytes=2 * nbytes)
        assert all(len(g) <= 2 for g in groups)
        flat = [p for g in groups for p in g]
        assert [p.name for p in flat] == [p.name for p in params]

    def test_gather_grouped_installs_target_layout(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.distributed.sharding import gather_grouped

        prev = mesh_mod._global_mesh
        try:
            mesh_mod._global_mesh = None
            mesh = mesh_mod.build_mesh({"sharding": 4},
                                       devices=jax.devices()[:4])
            mesh_mod.set_mesh(mesh)
            paddle.seed(1)
            params = [nn.Linear(16, 16).weight for _ in range(5)]
            vals = [p.numpy() for p in params]
            sharded = NamedSharding(mesh, P("sharding"))
            for p in params:
                p._data = jax.device_put(p._data, sharded)
            rep = NamedSharding(mesh, P())
            gather_grouped([(p, rep) for p in params], site="test",
                           max_bytes=2 * int(params[0]._data.nbytes))
            for p, v in zip(params, vals):
                assert p._data.sharding.spec == P()
                np.testing.assert_allclose(p.numpy(), v, rtol=1e-6)
        finally:
            mesh_mod._global_mesh = prev

    def test_zero_levels_parity_and_stage3_schedule(self):
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.distributed.sharding import (
            GroupShardedStage3, group_sharded_parallel)
        from paddle_tpu.optimizer import Adam

        prev = mesh_mod._global_mesh
        try:
            mesh_mod._global_mesh = None
            mesh_mod.set_mesh(mesh_mod.build_mesh(
                {"sharding": 4}, devices=jax.devices()[:4]))
            x = paddle.to_tensor(np.random.RandomState(0).randn(
                8, 16).astype(np.float32))
            y = paddle.to_tensor(np.random.RandomState(1).randn(
                8, 4).astype(np.float32))

            def fresh():
                paddle.seed(0)
                m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                                  nn.Linear(32, 32), nn.ReLU(),
                                  nn.Linear(32, 4))
                return m, Adam(learning_rate=1e-3,
                               parameters=m.parameters())

            m0, _ = fresh()
            ref = float(paddle.ops.mean((m0(x) - y) ** 2).numpy())
            finals = {}
            for level in ("os", "os_g", "p_g_os"):
                m, opt = fresh()
                wm, wo, _ = group_sharded_parallel(m, opt, level)
                for it in range(3):
                    loss = paddle.ops.mean((wm(x) - y) ** 2)
                    if it == 0:
                        assert float(loss.numpy()) == pytest.approx(
                            ref, rel=1e-4), level
                    loss.backward()
                    wo.step()
                    wo.clear_grad()
                finals[level] = float(
                    paddle.ops.mean((wm(x) - y) ** 2).numpy())
                if isinstance(wm, GroupShardedStage3):
                    assert wm._gather_schedule is not None
                    assert wm._gather_schedule._groups
            # every level trained to the same loss
            vals = list(finals.values())
            assert max(vals) - min(vals) < 1e-4, finals
        finally:
            mesh_mod._global_mesh = prev

    def test_stage3_save_roundtrip_stays_sharded(self, tmp_path):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.distributed.sharding import (
            group_sharded_parallel, save_group_sharded_model)
        from paddle_tpu.optimizer import Adam

        prev = mesh_mod._global_mesh
        try:
            mesh_mod._global_mesh = None
            mesh_mod.set_mesh(mesh_mod.build_mesh(
                {"sharding": 4}, devices=jax.devices()[:4]))
            paddle.seed(0)
            m = nn.Sequential(nn.Linear(16, 32), nn.Linear(32, 4))
            opt = Adam(learning_rate=1e-3, parameters=m.parameters())
            wm, wo, _ = group_sharded_parallel(m, opt, "p_g_os")
            save_group_sharded_model(wm, str(tmp_path / "ck"))
            # post-save the ZeRO-3 placement is restored
            w = m[0].weight._data
            assert w.sharding.spec != P()
        finally:
            mesh_mod._global_mesh = prev

    def test_stage3_schedule_installs_split_groups(self):
        """A byte-budget split INSIDE one sublayer must still install
        every group — a min-index-only hook would leave the tail group
        staged (replicated copy pinned) but never installed."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.distributed.fleet.meta_optimizers. \
            dygraph_sharding_optimizer import shard_spec_for
        from paddle_tpu.distributed.sharding import Stage3GatherSchedule

        prev = mesh_mod._global_mesh
        try:
            mesh_mod._global_mesh = None
            mesh = mesh_mod.build_mesh({"sharding": 4},
                                       devices=jax.devices()[:4])
            mesh_mod.set_mesh(mesh)
            paddle.seed(6)
            big = nn.Linear(64, 64)
            shardings = {}
            for p in big.parameters():
                spec = shard_spec_for(p.shape, 4, "sharding")
                if spec is not None:
                    sh = NamedSharding(mesh, spec)
                    p._data = jax.device_put(p._data, sh)
                    shardings[p.name] = sh
            sched = Stage3GatherSchedule(
                big, shardings, NamedSharding(mesh, P()),
                max_bytes=int(big.weight._data.nbytes) // 2 + 1)
            assert len(sched._groups) >= 2
            sched.begin_step()
            big(paddle.to_tensor(np.ones((4, 64), np.float32)))
            assert sched._installed == set(range(len(sched._groups)))
            assert not sched._staged     # nothing pinned in staging
        finally:
            mesh_mod._global_mesh = prev

    def test_gather_groups_metric(self):
        from paddle_tpu.core import flags as flags_mod
        from paddle_tpu.observability.metrics import REGISTRY

        prev = flags_mod.get_flag("enable_metrics")
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            self.test_gather_grouped_installs_target_layout()
            snap = REGISTRY.snapshot()
            fam = snap.get("paddle_tpu_sharding_gather_groups_total")
            assert fam is not None
            assert any(s["value"] > 0 for s in fam["series"])
        finally:
            paddle.set_flags({"FLAGS_enable_metrics": prev})


# =========================================================================
# perf layer: donated census + alias-aware peak
# =========================================================================
class TestPerfDonationAccounting:
    def test_census_counts_deleted_buffers_as_zero(self):
        from paddle_tpu.observability.perf import memory as mem

        big = jnp.ones((256, 256), jnp.float32)
        holder = [big]
        pid = mem.register_provider("kv_cache", lambda: list(holder))
        try:
            before = mem.census()["kv_cache"]
            assert before >= big.nbytes
            step = jax.jit(lambda a: a + 1, donate_argnums=(0,))
            out = step(big)
            assert big.is_deleted()
            after = mem.census()["kv_cache"]
            assert after == 0.0
            del out
        finally:
            mem.unregister_provider(pid)

    def test_record_compiled_alias_bytes_lower_peak(self):
        from paddle_tpu.observability.perf import device as pdev

        def f(state):
            return [s * 2 for s in state]

        args = [jnp.ones((128, 128)) for _ in range(4)]
        plain = jax.jit(f).lower(args).compile()
        donated = jax.jit(f, donate_argnums=(0,)).lower(args).compile()
        rec_plain = pdev.record_compiled("test", "plain", plain)
        rec_don = pdev.record_compiled("test", "donated", donated)
        assert rec_plain is not None and rec_don is not None
        if rec_don["alias_bytes"]:
            assert rec_don["peak_bytes"] < rec_plain["peak_bytes"]


# =========================================================================
# fleet_trace transfer/compute overlap report (satellite)
# =========================================================================
class TestTransferComputeOverlap:
    def test_synthetic_overlap_detected(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from tools.fleet_trace import transfer_compute_overlap

        mk = lambda cat, t0, dur, tid=0: {
            "name": "s", "cat": cat, "ph": "X", "pid": 0, "tid": tid,
            "ts": int(t0 * 1e6), "dur": int(dur * 1e6)}
        # io [0,10ms) ∥ device [5,20ms): 5ms overlap
        trace = {"traceEvents": [mk("io", 0.0, 0.010, tid=451),
                                 mk("device", 0.005, 0.020, tid=460)]}
        rep = transfer_compute_overlap(trace)
        assert rep[0]["overlap_s"] == pytest.approx(0.005, abs=1e-6)
        assert rep[0]["overlap_frac_of_io"] == pytest.approx(0.5,
                                                             abs=1e-3)

    def test_no_overlap_when_serial(self):
        from tools.fleet_trace import transfer_compute_overlap

        mk = lambda cat, t0, dur: {
            "name": "s", "cat": cat, "ph": "X", "pid": 0, "tid": 0,
            "ts": int(t0 * 1e6), "dur": int(dur * 1e6)}
        trace = {"traceEvents": [mk("io", 0.0, 0.005),
                                 mk("device", 0.005, 0.010)]}
        rep = transfer_compute_overlap(trace)
        assert rep[0]["overlap_s"] == 0.0

    def test_end_to_end_prefetched_loop_shows_overlap(self, tmp_path):
        """A real prefetched train loop, profiled and exported: the
        merged timeline must VISIBLY show transfer/compute overlap —
        the async runtime's acceptance evidence."""
        from paddle_tpu import profiler
        from paddle_tpu.observability.perf.device import timed_section
        from tools.fleet_trace import (merge_traces,
                                       transfer_compute_overlap)

        paddle.seed(3)
        w = jnp.asarray(np.random.RandomState(0).randn(
            256, 256).astype(np.float32))

        @jax.jit
        def step(w, x):
            for _ in range(8):
                x = jnp.tanh(x @ w)
            return x

        batches = [np.random.RandomState(i).randn(
            256, 256).astype(np.float32) for i in range(6)]
        # warm
        jax.block_until_ready(step(w, jnp.asarray(batches[0])))

        def place(b):
            time.sleep(0.002)    # representative host-side fetch work
            return jnp.asarray(b)

        prof = profiler.Profiler(
            on_trace_ready=profiler.export_chrome_tracing(
                str(tmp_path)))
        prof.start()
        pf = DevicePrefetcher(iter(batches), depth=2, place_fn=place)
        try:
            out = None
            for x in pf:
                with timed_section("train") as ts:
                    out = ts.track(step(w, x))
        finally:
            pf.close()
        prof.stop()
        trace_file = prof.trace_path
        merged = merge_traces([trace_file])
        rep = transfer_compute_overlap(merged)
        total_overlap = sum(o["overlap_s"] for o in rep.values())
        total_io = sum(o["io_s"] for o in rep.values())
        assert total_io > 0, "no io.prefetch spans in the timeline"
        assert total_overlap > 0, (
            "prefetch transfer never overlapped device compute "
            f"(report: {rep})")
