"""Round-6 satellite regressions (ISSUE 1).

1. gloo_* sync primitives route to the real barrier once the parallel
   env is up (VERDICT Weak #4 — a silent no-op corrupts ported
   rank-0-writes-checkpoint scripts).
2. Pallas autotune: positive-list TPU backend gate, schema-stamped cache
   entries that invalidate stale winners, timings emitted under the
   log-level flag (ADVICE r5 lows).
3. Auto-parallel Engine folds per-param ParamAttr regularizers into the
   traced grads exactly as eager Optimizer.step does.
4. Sharding stage-2/3 no longer silently drop offload=True.
"""
from __future__ import annotations

import json
import logging

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import mesh as mesh_mod


@pytest.fixture(autouse=True)
def _rng_neutral():
    """Keep the global key stream exactly as downstream test files expect:
    layer inits / paddle.seed here must not shift order-fragile tests
    (e.g. svd_lowrank in test_submodule_tail) that draw from it later."""
    state = paddle.get_rng_state()
    yield
    paddle.set_rng_state(state)


@pytest.fixture()
def sharding_mesh():
    old = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": 2, "sharding": 4}))
    yield mesh_mod.get_mesh()
    mesh_mod.set_mesh(old)


# ------------------------------------------------------------------- gloo
class TestGlooRouting:
    def test_barrier_noop_before_init(self, monkeypatch):
        from paddle_tpu.distributed import parallel, tail
        calls = []
        monkeypatch.setattr(parallel, "_initialized", False)
        monkeypatch.setattr(mesh_mod, "has_mesh", lambda: False)
        monkeypatch.setattr(
            "paddle_tpu.distributed.communication.collective.barrier",
            lambda group=None: calls.append(1))
        tail.gloo_barrier()   # pre-init: nothing to synchronize against
        assert calls == []

    def test_barrier_real_after_init(self, monkeypatch):
        from paddle_tpu.distributed import parallel, tail
        calls = []
        monkeypatch.setattr(parallel, "_initialized", True)
        monkeypatch.setattr(
            "paddle_tpu.distributed.communication.collective.barrier",
            lambda group=None: calls.append(1))
        tail.gloo_barrier()
        assert calls == [1]
        tail.gloo_release()   # release fences once more
        assert calls == [1, 1]

    def test_gloo_init_fences_but_never_forces_init(self, monkeypatch):
        # pre-init: a no-op that must NOT call init_parallel_env (that
        # would lock the default mesh and silently discard a later
        # init_parallel_env(mesh_shape=...) topology choice); post-init:
        # fences startup like the gloo ring rendezvous would
        from paddle_tpu.distributed import parallel, tail
        inits, fences = [], []
        monkeypatch.setattr(
            "paddle_tpu.distributed.parallel.init_parallel_env",
            lambda *a, **k: inits.append(1))
        monkeypatch.setattr(
            "paddle_tpu.distributed.communication.collective.barrier",
            lambda group=None: fences.append(1))
        monkeypatch.setattr(parallel, "_initialized", False)
        monkeypatch.setattr(mesh_mod, "has_mesh", lambda: False)
        tail.gloo_init_parallel_env(0, 1, "127.0.0.1:6170")
        assert inits == [] and fences == []
        monkeypatch.setattr(parallel, "_initialized", True)
        tail.gloo_init_parallel_env(0, 1, "127.0.0.1:6170")
        assert inits == [] and fences == [1]

    def test_end_to_end_barrier_executes(self):
        # on the 8-device virtual platform the routed barrier really runs
        # the all-reduce fence (init_parallel_env is idempotent)
        from paddle_tpu.distributed import parallel, tail
        parallel.init_parallel_env()
        tail.gloo_barrier()   # must not raise


# --------------------------------------------------------------- autotune
class TestAutotuneFixes:
    def test_backend_gate_is_positive_list(self, monkeypatch):
        import jax
        from paddle_tpu.ops.pallas import autotune as at
        # CPU test platform: not a TPU backend
        assert at.is_tpu_backend() is False
        # a GPU backend must NOT pass the gate (the old "not cpu" check
        # let GPU runs cache TPU tile probes)
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        assert at.is_tpu_backend() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert at.is_tpu_backend() is True

    def test_cache_entries_schema_stamped(self, tmp_path):
        from paddle_tpu.ops.pallas import autotune as at
        path = str(tmp_path / "a.json")
        cache = at.AutotuneCache(path)
        cache.put("k", [512, 512])
        with open(path) as f:
            raw = json.load(f)
        assert raw["k"]["schema"] == at.SCHEMA_VERSION
        assert raw["k"]["stamp"] > 0
        assert cache.get("k") == [512, 512]

    def test_stale_schema_invalidated(self, tmp_path):
        from paddle_tpu.ops.pallas import autotune as at
        path = str(tmp_path / "b.json")
        with open(path, "w") as f:
            json.dump({
                "legacy": [1024, 1024],  # pre-stamp bare value
                "old": {"schema": at.SCHEMA_VERSION - 1, "stamp": 1.0,
                        "value": [2048, 2048]},
                "ok": {"schema": at.SCHEMA_VERSION, "stamp": 2.0,
                       "value": [256, 256]},
            }, f)
        cache = at.AutotuneCache(path)
        assert cache.get("legacy") is None
        assert cache.get("old") is None
        assert cache.get("ok") == [256, 256]

    def test_timings_logged_under_flag(self, tmp_path, monkeypatch,
                                       caplog):
        import jax.numpy as jnp
        from paddle_tpu.core import flags
        from paddle_tpu.ops.pallas import autotune as at
        monkeypatch.setattr(at, "_cache",
                            at.AutotuneCache(str(tmp_path / "c.json")))
        old = flags.get_flag("log_level")
        flags.set_flags({"log_level": 1})
        # the paddle_tpu parent logger does not propagate to root (rank-
        # aware handler), so capture on the logger itself
        lg = logging.getLogger("paddle_tpu.autotune")
        lg.addHandler(caplog.handler)
        try:
            with caplog.at_level(logging.INFO, "paddle_tpu.autotune"):
                at.autotune("ktimings", [(1, 1), (2, 2)],
                            lambda c, i: jnp.zeros(()), default=(0, 0),
                            warmup=1, iters=1)
        finally:
            lg.removeHandler(caplog.handler)
            flags.set_flags({"log_level": old})
        msgs = [r.getMessage() for r in caplog.records]
        assert any("ktimings" in m and "ms" in m for m in msgs)

    def test_described_candidates_reach_the_log_and_the_span(
            self, tmp_path, monkeypatch, caplog):
        """``describe`` (the flash kernels' plan): beside every time of the
        log line, and the winner's on the ``autotune:<key>`` span."""
        import jax.numpy as jnp
        from paddle_tpu.core import flags
        from paddle_tpu.observability import trace
        from paddle_tpu.ops.pallas import autotune as at
        monkeypatch.setattr(at, "_cache",
                            at.AutotuneCache(str(tmp_path / "c.json")))
        old = flags.get_flag("log_level")
        flags.set_flags({"log_level": 1})
        lg = logging.getLogger("paddle_tpu.autotune")
        lg.addHandler(caplog.handler)
        trace.clear()
        trace.activate()
        try:
            with caplog.at_level(logging.INFO, "paddle_tpu.autotune"):
                best = at.autotune(
                    "kplan", [(1, 1), (2, 2)], lambda c, i: jnp.zeros(()),
                    default=(1, 1), warmup=1, iters=1,
                    describe=lambda c: {"executed_share": c[0] / 2})
        finally:
            trace.deactivate()
            lg.removeHandler(caplog.handler)
            flags.set_flags({"log_level": old})
        (line,) = [r.getMessage() for r in caplog.records
                   if "kplan" in r.getMessage()]
        assert "{'executed_share': 0.5}" in line
        assert "{'executed_share': 1.0}" in line
        (span,) = [e for e in trace.drain() if e[0] == "autotune:kplan"]
        assert span[5]["winner"] == str(best)
        assert span[5]["executed_share"] == best[0] / 2
        assert span[5]["candidates"] == 2


# ------------------------------------------------- Engine regularizer fold
class TestEngineRegularizerParity:
    def test_engine_matches_eager_with_param_attr_regularizer(
            self, monkeypatch):
        import jax.numpy as jnp
        import paddle_tpu.distributed as dist
        from paddle_tpu.regularizer import L2Decay

        old = mesh_mod.get_mesh()
        mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": 8}))
        try:
            def build():
                paddle.seed(11)
                net = nn.Linear(
                    6, 3,
                    weight_attr=nn.ParamAttr(regularizer=L2Decay(0.3)))
                opt = paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters())
                return net, opt

            rng = np.random.RandomState(4)
            x = rng.randn(8, 6).astype(np.float32)
            y = rng.randn(8, 3).astype(np.float32)

            def loss_fn(out, yy):
                return paddle.ops.mean((out - yy) ** 2)

            # eager reference step
            net_e, opt_e = build()
            loss = loss_fn(net_e(paddle.to_tensor(x)),
                           paddle.to_tensor(y))
            loss.backward()
            opt_e.step()
            want = [np.asarray(p._data) for p in net_e.parameters()]

            # the weight carries a regularizer: the eager update must
            # differ from a no-regularizer run (guards the guard)
            net_p, opt_p = build()
            for p in net_p.parameters():
                p.regularizer = None
            loss = loss_fn(net_p(paddle.to_tensor(x)),
                           paddle.to_tensor(y))
            loss.backward()
            opt_p.step()
            assert not np.allclose(np.asarray(net_p.weight._data),
                                   want[0])

            # Engine traced step
            net_s, opt_s = build()
            eng = dist.Engine(net_s, loss=loss_fn, optimizer=opt_s)
            eng.prepare()
            pa = [p._data for p in eng._params]
            state = eng._init_opt_state(pa)
            _, new_pa, _ = eng._train_step(pa, state,
                                           jnp.asarray(0.1, jnp.float32),
                                           jnp.asarray(x), jnp.asarray(y))
            by_id = {id(p): a for p, a in zip(eng._params, new_pa)}
            got = [np.asarray(by_id[id(p)]) for p in net_s.parameters()]
            for g, w in zip(got, want):
                np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)
        finally:
            mesh_mod.set_mesh(old)


# ------------------------------------------------------------ offload flag
class TestOffloadNotSilentlyDropped:
    def test_stage2_warns_and_stores(self, sharding_mesh):
        from paddle_tpu.distributed.fleet.meta_parallel.sharding. \
            group_sharded_optimizer_stage2 import \
            GroupShardedOptimizerStage2
        model = nn.Linear(16, 16)
        inner = paddle.optimizer.Adam(learning_rate=0.01,
                                      parameters=model.parameters())
        with pytest.warns(UserWarning, match="offload"):
            opt = GroupShardedOptimizerStage2(model.parameters(),
                                              optim=inner, offload=True)
        assert opt._offload is True
        opt.untag_grads()

    def test_stage3_warns_and_stores(self, sharding_mesh):
        from paddle_tpu.distributed.fleet.meta_parallel.sharding. \
            group_sharded_stage3 import GroupShardedStage3
        model = nn.Linear(16, 16)
        with pytest.warns(UserWarning, match="offload"):
            wrapped = GroupShardedStage3(model, offload=True)
        assert wrapped._offload is True

    def test_no_warning_without_offload(self, sharding_mesh,
                                        recwarn):
        from paddle_tpu.distributed.fleet.meta_parallel.sharding. \
            group_sharded_stage3 import GroupShardedStage3
        GroupShardedStage3(nn.Linear(16, 16), offload=False)
        assert not [w for w in recwarn.list
                    if "offload" in str(w.message)]
