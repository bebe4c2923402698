"""Round-12 tests: device-time performance attribution.

Covers the perf layer end to end: analytical cost model closed forms AND
their cross-check against XLA's own cost_analysis on compiled programs,
the attribution-sums-to-step-time property on a real train loop, the
attributed HBM census, compiled-program capture at to_static/SOT compile
time, the per-op metric accumulation in dispatch, the perf_report
renderer, and the process-unique metrics-dump suffix.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.observability import REGISTRY, perf
from paddle_tpu.observability.perf import costmodel, device, memory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import perf_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    paddle.set_flags({"FLAGS_enable_metrics": False,
                      "FLAGS_perf_op_cost": False,
                      "FLAGS_perf_capture": False,
                      "FLAGS_benchmark": False})


# =========================================================================
# Cost model — closed forms
# =========================================================================
class TestCostModelClosedForm:
    def test_matmul(self):
        c = costmodel.cost_of("matmul", [(64, 128), (128, 32)],
                              [np.float32, np.float32])
        assert c.flops == 2 * 64 * 128 * 32
        assert c.bytes_read == 4 * (64 * 128 + 128 * 32)

    def test_matmul_transpose_and_batch(self):
        c = costmodel.cost_of("matmul", [(3, 5, 64), (3, 7, 64)],
                              [np.float32] * 2, {"transpose_y": True},
                              [(3, 5, 7)])
        assert c.flops == 2 * 3 * 5 * 64 * 7

    def test_linear_bias(self):
        c = costmodel.cost_of("linear", [(8, 16), (16, 32), (32,)],
                              [np.float32] * 3, {}, [(8, 32)])
        assert c.flops == 2 * 8 * 16 * 32 + 8 * 32

    def test_conv2d(self):
        c = costmodel.cost_of("conv2d", [(2, 3, 16, 16), (8, 3, 3, 3)],
                              [np.float32] * 2, {"stride": 1},
                              [(2, 8, 16, 16)])
        assert c.flops == 2 * 2 * 8 * 16 * 16 * 3 * 3 * 3

    def test_attention(self):
        b, s, h, d = 2, 32, 4, 16
        c = costmodel.cost_of("flash_attention", [(b, s, h, d)] * 3,
                              [np.float32] * 3, {}, [(b, s, h, d)])
        assert c.flops == 4 * b * h * s * s * d + 5 * b * h * s * s
        # flash traffic model: qkv in + out, no S^2 round-trip
        assert c.bytes == 4 * 4 * b * s * h * d

    def test_layer_norm(self):
        c = costmodel.cost_of("layer_norm", [(4, 128)], [np.float32])
        assert c.flops == 8 * 4 * 128

    def test_bf16_bytes(self):
        c = costmodel.cost_of("matmul", [(8, 8), (8, 8)],
                              [jnp.bfloat16, jnp.bfloat16])
        assert c.bytes_read == 2 * (64 + 64)

    def test_collectives(self):
        assert costmodel.collective_cost(
            "all_reduce", 1000, 4).bytes_read == 1500
        assert costmodel.collective_cost(
            "all_gather", 1000, 4).bytes_read == 750
        assert costmodel.collective_cost(
            "broadcast", 1000, 4).bytes_read == 1000
        assert costmodel.collective_cost(
            "all_reduce", 1000, 1).bytes_read == 0

    def test_unknown_op_is_none(self):
        assert costmodel.cost_of("definitely_not_an_op", [(4,)]) is None

    def test_attach_is_idempotent_and_broad(self):
        n1 = perf.attach_cost_models()
        n2 = perf.attach_cost_models()
        assert n1 == n2 >= 300
        from paddle_tpu.ops.registry import OPS
        assert OPS["matmul"].cost_fn is costmodel.matmul_cost

    def test_registry_cost_fn_override_wins(self):
        """register(..., cost_fn=) beats the generic name table — the
        documented extension contract."""
        from paddle_tpu.ops import registry

        def my_fn(shapes, dtypes, attrs, outs):
            return costmodel.OpCost(flops=42.0)

        prev = registry.OPS["matmul"].cost_fn
        registry.OPS["matmul"].cost_fn = my_fn
        try:
            assert costmodel.cost_of("matmul", [(4, 4), (4, 4)]).flops == 42.0
        finally:
            registry.OPS["matmul"].cost_fn = prev
        assert costmodel.cost_of("matmul",
                                 [(4, 4), (4, 4)]).flops == 2 * 4 * 4 * 4

    def test_roofline_bound(self):
        c = costmodel.OpCost(flops=1000.0, bytes_read=10.0,
                             bytes_written=10.0)
        r = costmodel.roofline_bound(c, peak_flops=1e12, peak_bw=1e11)
        assert r["bound"] == "compute"           # AI 50 > ridge 10
        assert r["attainable_flops"] == 1e12


# =========================================================================
# Cost model — XLA cross-check (tolerance-based, per ISSUE fixture list)
# =========================================================================
class TestCostModelVsXLA:
    def _xla(self, f, *args):
        rec = device.analyze(f, *args)
        assert rec is not None and rec["flops"] > 0
        return rec

    def test_matmul_flops_exact(self):
        a = jnp.ones((64, 128), jnp.float32)
        b = jnp.ones((128, 32), jnp.float32)
        rec = self._xla(lambda x, y: x @ y, a, b)
        c = costmodel.cost_of("matmul", [(64, 128), (128, 32)],
                              [np.float32] * 2)
        assert costmodel.relative_error(c.flops, rec["flops"]) < 0.01
        # bytes: XLA counts actual accesses; the model is the minimal
        # floor — same order of magnitude
        assert 0.25 < c.bytes / rec["bytes_accessed"] < 4.0

    def test_conv2d_flops(self):
        x = jnp.ones((2, 3, 16, 16), jnp.float32)
        w = jnp.ones((8, 3, 3, 3), jnp.float32)

        def conv(x, w):
            return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME")

        rec = self._xla(conv, x, w)
        c = costmodel.cost_of("conv2d", [(2, 3, 16, 16), (8, 3, 3, 3)],
                              [np.float32] * 2, {}, [(2, 8, 16, 16)])
        # SAME padding: XLA skips multiplies at the borders the
        # analytical formula counts
        assert costmodel.relative_error(c.flops, rec["flops"]) < 0.15

    def test_attention_flops(self):
        b, s, h, d = 2, 32, 4, 16
        q = jnp.ones((b, s, h, d), jnp.float32)

        def sdpa(q, k, v):
            logits = jnp.einsum("bshd,bthd->bhst", q, k) / (d ** 0.5)
            p = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bhst,bthd->bshd", p, v)

        rec = self._xla(sdpa, q, q, q)
        c = costmodel.cost_of("flash_attention", [(b, s, h, d)] * 3,
                              [np.float32] * 3, {}, [(b, s, h, d)])
        assert costmodel.relative_error(c.flops, rec["flops"]) < 0.10

    def test_layer_norm_flops(self):
        x = jnp.ones((4, 128), jnp.float32)
        g = jnp.ones((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)

        def ln(x, g, b):
            m = x.mean(-1, keepdims=True)
            v = ((x - m) ** 2).mean(-1, keepdims=True)
            return (x - m) / jnp.sqrt(v + 1e-5) * g + b

        rec = self._xla(ln, x, g, b)
        c = costmodel.cost_of("layer_norm", [(4, 128)], [np.float32])
        assert costmodel.relative_error(c.flops, rec["flops"]) < 0.10

    def test_xla_cost_sums_partitions(self):
        fake = type("C", (), {"cost_analysis": lambda self: [
            {"flops": 10.0, "bytes accessed": 5.0},
            {"flops": 7.0, "bytes accessed": 2.0}]})()
        out = costmodel.xla_cost(fake)
        assert out == {"flops": 17.0, "bytes_accessed": 7.0,
                       "transcendentals": 0.0}


# =========================================================================
# Device profiler — attribution
# =========================================================================
class TestAttribution:
    def test_interval_resolution_priorities(self):
        # hand-built timeline: one 1.0s step; 0.4s device, 0.2s
        # collective INSIDE the device wait, 0.1s host outside both
        spans = [
            ("step", "step", 0.0, 1.0, 0, None),
            ("wait", "device", 0.1, 0.5, 0, None),
            ("ar", "collective", 0.2, 0.4, 0, None),
            ("op", "dispatch", 0.6, 0.7, 0, None),
        ]
        out = device.attribute(spans)
        tot = out["total"]
        assert tot["n_steps"] == 1
        assert abs(tot["collective_s"] - 0.2) < 1e-9
        assert abs(tot["compute_s"] - 0.2) < 1e-9     # device minus coll
        assert abs(tot["host_s"] - 0.1) < 1e-9
        assert abs(tot["idle_s"] - 0.5) < 1e-9
        s = (tot["compute_s"] + tot["collective_s"] + tot["host_s"]
             + tot["idle_s"])
        assert abs(s - tot["step_s"]) < 1e-9          # exact sum

    def test_sums_to_step_time_on_train_loop(self):
        """ISSUE acceptance: attribution of a real small train loop sums
        to measured step time within 10% (exact by construction here),
        with nonzero compute from the jitted step's device wait."""
        paddle.seed(0)
        w = jnp.asarray(np.random.randn(64, 64).astype(np.float32))
        x = jnp.asarray(np.random.randn(128, 64).astype(np.float32))
        y = jnp.asarray(np.random.randn(128, 64).astype(np.float32))

        @jax.jit
        def train_step(w):
            def loss(w):
                return jnp.mean((jnp.tanh(x @ w) - y) ** 2)
            g = jax.grad(loss)(w)
            return w - 0.1 * g

        state = {"w": w}

        def step():
            state["w"] = train_step(state["w"])
            return state["w"]

        out = perf.step_attribution(step, iters=3, warmup=1)
        tot = out["total"]
        assert tot["n_steps"] == 3
        parts = (tot["compute_s"] + tot["collective_s"] + tot["host_s"]
                 + tot["idle_s"])
        assert abs(parts - tot["step_s"]) <= 0.1 * tot["step_s"] + 1e-9
        assert tot["compute_s"] > 0          # the block wait is real
        for st in out["steps"]:
            p = (st["compute_s"] + st["collective_s"] + st["host_s"]
                 + st["idle_s"])
            assert abs(p - st["step_s"]) <= 0.1 * st["step_s"] + 1e-9

    def test_measure_blocks(self):
        x = jnp.ones((256, 256), jnp.float32)
        dt = device.measure(lambda a: a @ a, x, warmup=1, iters=2)
        assert dt > 0

    def test_timed_section_emits_spans(self):
        from paddle_tpu.observability import trace
        trace.clear()
        trace.activate()
        try:
            with device.timed_section("s1") as ts:
                ts.track(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
        finally:
            trace.deactivate()
        spans = trace.drain()
        cats = {cat for _n, cat, *_ in spans}
        assert "device" in cats and "step" in cats


# =========================================================================
# HBM memory census
# =========================================================================
class TestMemoryCensus:
    def test_param_grad_optimizer_attribution(self):
        from paddle_tpu import nn

        paddle.seed(0)
        lin = nn.Linear(32, 32)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=lin.parameters())
        x = paddle.to_tensor(np.random.randn(4, 32).astype(np.float32))
        loss = paddle.ops.mean(lin(x) ** 2)
        loss.backward()
        opt.step()                        # materializes moments
        c = memory.census()
        assert c["params"] >= 32 * 32 * 4
        assert c["grads"] >= 32 * 32 * 4
        assert c["optimizer_state"] >= 2 * 32 * 32 * 4
        assert c["total"] >= (c["params"] + c["grads"]
                              + c["optimizer_state"])

    def test_dedup_one_tag_per_buffer(self):
        a = jnp.ones((16,), jnp.float32)
        before = memory.census(include_unclaimed=False)
        p1 = memory.register_provider("params", lambda: [a])
        p2 = memory.register_provider("optimizer_state", lambda: [a])
        try:
            c = memory.census(include_unclaimed=False)
            assert c["params"] == before["params"] + a.nbytes
            # second provider must not double-count the same buffer
            assert c["optimizer_state"] == before["optimizer_state"]
        finally:
            memory.unregister_provider(p1)
            memory.unregister_provider(p2)

    def test_provider_dies_with_object(self):
        class Holder:
            def __init__(self):
                self.buf = jnp.ones((1024,), jnp.float32)

        h = Holder()
        memory.register_object("kv_cache", h, lambda o: [o.buf])
        assert memory.census(include_unclaimed=False)["kv_cache"] >= 4096
        del h
        import gc
        gc.collect()
        assert memory.census(include_unclaimed=False)["kv_cache"] == 0.0

    def test_high_water_per_phase(self):
        memory.reset_high_water()
        big = jnp.ones((4096,), jnp.float32)
        pid = memory.register_provider("kv_cache", lambda: [big])
        try:
            memory.update_high_water("phase_a")
        finally:
            memory.unregister_provider(pid)
        memory.update_high_water("phase_b")
        hw = memory.high_water()
        assert hw["phase_a"] >= big.nbytes
        assert hw["phase_a"] > hw["phase_b"] - 1  # a saw the big buffer
        assert memory.high_water("phase_a")["kv_cache"] >= big.nbytes

    def test_hbm_metrics_exported(self):
        paddle.set_flags({"FLAGS_enable_metrics": True})
        REGISTRY.reset()
        memory.refresh_metrics()
        snap = REGISTRY.snapshot()
        assert "paddle_tpu_hbm_live_bytes" in snap
        tags = {s["labels"][0]
                for s in snap["paddle_tpu_hbm_live_bytes"]["series"]}
        assert {"params", "grads", "optimizer_state", "kv_cache",
                "activations"} <= tags


# =========================================================================
# Compiled-program capture (to_static / SOT) + dispatch op-cost metrics
# =========================================================================
class TestCaptureAndDispatchCost:
    def test_to_static_capture(self):
        from paddle_tpu import nn
        from paddle_tpu.jit.api import to_static

        device.clear_compiled()
        paddle.set_flags({"FLAGS_perf_capture": True})
        paddle.seed(0)
        lin = nn.Linear(16, 16)

        @to_static
        def f(x):
            return paddle.ops.tanh(lin(x))

        x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
        f(x)
        progs = device.compiled_programs("to_static")
        assert progs and progs[0]["flops"] > 0
        assert progs[0]["peak_bytes"] > 0

    def test_sot_capture_on_graph_break(self):
        from paddle_tpu import nn
        from paddle_tpu.jit.api import to_static

        device.clear_compiled()
        paddle.set_flags({"FLAGS_perf_capture": True})
        paddle.seed(0)
        lin = nn.Linear(16, 16)

        @to_static
        def g(x):
            y = lin(x)
            if float(y.sum()) > -1e9:      # host sync → SOT fallback
                y = y + 1.0
            return y

        x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
        with pytest.warns(UserWarning):
            g(x)
        assert device.compiled_programs("sot")

    def test_capture_off_records_nothing(self):
        device.clear_compiled()
        assert not perf.capture_enabled()
        compiled = jax.jit(lambda a: a + 1).lower(jnp.ones((4,))).compile()
        # record_compiled is explicit; the gate is at call sites — verify
        # the to_static site respects the flag
        from paddle_tpu.jit.api import to_static

        @to_static
        def f(x):
            return x + 1

        f(paddle.to_tensor(np.ones((4,), np.float32)))
        assert device.compiled_programs("to_static") == []
        del compiled

    def test_dispatch_accumulates_modeled_cost(self):
        perf.attach_cost_models()
        paddle.set_flags({"FLAGS_enable_metrics": True,
                          "FLAGS_perf_op_cost": True})
        REGISTRY.reset()
        a = paddle.to_tensor(np.random.randn(32, 64).astype(np.float32))
        b = paddle.to_tensor(np.random.randn(64, 16).astype(np.float32))
        paddle.ops.matmul(a, b)
        paddle.ops.matmul(a, b)
        m = REGISTRY.get("paddle_tpu_perf_op_flops_total")
        assert m.value(op="matmul") == 2 * (2 * 32 * 64 * 16)
        mb = REGISTRY.get("paddle_tpu_perf_op_bytes_total")
        assert mb.value(op="matmul") > 0

    def test_dispatch_cost_off_by_default(self):
        paddle.set_flags({"FLAGS_enable_metrics": True})
        REGISTRY.reset()
        a = paddle.to_tensor(np.ones((8, 8), np.float32))
        paddle.ops.matmul(a, a)
        m = REGISTRY.get("paddle_tpu_perf_op_flops_total")
        assert m is None or m.value(op="matmul") == 0


# =========================================================================
# perf_report
# =========================================================================
class TestPerfReport:
    def _sample_report(self):
        op_time = {"matmul": {"calls": 4, "total_s": 0.01},
                   "layer_norm": {"calls": 4, "total_s": 0.002}}
        op_cost = {"matmul": {"flops": 4e9, "bytes": 1e8},
                   "layer_norm": {"flops": 1e7, "bytes": 2e7}}
        attribution = device.attribute([
            ("step", "step", 0.0, 0.012, 0, None),
            ("wait", "device", 0.0, 0.01, 0, None),
        ])
        return perf_report.build_report(op_time, op_cost,
                                        attribution=attribution,
                                        hbm={"params": 1000, "total": 2000})

    def test_build_report_structure(self):
        r = self._sample_report()
        assert r["ops"][0]["op"] == "matmul"     # sorted by host time
        row = r["ops"][0]
        assert row["achieved_gflops_per_s"] == pytest.approx(400.0)
        assert row["bound"] in ("compute", "bandwidth")
        assert 0 <= row["pct_of_roofline"]
        assert "whole_step" in r and r["whole_step"]["mfu"] >= 0
        assert r["device"]["peak_gflops_per_s"] > 0

    def test_markdown_contains_tables(self):
        md = perf_report.render_markdown(self._sample_report())
        assert "Per-op roofline" in md
        assert "Step-time attribution" in md
        assert "matmul" in md and "% roof" in md
        assert "HBM census" in md

    def test_snapshot_roundtrip(self):
        perf.attach_cost_models()
        paddle.set_flags({"FLAGS_enable_metrics": True,
                          "FLAGS_perf_op_cost": True})
        REGISTRY.reset()
        a = paddle.to_tensor(np.random.randn(16, 16).astype(np.float32))
        paddle.ops.matmul(a, a)
        snap = REGISTRY.snapshot()
        r = perf_report.build_report_from_snapshot(snap)
        ops = {row["op"] for row in r["ops"]}
        assert "matmul" in ops


# =========================================================================
# Metrics-dump process-unique suffix
# =========================================================================
class TestMetricsDumpSuffix:
    def test_dump_path_rank_env(self, monkeypatch):
        from paddle_tpu import observability as obs

        monkeypatch.delenv("PADDLE_TPU_METRICS_SUFFIX", raising=False)
        monkeypatch.setenv(obs._PRIMARY_PID_ENV, str(os.getpid()))
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        assert obs._dump_path("/tmp/m.json") == "/tmp/m.json.rank3"
        monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
        assert obs._dump_path("/tmp/m.json") == "/tmp/m.json"

    def test_dump_path_rank_worker_gets_both_suffixes(self, monkeypatch):
        """A fork/spawn worker OF rank N must not clobber rank N's own
        file — the pid rides along with the rank suffix."""
        from paddle_tpu import observability as obs

        monkeypatch.delenv("PADDLE_TPU_METRICS_SUFFIX", raising=False)
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
        monkeypatch.setenv(obs._PRIMARY_PID_ENV, str(os.getpid() + 1))
        assert (obs._dump_path("/tmp/m.json")
                == f"/tmp/m.json.rank2.pid{os.getpid()}")

    def test_dump_path_explicit_suffix_wins(self, monkeypatch):
        from paddle_tpu import observability as obs

        monkeypatch.setenv("PADDLE_TPU_METRICS_SUFFIX", "worker7")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        assert obs._dump_path("/tmp/m.json") == "/tmp/m.json.worker7"

    def test_dump_path_child_process_gets_pid(self, monkeypatch):
        from paddle_tpu import observability as obs

        monkeypatch.delenv("PADDLE_TPU_METRICS_SUFFIX", raising=False)
        monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
        monkeypatch.delenv("RANK", raising=False)
        # the primary pid travels via the ENVIRONMENT so fork AND spawn
        # children both see they are not the owner of the bare path
        monkeypatch.setenv(obs._PRIMARY_PID_ENV, str(os.getpid() + 1))
        assert obs._dump_path("/tmp/m.json") == f"/tmp/m.json.pid{os.getpid()}"
        monkeypatch.setenv(obs._PRIMARY_PID_ENV, str(os.getpid()))
        assert obs._dump_path("/tmp/m.json") == "/tmp/m.json"

    @pytest.mark.slow
    def test_rank_worker_writes_suffixed_file(self, tmp_path):
        from paddle_tpu import observability as obs

        dump = tmp_path / "metrics.json"
        env = dict(os.environ)
        # an independently-launched rank (fresh env, no inherited
        # primary pid) owns its .rankN file
        env.pop(obs._PRIMARY_PID_ENV, None)
        env.update(JAX_PLATFORMS="cpu", FLAGS_enable_metrics="1",
                   PADDLE_TPU_METRICS_DUMP=str(dump),
                   PADDLE_TRAINER_ID="2")
        subprocess.run(
            [sys.executable, "-c",
             "import paddle_tpu, numpy as np; "
             "a = paddle_tpu.to_tensor(np.ones((4,4), np.float32)); "
             "paddle_tpu.ops.matmul(a, a)"],
            env=env, cwd=REPO, check=True, timeout=240)
        assert not dump.exists()
        assert (tmp_path / "metrics.json.rank2").exists()


# =========================================================================
# Serving / loadgen per-tick attribution (satellite)
# =========================================================================
class TestServingAttribution:
    def test_loadgen_reports_prefill_decode_split(self):
        from tools.loadgen import _tiny_engine, run_load

        eng = _tiny_engine()
        eng.warmup()
        rep = run_load(eng, offered_rps=100.0, n_requests=6,
                       max_new_tokens=4)
        eng.drain()
        att = rep["device_attribution"]
        assert att is not None
        assert att["ticks"] > 0
        assert att["prefill_compute_s"] > 0
        assert att["decode_compute_s"] > 0
        share = att["prefill_compute_share"] + att["decode_compute_share"]
        assert share == pytest.approx(1.0, abs=1e-3)
        # kv census: the engine's pages are attributed while it lives
        assert memory.census(include_unclaimed=False)["kv_cache"] > 0
