"""Persistent compilation cache + AOT warmup (paddle_tpu/compile/).

Covers the ISSUE-5 acceptance criteria:
- a second process reusing the cache performs ZERO framework compiles
  for an already-seen signature (trace count 0, pcc_hits_total 1);
- a corrupted cache entry (flip / truncate / torn publish / failed
  rename) is quarantined and recompiled without user-visible failure;
plus the store unit behavior (CRC verify, LRU budget, manifest
tolerance), all three integration sites (to_static, SOT segments,
loaded artifacts/Predictor), and the warm CLI flow.
"""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.compile as pcc
from paddle_tpu import jit, nn
from paddle_tpu.fault import inject
from paddle_tpu.observability import REGISTRY
from paddle_tpu.static import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")
if FIXTURES not in sys.path:
    sys.path.insert(0, FIXTURES)

import pcc_targets  # noqa: E402


@pytest.fixture
def cache_env(tmp_path):
    """Metrics on + cache on, pointed at a per-test directory; restores
    everything afterwards."""
    cache_dir = str(tmp_path / "pcc")
    paddle.set_flags({"FLAGS_enable_metrics": True,
                      "FLAGS_compile_cache": True,
                      "FLAGS_compile_cache_dir": cache_dir})
    REGISTRY.reset()
    yield cache_dir
    paddle.set_flags({"FLAGS_enable_metrics": False,
                      "FLAGS_compile_cache": False,
                      "FLAGS_compile_cache_dir": "",
                      "FLAGS_compile_cache_manifest": ""})
    REGISTRY.reset()
    inject.disarm_all()


def _entry_files(cache_dir):
    return sorted(glob.glob(os.path.join(cache_dir, "*.pcc")))


def _subproc_env():
    """Child env identical to the pytest process (same JAX_PLATFORMS and
    virtual-device XLA_FLAGS — the topology is part of the cache key)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (REPO + os.pathsep + FIXTURES + os.pathsep
                         + env.get("PYTHONPATH", ""))
    return env


# ---------------------------------------------------------------------------
# where the caches live: placed from outside, never under $HOME
# ---------------------------------------------------------------------------
class TestCacheRoot:
    @pytest.fixture(autouse=True)
    def _restore_jax_config(self):
        import jax
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_set_is_left_alone(self, monkeypatch, tmp_path):
        import jax
        from paddle_tpu.compile import cache
        from paddle_tpu.ops.pallas import autotune as at
        machine = str(tmp_path / "machine_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", machine)
        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE_CACHE", raising=False)
        monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert cache.enable_jax_cache() == machine
        # JAX reads the variable itself: no code sets another directory
        assert jax.config.jax_compilation_cache_dir == before
        # the repo's own stores follow the same root
        assert cache.cache_dir() == os.path.join(machine, "paddle_tpu", "pcc")
        assert at.AutotuneCache()._path == os.path.join(
            machine, "paddle_tpu", "autotune.json")

    def test_env_unset_is_the_checkout(self, monkeypatch):
        import jax
        from paddle_tpu.compile import cache
        from paddle_tpu.ops.pallas import autotune as at
        for var in ("JAX_COMPILATION_CACHE_DIR", "PADDLE_TPU_AUTOTUNE_CACHE",
                    "PADDLE_TPU_COMPILE_CACHE_DIR"):
            monkeypatch.delenv(var, raising=False)
        fixed = os.path.join(REPO, ".jax_cache")
        assert cache.enable_jax_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert cache.enable_jax_cache() == fixed        # fixed: no pid/time
        home = os.path.expanduser("~")
        for path in (cache.cache_dir(), at.cache_path()):
            assert path.startswith(fixed + os.sep)
            assert not path.startswith(os.path.join(home, ".cache"))


# ---------------------------------------------------------------------------
# store unit behavior
# ---------------------------------------------------------------------------
class TestCacheStore:
    def test_roundtrip(self, cache_env):
        c = pcc.CompileCache(cache_env)
        assert c.put("k1", b"payload-bytes", {"site": "test", "n": 3})
        meta, payload = c.get("k1", site="test")
        assert payload == b"payload-bytes"
        assert meta["site"] == "test" and meta["n"] == 3

    def test_absent_is_miss(self, cache_env):
        c = pcc.CompileCache(cache_env)
        assert c.get("nope", site="test") is None
        assert REGISTRY.get("paddle_tpu_pcc_misses_total").total() == 1

    @pytest.mark.parametrize("damage", ["flip_meta", "flip_payload",
                                        "truncate", "magic"])
    def test_corruption_quarantined(self, cache_env, damage):
        c = pcc.CompileCache(cache_env)
        c.put("k1", b"x" * 256, {"site": "test"})
        path = _entry_files(cache_env)[0]
        data = bytearray(open(path, "rb").read())
        if damage == "flip_meta":
            data[12] ^= 0xFF
        elif damage == "flip_payload":
            data[-10] ^= 0xFF
        elif damage == "truncate":
            data = data[:len(data) // 2]
        else:
            data[0] ^= 0xFF
        open(path, "wb").write(bytes(data))
        assert c.get("k1", site="test") is None
        assert not _entry_files(cache_env)          # moved aside
        qdir = os.path.join(cache_env, "quarantine")
        assert len(os.listdir(qdir)) == 1           # evidence kept
        assert REGISTRY.get(
            "paddle_tpu_pcc_quarantined_total").total() == 1

    def test_torn_publish_leaves_no_entry(self, cache_env):
        c = pcc.CompileCache(cache_env)
        with inject.armed("pcc.write_truncate_after_bytes", after_bytes=20):
            assert not c.put("k1", b"y" * 500, {"site": "test"})
        assert not _entry_files(cache_env)
        assert c.get("k1", site="test") is None     # miss, no crash

    def test_rename_fail_leaves_no_entry(self, cache_env):
        c = pcc.CompileCache(cache_env)
        with inject.armed("io.rename_fail"):
            assert not c.put("k1", b"z" * 500, {"site": "test"})
        assert not _entry_files(cache_env)

    def test_lru_budget_evicts_oldest(self, cache_env):
        c = pcc.CompileCache(cache_env, size_limit_mb=1)
        for i in range(5):
            c.put(f"k{i}", b"x" * 300_000, {"site": "test"})
        assert c.total_bytes() <= 1 << 20
        live = {e["key"] for e in c.entries()}
        assert "k4" in live and "k0" not in live
        assert REGISTRY.get("paddle_tpu_pcc_evicted_total").total() >= 1

    def test_lru_touch_protects_hot_entry(self, cache_env):
        c = pcc.CompileCache(cache_env, size_limit_mb=1)
        c.put("hot", b"x" * 300_000, {"site": "test"})
        for i in range(3):
            c.get("hot", site="test")               # keep it recent
            c.put(f"cold{i}", b"x" * 300_000, {"site": "test"})
        assert "hot" in {e["key"] for e in c.entries()}

    def test_torn_manifest_tolerated(self, cache_env):
        c = pcc.CompileCache(cache_env)
        c.put("k1", b"p", {"site": "test"})
        with open(os.path.join(cache_env, "manifest.json"), "w") as f:
            f.write("{not json")
        assert c.get("k1", site="test")[1] == b"p"
        assert len(c.entries()) == 1                # rebuilt from scan


# ---------------------------------------------------------------------------
# to_static integration
# ---------------------------------------------------------------------------
class TestToStaticCache:
    def test_second_instance_hits_without_compiling(self, cache_env):
        x, y = pcc_targets.example_inputs()
        o1 = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        compiles = REGISTRY.get("paddle_tpu_to_static_compile_total")
        assert compiles.total() == 1
        assert REGISTRY.get("paddle_tpu_pcc_misses_total").value(
            site="to_static") == 1
        o2 = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        assert compiles.total() == 1                # no new trace/compile
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="to_static") == 1
        np.testing.assert_allclose(o1.numpy(), o2.numpy())
        assert REGISTRY.get(
            "paddle_tpu_pcc_time_saved_seconds").total() > 0

    def test_edited_body_does_not_stale_hit(self, cache_env):
        """Two versions of a function at the SAME file/line (an in-place
        edit between runs): the cache must miss on the new body, never
        serve the old executable."""
        def make(body):
            src = f"def f(x):\n    return x * {body}\n"
            ns = {}
            exec(compile(src, "fake_edit.py", "exec"),
                 {"__name__": "fake_edit_mod"}, ns)
            return ns["f"]

        x = paddle.to_tensor(np.ones((3,), np.float32))
        o1 = jit.to_static(make("2.0"), full_graph=True)(x)
        np.testing.assert_allclose(o1.numpy(), [2, 2, 2])
        o2 = jit.to_static(make("3.0"), full_graph=True)(x)
        np.testing.assert_allclose(o2.numpy(), [3, 3, 3])
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").total() == 0
        assert REGISTRY.get("paddle_tpu_pcc_misses_total").value(
            site="to_static") == 2
        # unchanged body still hits
        o3 = jit.to_static(make("2.0"), full_graph=True)(x)
        np.testing.assert_allclose(o3.numpy(), [2, 2, 2])
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="to_static") == 1

    def test_lowering_flag_changes_key(self, cache_env):
        x, y = pcc_targets.example_inputs()
        jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        try:
            paddle.set_flags({"FLAGS_tpu_matmul_precision": "highest"})
            jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
            # different lowering flags must be a different entry, not a
            # stale hit
            assert REGISTRY.get("paddle_tpu_pcc_misses_total").value(
                site="to_static") == 2
        finally:
            paddle.set_flags({"FLAGS_tpu_matmul_precision": "default"})

    @pytest.mark.parametrize("damage", ["flip", "truncate"])
    def test_corrupt_entry_recompiles_silently(self, cache_env, damage):
        x, y = pcc_targets.example_inputs()
        o1 = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        path = _entry_files(cache_env)[0]
        data = bytearray(open(path, "rb").read())
        if damage == "flip":
            data[len(data) // 2] ^= 0xFF
        else:
            data = data[:30]
        open(path, "wb").write(bytes(data))
        o2 = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        np.testing.assert_allclose(o1.numpy(), o2.numpy())
        assert REGISTRY.get(
            "paddle_tpu_pcc_quarantined_total").total() == 1
        # the recompile republished a fresh entry
        assert len(_entry_files(cache_env)) == 1

    def test_torn_publish_then_clean_run(self, cache_env):
        x, y = pcc_targets.example_inputs()
        with inject.armed("pcc.write_truncate_after_bytes",
                          after_bytes=40):
            o1 = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        assert not _entry_files(cache_env)          # publish failed clean
        o2 = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        np.testing.assert_allclose(o1.numpy(), o2.numpy())
        assert len(_entry_files(cache_env)) == 1    # second run published

    def test_disabled_flag_means_no_cache_io(self, cache_env):
        paddle.set_flags({"FLAGS_compile_cache": False})
        x, y = pcc_targets.example_inputs()
        jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        assert not os.path.exists(cache_env) or not _entry_files(cache_env)
        assert REGISTRY.get("paddle_tpu_pcc_misses_total").total() == 0


# ---------------------------------------------------------------------------
# cross-process proof (the acceptance criterion)
# ---------------------------------------------------------------------------
_CHILD = """
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import jit
import pcc_targets
x, y = pcc_targets.example_inputs()
o = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
from paddle_tpu.observability import REGISTRY
import json
print(json.dumps({
    "compiles": REGISTRY.get("paddle_tpu_to_static_compile_total").total(),
    "out": np.asarray(o._data).tolist()}))
"""


class TestCrossProcess:
    def test_second_process_zero_compiles(self, cache_env):
        env = _subproc_env()
        env.update({"FLAGS_enable_metrics": "1",
                    "FLAGS_compile_cache": "1",
                    "FLAGS_compile_cache_dir": cache_env})
        proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        child = json.loads(proc.stdout.strip().splitlines()[-1])
        assert child["compiles"] == 1               # child paid the compile
        assert len(_entry_files(cache_env)) == 1

        REGISTRY.reset()
        x, y = pcc_targets.example_inputs()
        o = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        # zero framework trace/compiles + exactly one persistent hit
        assert REGISTRY.get(
            "paddle_tpu_to_static_compile_total").total() == 0
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="to_static") == 1
        np.testing.assert_allclose(o.numpy(), np.asarray(child["out"]),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# SOT segment integration
# ---------------------------------------------------------------------------
class TestSOTSegmentCache:
    def test_fresh_instance_reuses_segments(self, cache_env):
        x = paddle.to_tensor(np.eye(4, dtype=np.float32))
        with pytest.warns(UserWarning):
            o1 = jit.to_static(pcc_targets.breaking_fn,
                               full_graph=False)(x)
        misses = REGISTRY.get("paddle_tpu_pcc_misses_total").value(
            site="sot")
        assert misses >= 2                          # both segments published
        with pytest.warns(UserWarning):
            o2 = jit.to_static(pcc_targets.breaking_fn,
                               full_graph=False)(x)
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="sot") == misses
        np.testing.assert_allclose(o1.numpy(), o2.numpy())

    def test_corrupt_segment_recompiles(self, cache_env):
        x = paddle.to_tensor(np.eye(4, dtype=np.float32))
        with pytest.warns(UserWarning):
            o1 = jit.to_static(pcc_targets.breaking_fn,
                               full_graph=False)(x)
        for path in _entry_files(cache_env):
            data = bytearray(open(path, "rb").read())
            data[len(data) // 2] ^= 0xFF
            open(path, "wb").write(bytes(data))
        with pytest.warns(UserWarning):
            o2 = jit.to_static(pcc_targets.breaking_fn,
                               full_graph=False)(x)
        np.testing.assert_allclose(o1.numpy(), o2.numpy())
        assert REGISTRY.get(
            "paddle_tpu_pcc_quarantined_total").total() >= 2


# ---------------------------------------------------------------------------
# loaded artifacts + Predictor
# ---------------------------------------------------------------------------
class TestArtifactCache:
    def _save(self, tmp_path, batch_dim=-1):
        paddle.seed(7)
        net = nn.Linear(8, 4)
        prefix = str(tmp_path / "model")
        jit.save(net, prefix,
                 input_spec=[InputSpec([batch_dim, 8], "float32")])
        return prefix

    def test_second_load_hits(self, cache_env, tmp_path):
        prefix = self._save(tmp_path)
        x = paddle.to_tensor(np.random.randn(2, 8).astype(np.float32))
        o1 = jit.load(prefix)(x)
        assert REGISTRY.get("paddle_tpu_pcc_misses_total").value(
            site="artifact") == 1
        o2 = jit.load(prefix)(x)
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="artifact") == 1
        np.testing.assert_allclose(o1.numpy(), o2.numpy())

    def test_predictor_rides_the_cache(self, cache_env, tmp_path):
        from paddle_tpu.inference import Config, create_predictor
        prefix = self._save(tmp_path)
        x = np.random.randn(2, 8).astype(np.float32)
        jit.load(prefix)(paddle.to_tensor(x))       # publish
        pred = create_predictor(Config(prefix))
        h = pred.get_input_handle("input_0")
        h.copy_from_cpu(x)
        pred.run()
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="artifact") == 1
        assert pred.get_output_handle("output_0").copy_to_cpu().shape \
            == (2, 4)

    def test_precompile_warms_unseen_shape(self, cache_env, tmp_path):
        prefix = self._save(tmp_path)               # symbolic batch dim
        jit.load(prefix).precompile([InputSpec([5, 8], "float32")])
        assert len(_entry_files(cache_env)) == 1
        o = jit.load(prefix)(
            paddle.to_tensor(np.random.randn(5, 8).astype(np.float32)))
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="artifact") == 1
        assert o.shape == [5, 4]


# ---------------------------------------------------------------------------
# warmup manifest + CLI
# ---------------------------------------------------------------------------
class TestWarmup:
    def test_record_and_warm_in_process(self, cache_env, tmp_path):
        manifest = str(tmp_path / "sigs.jsonl")
        paddle.set_flags({"FLAGS_compile_cache_manifest": manifest})
        x, y = pcc_targets.example_inputs()
        jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        paddle.set_flags({"FLAGS_compile_cache_manifest": ""})
        recs = pcc.read_manifest(manifest)
        assert recs and recs[0]["target"] == "pcc_targets:affine_fn"

        pcc.get_cache().clear()
        summary = pcc.warm(manifest)
        assert summary["warmed"] == ["pcc_targets:affine_fn"]
        assert not summary["failed"]
        assert len(_entry_files(cache_env)) == 1

        REGISTRY.reset()
        o = jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        assert REGISTRY.get(
            "paddle_tpu_to_static_compile_total").total() == 0
        assert REGISTRY.get("paddle_tpu_pcc_hits_total").value(
            site="to_static") == 1
        np.testing.assert_allclose(
            o.numpy(), x.numpy() @ y.numpy() + 1.0, rtol=1e-5)

    def test_unresolvable_record_is_skipped(self, cache_env, tmp_path):
        manifest = str(tmp_path / "sigs.jsonl")
        with open(manifest, "w") as f:
            f.write(json.dumps({"kind": "to_static", "target": None,
                                "name": "lambda",
                                "arrays": [[[2, 2], "float32"]]}) + "\n")
        summary = pcc.warm(manifest)
        assert summary["skipped"] == ["lambda"]
        assert not summary["failed"]

    def test_warm_cli(self, cache_env, tmp_path):
        manifest = str(tmp_path / "sigs.jsonl")
        paddle.set_flags({"FLAGS_compile_cache_manifest": manifest})
        x, y = pcc_targets.example_inputs()
        jit.to_static(pcc_targets.affine_fn, full_graph=True)(x, y)
        paddle.set_flags({"FLAGS_compile_cache_manifest": ""})
        pcc.get_cache().clear()

        env = _subproc_env()
        env.pop("FLAGS_compile_cache", None)
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.compile", "warm", manifest,
             "--cache-dir", cache_env],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert json.loads(proc.stdout)["warmed"] == [
            "pcc_targets:affine_fn"]
        assert len(_entry_files(cache_env)) == 1

    def test_inspect_and_prune_cli(self, cache_env):
        c = pcc.CompileCache(cache_env)
        c.put("k1", b"x" * 1000, {"site": "test", "tier": "exec"})
        env = _subproc_env()
        env["FLAGS_compile_cache_dir"] = cache_env
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.compile", "inspect"],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "1 entries" in out.stdout
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.compile", "clear"],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0
        assert not _entry_files(cache_env)
