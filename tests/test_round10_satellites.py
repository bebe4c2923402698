"""Round-10 satellites: jit.save version stamping + ArtifactVersionError,
and DataLoader multiprocess-worker lifecycle guarantees."""
import gc
import os
import pickle
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, nn
from paddle_tpu.io import DataLoader, Dataset
from paddle_tpu.static import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# jit.save version stamp / ArtifactVersionError
# ---------------------------------------------------------------------------
class TestArtifactVersionStamp:
    def _save(self, tmp_path):
        net = nn.Linear(4, 2)
        prefix = str(tmp_path / "m")
        jit.save(net, prefix, input_spec=[InputSpec([3, 4], "float32")])
        return prefix

    def test_blob_carries_toolchain_stamp(self, tmp_path):
        import jax
        import jaxlib
        prefix = self._save(tmp_path)
        with open(prefix + ".pdmodel", "rb") as f:
            blob = pickle.load(f)
        assert blob["format"] == "paddle_tpu.jit/2"
        assert blob["jax_version"] == jax.__version__
        assert blob["jaxlib_version"] == jaxlib.__version__
        assert blob["platform"]

    def test_roundtrip_still_loads(self, tmp_path):
        prefix = self._save(tmp_path)
        out = jit.load(prefix)(
            paddle.to_tensor(np.ones((3, 4), np.float32)))
        assert out.shape == [3, 2]

    def test_version_skew_raises_clear_error(self, tmp_path):
        prefix = self._save(tmp_path)
        with open(prefix + ".pdmodel", "rb") as f:
            blob = pickle.load(f)
        # stamped by an older toolchain AND undecodable program bytes:
        # the load must name both versions, not dump a deserialize trace
        blob["jax_version"] = "0.3.99"
        blob["jaxlib_version"] = "0.3.99"
        blob["stablehlo"] = b"\x00garbage"
        with open(prefix + ".pdmodel", "wb") as f:
            pickle.dump(blob, f)
        with pytest.raises(jit.ArtifactVersionError) as ei:
            jit.load(prefix)
        msg = str(ei.value)
        assert "0.3.99" in msg and "jit.save" in msg

    def test_same_version_corruption_not_masked(self, tmp_path):
        prefix = self._save(tmp_path)
        with open(prefix + ".pdmodel", "rb") as f:
            blob = pickle.load(f)
        blob["stablehlo"] = b"\x00garbage"          # versions match
        with open(prefix + ".pdmodel", "wb") as f:
            pickle.dump(blob, f)
        with pytest.raises(Exception) as ei:
            jit.load(prefix)
        assert not isinstance(ei.value, jit.ArtifactVersionError)

    def test_foreign_blob_rejected(self, tmp_path):
        prefix = self._save(tmp_path)
        with open(prefix + ".pdmodel", "wb") as f:
            pickle.dump({"format": "something_else/7"}, f)
        with pytest.raises(jit.ArtifactVersionError):
            jit.load(prefix)


# ---------------------------------------------------------------------------
# DataLoader worker lifecycle
# ---------------------------------------------------------------------------
class _Range(Dataset):
    def __init__(self, n=64):
        self.n = n

    def __getitem__(self, i):
        return np.full((4,), i, np.float32)

    def __len__(self):
        return self.n


def _alive(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    # reaped-but-zombie also counts as gone once waited on; poll /proc
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


class TestDataLoaderWorkerCleanup:
    def test_workers_join_after_full_iteration(self):
        loader = DataLoader(_Range(16), batch_size=4, num_workers=2)
        it = iter(loader)
        pids = [w.pid for w in it._workers]
        batches = list(it)
        assert len(batches) == 4
        assert _wait_dead(pids), "workers outlived a completed epoch"

    def test_workers_terminated_after_consumer_exception(self):
        loader = DataLoader(_Range(64), batch_size=4, num_workers=2)
        pids = []

        def consume():
            it = iter(loader)
            pids.extend(w.pid for w in it._workers)
            for i, _batch in enumerate(it):
                if i == 2:
                    raise ValueError("consumer blew up mid-epoch")

        with pytest.raises(ValueError):
            consume()
        # the iterator died with the consumer frame; GC must reap workers
        gc.collect()
        assert _wait_dead(pids), (
            "orphaned DataLoader workers after a consumer-loop exception")

    def test_workers_terminated_on_explicit_del(self):
        loader = DataLoader(_Range(64), batch_size=4, num_workers=2)
        it = iter(loader)
        pids = [w.pid for w in it._workers]
        next(it)
        del it
        gc.collect()
        assert _wait_dead(pids), "workers survived iterator deletion"

    def test_workers_reaped_at_interpreter_exit(self, tmp_path):
        """A child interpreter that abandons a mid-epoch iterator (the
        finalize/atexit path) must leave no orphan workers behind."""
        script = r"""
import os, sys
import numpy as np
from paddle_tpu.io import DataLoader, Dataset

class DS(Dataset):
    def __getitem__(self, i):
        return np.full((4,), i, np.float32)
    def __len__(self):
        return 64

loader = DataLoader(DS(), batch_size=4, num_workers=2)
it = iter(loader)
next(it)
print("PIDS", " ".join(str(w.pid) for w in it._workers))
sys.stdout.flush()
# exit with the iterator still alive and batches in flight
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=180)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        pids = [int(p) for line in proc.stdout.splitlines()
                if line.startswith("PIDS")
                for p in line.split()[1:]]
        assert pids
        assert _wait_dead(pids), (
            f"workers {pids} orphaned after interpreter exit")
