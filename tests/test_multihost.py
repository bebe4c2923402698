"""Multi-host execution tests: 2-process rendezvous + real cross-process
collective + elastic kill/restart, and cross-process RPC.

Reference contracts: launch/controllers/master.py (HTTPMaster rendezvous),
fleet/elastic/manager.py:124 (lease-driven membership -> relaunch
decisions), distributed/rpc/rpc.py (init_rpc/rpc_sync across workers).
These run REAL subprocesses on localhost — the closest CPU analog of the
reference's multi-node TestDistBase strategy.
"""
import os
import subprocess
import sys
import time

import pytest

from paddle_tpu.distributed.launch.elastic import (ElasticManager,
                                                   parse_nnodes)
from paddle_tpu.distributed.launch.kv_server import (Heartbeat, KVClient,
                                                     KVServer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""              # no virtual 8-device mesh in workers
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _free_port_pair():
    """A port whose successor is also free: the launcher binds the KV
    master on master_port + 1."""
    import socket
    for _ in range(50):
        s1 = socket.socket()
        s1.bind(("127.0.0.1", 0))
        port = s1.getsockname()[1]
        s2 = socket.socket()
        try:
            s2.bind(("127.0.0.1", port + 1))
        except OSError:
            continue
        finally:
            s1.close()
            s2.close()
        return port
    raise RuntimeError("no consecutive free port pair found")


class TestParseNnodes:
    def test_forms(self):
        assert parse_nnodes(2) == (2, 2)
        assert parse_nnodes("2:4") == (2, 4)
        with pytest.raises(ValueError):
            parse_nnodes("0:2")


class TestElasticDecisions:
    def _mgr(self, master, nnodes="1:4"):
        return ElasticManager(master, 0, nnodes=nnodes, grace=1.0,
                              interval=0.3, job_id="dec")

    def test_decide_pure(self):
        kv = KVServer(0).start()
        try:
            m = self._mgr(f"127.0.0.1:{kv.port}")
            assert m.decide([0, 1], [0, 1]) == ("noop", [0, 1])
            assert m.decide([0, 1], [0]) == ("rescale", [0])
            assert m.decide([0], [0, 1]) == ("rescale", [0, 1])
            m2 = ElasticManager(f"127.0.0.1:{kv.port}", 0, nnodes="2:4",
                                job_id="dec2")
            assert m2.decide([0, 1], [0])[0] == "fail"
            m3 = ElasticManager(f"127.0.0.1:{kv.port}", 0, nnodes="1:2",
                                job_id="dec3")
            # scale-out capped at max_nodes
            assert m3.decide([0, 1], [0, 1, 2]) == ("noop", [0, 1])
        finally:
            kv.stop()

    def test_watch_scale_in_and_out(self):
        kv = KVServer(0).start()
        master = f"127.0.0.1:{kv.port}"
        try:
            mgr = ElasticManager(master, 0, nnodes="1:2", grace=1.2,
                                 interval=0.3, job_id="watch")
            hb1 = Heartbeat(master, 1, job_id="watch", interval=0.3,
                            ttl=1.2).start()
            mgr.start(initial_world=[0, 1])
            time.sleep(1.0)
            assert mgr.current_epoch() == 0  # both beating: no decision

            hb1.stop()                        # node 1 dies -> scale-in
            t0 = time.time()
            while mgr.current_epoch() < 1 and time.time() - t0 < 15:
                time.sleep(0.2)
            assert mgr.current_epoch() >= 1
            assert mgr.current_world() == [0]

            hb1 = Heartbeat(master, 1, job_id="watch", interval=0.3,
                            ttl=1.2).start()  # node 1 returns -> scale-out
            t0 = time.time()
            while (mgr.current_world() != [0, 1]
                   and time.time() - t0 < 15):
                time.sleep(0.2)
            assert mgr.current_world() == [0, 1]
            hb1.stop()
            mgr.stop()
        finally:
            kv.stop()


class TestCrossProcessRpc:
    WORKER = r"""
import os, sys, operator
sys.path.insert(0, {repo!r})
from paddle_tpu.distributed import rpc
rank = int(sys.argv[1]); master = sys.argv[2]
me = f"worker{{rank}}".format(rank=rank)
rpc.init_rpc(me, rank=rank, world_size=2, master_endpoint=master)
peer = "worker%d" % (1 - rank)
out = rpc.rpc_sync(peer, operator.add, args=(10 * (rank + 1), 5))
assert out == 10 * (rank + 1) + 5, out
fut = rpc.rpc_async(peer, operator.mul, args=(3, 4))
assert fut.result() == 12
print("rpc-ok", rank, flush=True)
rpc.shutdown()
"""

    def test_two_process_rpc(self, tmp_path):
        kv = KVServer(0).start()
        master = f"127.0.0.1:{kv.port}"
        script = tmp_path / "rpc_worker.py"
        script.write_text(self.WORKER.format(repo=REPO))
        env = _clean_env()
        try:
            procs = [subprocess.Popen(
                [sys.executable, str(script), str(r), master],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True) for r in range(2)]
            outs = [p.communicate(timeout=120)[0] for p in procs]
            for r, (p, out) in enumerate(zip(procs, outs)):
                assert p.returncode == 0, f"rank {r} failed:\n{out}"
                assert f"rpc-ok {r}" in out
        finally:
            kv.stop()


COLLECTIVE_WORKER = r"""
import os, sys
rank = int(os.environ["PADDLE_TRAINER_ID"])
world = int(os.environ["PADDLE_TRAINERS_NUM"])
epoch = int(os.environ.get("PADDLE_ELASTIC_EPOCH", "0"))
outdir = sys.argv[1]
import jax
jax.config.update("jax_platforms", "cpu")
try:  # CPU cross-process collectives need an explicit transport here
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    pass
jax.distributed.initialize(
    coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
    num_processes=world, process_id=rank)
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(jax.devices(), ("dp",))
x = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("dp")), jnp.ones((1, 4)) * (rank + 1),
    (world, 4))
tot = jax.jit(lambda a: jnp.sum(a),
              out_shardings=NamedSharding(mesh, P()))(x)
with open(os.path.join(outdir, f"e{epoch}.r{rank}"), "w") as f:
    f.write(str(float(tot)))
jax.distributed.shutdown()
if epoch == 0 and rank == 1:
    os._exit(13)   # simulated failure AFTER the epoch-0 collective
"""


TWO_NODE_WORKER = r"""
import os, sys
rank = int(os.environ["PADDLE_TRAINER_ID"])
world = int(os.environ["PADDLE_TRAINERS_NUM"])
epoch = int(os.environ.get("PADDLE_ELASTIC_EPOCH", "0"))
outdir = sys.argv[1]
import jax
jax.config.update("jax_platforms", "cpu")
try:  # CPU cross-process collectives need an explicit transport here
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    pass
jax.distributed.initialize(
    coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
    num_processes=world, process_id=rank)
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(jax.devices(), ("dp",))
x = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("dp")), jnp.ones((1, 4)) * (rank + 1),
    (world, 4))
tot = jax.jit(lambda a: jnp.sum(a),
              out_shardings=NamedSharding(mesh, P()))(x)
with open(os.path.join(outdir, f"e{epoch}.r{rank}"), "w") as f:
    f.write(str(float(tot)))
jax.distributed.shutdown()
if epoch == 0 and rank == 1:
    os._exit(13)   # node 1 fails after the epoch-0 collective
"""


class TestTwoNodeElastic:
    def test_two_launchers_epoch_restart(self, tmp_path):
        """Full multi-NODE elastic flow: two launcher processes (one per
        'host') rendezvous through the KV master, their workers form a
        jax.distributed world; node 1's worker dies, node 1's launcher
        publishes a job-wide epoch, BOTH launchers relaunch in step, and
        the finished node waits on job-wide done markers instead of
        abandoning the job."""
        script = tmp_path / "worker.py"
        script.write_text(TWO_NODE_WORKER)
        outdir = tmp_path / "out"
        outdir.mkdir()
        port = _free_port_pair()
        env = _clean_env()

        def launcher(node_rank):
            return subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--nnodes", "2", "--node_rank", str(node_rank),
                 "--nproc_per_node", "1", "--max_restarts", "1",
                 "--master", f"127.0.0.1:{port}",
                 str(script), str(outdir)],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)

        procs = [launcher(0), launcher(1)]
        logs = [p.communicate(timeout=420)[0] for p in procs]
        for r, (p, log) in enumerate(zip(procs, logs)):
            assert p.returncode == 0, f"node {r} launcher:\n{log}"
        assert any("elastic epoch" in l or "published job-wide" in l
                   for l in logs), logs
        for fname in ("e0.r0", "e0.r1", "e1.r0", "e1.r1"):
            f = outdir / fname
            assert f.exists(), f"{fname} missing; logs:\n" + "\n".join(logs)
            assert float(f.read_text()) == 12.0


class TestLaunchElasticCollective:
    def test_rendezvous_collective_kill_restart(self, tmp_path):
        """The round-3 'Done' criterion: 2 processes rendezvous, run a
        REAL cross-process XLA collective (Gloo CPU), one worker dies,
        the launcher group-restarts at the next elastic epoch, and the
        new world completes another collective."""
        script = tmp_path / "collective_worker.py"
        script.write_text(COLLECTIVE_WORKER)
        outdir = tmp_path / "out"
        outdir.mkdir()
        port = _free_port_pair()
        env = _clean_env()
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--max_restarts", "1",
             "--master", f"127.0.0.1:{port}",
             str(script), str(outdir)],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=420)
        log = proc.stdout + proc.stderr
        assert proc.returncode == 0, log
        assert "group restart" in log
        for fname in ("e0.r0", "e0.r1", "e1.r0", "e1.r1"):
            f = outdir / fname
            assert f.exists(), f"{fname} missing; log:\n{log}"
            # sum over global [2,4] of ones*(rank+1) = 4*1 + 4*2 = 12
            assert float(f.read_text()) == 12.0
