"""Parameter-server mode: tables, accessors, client routing, the
PS-backed embedding, and the fleet PS lifecycle.

Reference contracts: paddle/fluid/distributed/ps/table/
(memory_sparse_table, memory_dense_table, accessors),
service/brpc_ps_{server,client}.cc (pull/push/save/load/barrier), and
python/paddle/distributed/ps/the_one_ps.py + fleet role lifecycle
(role_maker.py:849-1003).
"""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import ps
from paddle_tpu.distributed.ps import (DistributedEmbedding, PsClient,
                                       PsServer, SparseTable)


# ----------------------------------------------------------- fixtures
@pytest.fixture()
def cluster():
    """Two in-process PS shards + a client (2-server sharding)."""
    servers = [PsServer(i, 2, token="t0").start() for i in range(2)]
    client = PsClient([s.endpoint for s in servers], token="t0")
    yield servers, client
    client.close()
    for s in servers:
        s.stop()


# ------------------------------------------------------------- tables
def test_sparse_table_lazy_rows_and_sgd():
    t = SparseTable(dim=4, accessor="sgd", lr=0.5, initializer="constant",
                    init_range=1.0)
    v = t.pull([7, 3, 7])
    assert v.shape == (3, 4)
    np.testing.assert_allclose(v, 1.0)
    assert t.size == 2  # lazy creation, deduped storage
    t.push([7], np.full((1, 4), 2.0, np.float32))
    np.testing.assert_allclose(t.pull([7]), 1.0 - 0.5 * 2.0)
    np.testing.assert_allclose(t.pull([3]), 1.0)  # untouched row


def test_adam_accessor_matches_local_adam():
    """Server-side adam == a local reference adam loop on the same rows."""
    t = SparseTable(dim=3, accessor="adam", lr=0.1, initializer="constant",
                    init_range=0.0)
    rng = np.random.RandomState(0)
    w = t.pull([5])[0].copy()
    m = np.zeros(3); v = np.zeros(3)
    for step in range(1, 6):
        g = rng.randn(3).astype(np.float32)
        t.push([5], g[None])
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mhat = m / (1 - 0.9 ** step)
        vhat = v / (1 - 0.999 ** step)
        w = w - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(t.pull([5])[0], w, rtol=1e-5, atol=1e-6)


def test_adagrad_and_sum_accessors():
    t = SparseTable(dim=2, accessor="adagrad", lr=1.0,
                    initializer="constant", init_range=0.0)
    g = np.array([[3.0, 4.0]], np.float32)
    t.push([1], g)
    np.testing.assert_allclose(
        t.pull([1]), -1.0 * g / (np.sqrt(g * g) + 1e-6), rtol=1e-5)
    s = SparseTable(dim=2, accessor="sum", initializer="constant",
                    init_range=0.0)
    s.push([1], g)
    s.push([1], g)
    np.testing.assert_allclose(s.pull([1]), 2 * g)


def test_sparse_state_dict_roundtrip():
    t = SparseTable(dim=3, accessor="adam", lr=0.1)
    t.push(np.arange(10), np.ones((10, 3), np.float32))
    sd = t.state_dict()
    t2 = SparseTable(dim=3, accessor="adam", lr=0.1)
    t2.load_state_dict(sd)
    np.testing.assert_allclose(t2.pull(np.arange(10)), t.pull(np.arange(10)))
    # optimizer state carried: the next identical push matches too
    t.push([4], np.ones((1, 3), np.float32))
    t2.push([4], np.ones((1, 3), np.float32))
    np.testing.assert_allclose(t2.pull([4]), t.pull([4]), rtol=1e-6)


# ----------------------------------------------------- client/server
def test_client_routing_and_dedup(cluster):
    servers, client = cluster
    client.create_table(0, {"type": "sparse", "dim": 4, "accessor": "sgd",
                            "lr": 1.0, "initializer": "constant",
                            "init_range": 0.0})
    ids = np.array([2, 3, 2, 5, 3, 2], np.int64)
    vals = client.pull_sparse(0, ids)
    assert vals.shape == (6, 4)
    # rows landed on both shards (id%2 routing)
    sizes = [s._tables[0].size for s in servers]
    assert sizes == [1, 2]  # {2} on shard0, {3,5} on shard1
    # duplicate-id push merges client-side: id 2 appears 3x with grad 1
    # → one sgd step of summed grad 3
    client.push_sparse(0, ids, np.ones((6, 4), np.float32))
    np.testing.assert_allclose(client.pull_sparse(0, [2])[0], -3.0)
    np.testing.assert_allclose(client.pull_sparse(0, [5])[0], -1.0)


def test_client_auth_rejected(cluster):
    servers, _ = cluster
    bad = PsClient([servers[0].endpoint], token="WRONG")
    with pytest.raises(Exception):
        bad.pull_sparse(0, [1])
    bad.close()


def test_dense_table_chunking(cluster):
    servers, client = cluster
    client.create_table(1, {"type": "dense", "length": 7, "accessor": "sgd",
                            "lr": 0.5, "init_value": 0.0})
    v = np.arange(7, dtype=np.float32)
    client.set_dense(1, v)
    np.testing.assert_allclose(client.pull_dense(1), v)
    # chunked across servers: 4 + 3
    assert servers[0]._tables[1].length == 4
    assert servers[1]._tables[1].length == 3
    client.push_dense(1, np.ones(7, np.float32))
    np.testing.assert_allclose(client.pull_dense(1), v - 0.5)


def test_save_load_roundtrip(cluster, tmp_path):
    servers, client = cluster
    client.create_table(0, {"type": "sparse", "dim": 2, "accessor": "sgd",
                            "lr": 1.0})
    ids = np.arange(20)
    before = client.pull_sparse(0, ids)
    client.save(str(tmp_path))
    client.push_sparse(0, ids, np.ones((20, 2), np.float32))  # perturb
    client.load(str(tmp_path))
    np.testing.assert_allclose(client.pull_sparse(0, ids), before)


def test_table_create_conflict_and_missing(cluster):
    _, client = cluster
    client.create_table(3, {"type": "sparse", "dim": 2})
    client.create_table(3, {"type": "sparse", "dim": 2})  # idempotent
    with pytest.raises(ValueError):
        client.create_table(3, {"type": "sparse", "dim": 8})
    with pytest.raises(KeyError):
        client.pull_sparse(99, [1])


def test_worker_barrier(cluster):
    _, client = cluster
    c2 = PsClient(client.endpoints, token="t0")
    results = []

    def w(c):
        c.barrier("sync", 2)
        results.append(1)

    th = threading.Thread(target=w, args=(c2,))
    th.start()
    client.barrier("sync", 2)
    th.join(timeout=10)
    assert len(results) == 1
    # reusable: second generation also completes
    th2 = threading.Thread(target=w, args=(c2,))
    th2.start()
    client.barrier("sync", 2)
    th2.join(timeout=10)
    assert len(results) == 2
    c2.close()


# ------------------------------------------------- PS-backed embedding
def test_distributed_embedding_trains(cluster):
    _, client = cluster
    emb = DistributedEmbedding(0, 8, client=client, accessor="sgd", lr=0.3,
                               init_range=0.05)
    lin = paddle.nn.Linear(8, 2)
    opt = paddle.optimizer.SGD(parameters=lin.parameters(),
                               learning_rate=0.3)
    ids = paddle.to_tensor(np.array([[1, 2, 3], [4, 2, 9]], np.int64))
    labels = paddle.to_tensor(np.array([0, 1], np.int64))
    losses = []
    for _ in range(20):
        h = emb(ids).mean(axis=1)
        loss = paddle.nn.functional.cross_entropy(lin(h), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < 0.5 * losses[0], losses


def test_distributed_embedding_matches_local(cluster):
    """PS-backed training == local-embedding training, step for step.

    Same init rows, same duplicate-heavy batch, plain SGD on both sides;
    the PS path (pull → device gather → push → server-side sgd) must
    reproduce the local embedding's weights exactly.
    """
    _, client = cluster
    dim, lr = 4, 0.2
    emb = DistributedEmbedding(7, dim, client=client, accessor="sgd",
                               lr=lr, initializer="constant",
                               init_range=0.1)
    ids_np = np.array([[0, 1, 1], [2, 1, 0]], np.int64)
    ids = paddle.to_tensor(ids_np)

    # local reference: same constant init
    W = np.full((3, dim), 0.1, np.float32)
    for step in range(3):
        out = emb(ids)                      # [2, 3, dim]
        loss = (out * out).sum()
        loss.backward()
        # local numpy replica
        g_out = 2 * W[ids_np]               # dL/d(out)
        gW = np.zeros_like(W)
        np.add.at(gW, ids_np.reshape(-1), g_out.reshape(-1, dim))
        W -= lr * gW
        np.testing.assert_allclose(
            client.pull_sparse(7, [0, 1, 2]), W, rtol=1e-5, atol=1e-6)


def test_embedding_not_trainable_pulls_only(cluster):
    _, client = cluster
    emb = DistributedEmbedding(8, 4, client=client, accessor="sgd", lr=1.0,
                               trainable=False)
    ids = paddle.to_tensor(np.array([1, 2], np.int64))
    before = client.pull_sparse(8, [1, 2])
    out = emb(ids)
    s = out.sum()
    # no tape reaches the PS: rows are stop_gradient, output too
    assert out.stop_gradient
    np.testing.assert_allclose(client.pull_sparse(8, [1, 2]), before)


# ------------------------------------------------------ fleet PS mode
def _free_ports(n):
    import socket
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def test_fleet_ps_lifecycle(monkeypatch):
    """Server + worker roles through the fleet facade (single process:
    the server runs on a thread, the worker on the main thread)."""
    from paddle_tpu.distributed.fleet.fleet_base import Fleet
    from paddle_tpu.distributed.ps import (PaddleCloudRoleMaker, Role,
                                           UserDefinedRoleMaker)

    (port,) = _free_ports(1)
    eps = f"127.0.0.1:{port}"
    monkeypatch.setenv("PADDLE_PS_TOKEN", "fleet-tok")

    # ---- server role (background thread, its own Fleet instance,
    # programmatic roles — no env needed)
    server_ready = threading.Event()
    server_done = threading.Event()

    def run_server():
        f = Fleet()
        f.init(UserDefinedRoleMaker(current_id=0, role=Role.SERVER,
                                    worker_num=1, server_endpoints=[eps]))
        assert f.is_server() and not f.is_worker()
        assert f.server_index() == 0 and f.server_num() == 1
        f.init_server()
        server_ready.set()
        f.run_server()  # blocks until stop_worker
        server_done.set()

    th = threading.Thread(target=run_server, daemon=True)
    th.start()
    assert server_ready.wait(timeout=30)

    # ---- worker role (env-driven role maker, reference contract)
    for k, v in {"PADDLE_PSERVERS_IP_PORT_LIST": eps,
                 "PADDLE_TRAINERS_NUM": "1", "TRAINING_ROLE": "TRAINER",
                 "PADDLE_TRAINER_ID": "0"}.items():
        monkeypatch.setenv(k, v)
    f = Fleet()
    f.init(PaddleCloudRoleMaker())
    assert f.is_worker() and not f.is_server()
    assert f.worker_num() == 1 and f.is_first_worker()
    client = f.init_worker()
    emb = DistributedEmbedding(0, 4, accessor="sgd", lr=0.5)  # via fleet ctx
    ids = paddle.to_tensor(np.array([3, 4], np.int64))
    out = emb(ids)
    loss = out.sum()
    loss.backward()
    f.barrier_worker()
    after = client.pull_sparse(0, [3, 4])
    np.testing.assert_allclose(after, out.numpy() - 0.5 * 1.0, atol=1e-6)
    f.stop_worker()
    assert server_done.wait(timeout=30)
    th.join(timeout=10)


# ----------------------------------------------------------- geo-SGD
def test_geo_sparse_table_dirty_tracking():
    from paddle_tpu.distributed.ps import GeoSparseTable
    t = GeoSparseTable(dim=2, trainer_num=3, initializer="constant",
                       init_range=0.0)
    t.pull([1, 2])  # materialize
    t.push_delta(0, [1], np.array([[1.0, 1.0]], np.float32))
    # trainer 0's own push doesn't dirty trainer 0
    ids0, _ = t.pull_geo(0)
    assert ids0.size == 0
    ids1, vals1 = t.pull_geo(1)
    assert ids1.tolist() == [1]
    np.testing.assert_allclose(vals1, [[1.0, 1.0]])
    # drained: second pull is empty
    ids1b, _ = t.pull_geo(1)
    assert ids1b.size == 0
    # trainer 2 still has it pending
    ids2, _ = t.pull_geo(2)
    assert ids2.tolist() == [1]


def test_geo_embedding_two_trainers_converge(cluster):
    """Two geo trainers sharing the PS: after both sync, both local
    replicas equal the server value = init + delta0 + delta1."""
    from paddle_tpu.distributed.ps import GeoDistributedEmbedding
    _, client = cluster
    dim = 4
    t0 = GeoDistributedEmbedding(11, dim, trainer_id=0, trainer_num=2,
                                 client=client, lr=0.5, sync_steps=1,
                                 initializer="constant", init_range=0.2)
    t1 = GeoDistributedEmbedding(11, dim, trainer_id=1, trainer_num=2,
                                 client=client, lr=0.5, sync_steps=10**9,
                                 initializer="constant", init_range=0.2)
    ids = paddle.to_tensor(np.array([3, 8], np.int64))  # both shards

    # each trainer runs one local step: loss = sum(out) → grad 1 per elt
    for tr in (t0, t1):
        out = tr(ids)
        out.sum().backward()
    # t0 synced automatically (sync_steps=1); t1 syncs manually
    t1.sync()
    # server merged both deltas: 0.2 - 0.5 - 0.5 = -0.8
    server_vals = client.pull_sparse(11, [3, 8])
    np.testing.assert_allclose(server_vals, -0.8, atol=1e-6)
    # t1 pushed then pulled: its replica is the merged value
    np.testing.assert_allclose(np.stack([t1._local[3], t1._local[8]]),
                               -0.8, atol=1e-6)
    # t0 synced BEFORE t1 pushed → still has only its own step; the next
    # sync absorbs t1's delta
    np.testing.assert_allclose(t0._local[3], -0.3, atol=1e-6)
    t0.sync()
    np.testing.assert_allclose(t0._local[3], -0.8, atol=1e-6)


def test_geo_embedding_trains_locally(cluster):
    """Single geo trainer: local SGD converges and, after sync, the
    server mirrors the local replica exactly."""
    from paddle_tpu.distributed.ps import GeoDistributedEmbedding
    _, client = cluster
    emb = GeoDistributedEmbedding(12, 8, trainer_id=0, trainer_num=1,
                                  client=client, lr=0.3, sync_steps=3)
    lin = paddle.nn.Linear(8, 2)
    opt = paddle.optimizer.SGD(parameters=lin.parameters(),
                               learning_rate=0.3)
    ids = paddle.to_tensor(np.array([[1, 5, 9], [2, 5, 7]], np.int64))
    labels = paddle.to_tensor(np.array([0, 1], np.int64))
    losses = []
    for _ in range(18):
        h = emb(ids).mean(axis=1)
        loss = paddle.nn.functional.cross_entropy(lin(h), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < 0.6 * losses[0], losses
    emb.sync()
    all_ids = sorted(emb._local)
    server_vals = client.pull_sparse(12, all_ids)
    local_vals = np.stack([emb._local[i] for i in all_ids])
    np.testing.assert_allclose(server_vals, local_vals, atol=1e-5)


def test_static_nn_sparse_embedding(cluster):
    """static.nn.sparse_embedding routes through the PS tier (reference
    static/nn/common.py:3691), including the geo table_class."""
    from paddle_tpu.distributed import ps as ps_mod
    _, client = cluster
    ps_mod._CTX["client"] = client  # bind as the PS-mode client
    try:
        import paddle_tpu.static as static
        ids = paddle.to_tensor(np.array([[1, 2], [3, 1]], np.int64))
        out = static.nn.sparse_embedding(
            ids, [100, 6], param_attr="emb_a")
        assert list(out.shape) == [2, 2, 6]
        out.sum().backward()  # pushes grads to the PS (sgd accessor)
        out2 = static.nn.sparse_embedding(
            ids, [100, 6], param_attr="emb_a")
        # same param name -> same table: values moved by the sgd step
        assert not np.allclose(out.numpy(), out2.numpy())
        # geo path shares one stateful replica across calls
        g1 = static.nn.sparse_embedding(
            ids, [100, 6], param_attr="emb_geo",
            table_class="MemorySparseGeoTable")
        g1.sum().backward()
        g2 = static.nn.sparse_embedding(
            ids, [100, 6], param_attr="emb_geo",
            table_class="MemorySparseGeoTable")
        assert not np.allclose(g1.numpy(), g2.numpy())
        # is_test freezes the lookup: output carries no grad graph and
        # repeated eval lookups see identical values
        frozen = static.nn.sparse_embedding(
            ids, [100, 6], param_attr="emb_a", is_test=True)
        assert frozen.stop_gradient
        after = static.nn.sparse_embedding(
            ids, [100, 6], param_attr="emb_a", is_test=True)
        np.testing.assert_allclose(frozen.numpy(), after.numpy())
    finally:
        ps_mod._CTX["client"] = None
        from paddle_tpu.static.nn import _GEO_LAYERS
        _GEO_LAYERS.clear()


PS_SERVER_PROC = r"""
import sys
sys.path.insert(0, {repo!r})
from paddle_tpu.distributed.fleet.fleet_base import Fleet
from paddle_tpu.distributed.ps import PaddleCloudRoleMaker
f = Fleet()
f.init(PaddleCloudRoleMaker())
assert f.is_server()
f.init_server()
print("server-ready", f.server_index(), flush=True)
f.run_server()
print("server-done", f.server_index(), flush=True)
"""

PS_WORKER_PROC = r"""
import sys
import numpy as np
sys.path.insert(0, {repo!r})
from paddle_tpu.distributed.fleet.fleet_base import Fleet
from paddle_tpu.distributed.ps import PaddleCloudRoleMaker
f = Fleet()
f.init(PaddleCloudRoleMaker())
assert f.is_worker()
rank = f.worker_index()
client = f.init_worker()
client.create_table(0, {{"type": "sparse", "dim": 2, "accessor": "sgd",
                         "lr": 0.5, "initializer": "constant",
                         "init_range": 0.1}})
# ids 7 and 8 land on different shards (id % 2)
if rank == 0:
    client.push_sparse(0, [7], np.ones((1, 2), np.float32))
f.barrier_worker()
if rank == 1:
    got = client.pull_sparse(0, [7])[0]
    np.testing.assert_allclose(got, 0.1 - 0.5, atol=1e-6)
    client.push_sparse(0, [8], 2 * np.ones((1, 2), np.float32))
f.barrier_worker()
if rank == 0:
    got = client.pull_sparse(0, [8])[0]
    np.testing.assert_allclose(got, 0.1 - 1.0, atol=1e-6)
f.barrier_worker()
print("worker-ok", rank, flush=True)
f.stop_worker()
"""


def test_ps_cross_process(tmp_path):
    """2 server + 2 worker PROCESSES over the reference env contract:
    cross-process row visibility on both shards, reusable barriers,
    worker-0-driven shutdown."""
    import subprocess
    import sys as _sys

    repo = __file__.rsplit("/tests/", 1)[0]
    ports = _free_ports(2)
    eps = ",".join(f"127.0.0.1:{p}" for p in ports)
    sscript = tmp_path / "ps_server.py"
    sscript.write_text(PS_SERVER_PROC.format(repo=repo))
    wscript = tmp_path / "ps_worker.py"
    wscript.write_text(PS_WORKER_PROC.format(repo=repo))

    import os as _os
    base = dict(_os.environ)
    base.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                 "PADDLE_PSERVERS_IP_PORT_LIST": eps,
                 "PADDLE_TRAINERS_NUM": "2",
                 "PADDLE_PS_TOKEN": "xproc-tok"})
    procs = []
    try:
        for i, p in enumerate(ports):
            env = {**base, "TRAINING_ROLE": "PSERVER",
                   "POD_IP": "127.0.0.1", "PADDLE_PORT": str(p)}
            procs.append(subprocess.Popen(
                [_sys.executable, str(sscript)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        for r in range(2):
            env = {**base, "TRAINING_ROLE": "TRAINER",
                   "PADDLE_TRAINER_ID": str(r)}
            procs.append(subprocess.Popen(
                [_sys.executable, str(wscript)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = [p.communicate(timeout=180)[0] for p in procs]
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert "server-done 0" in outs[0]
        assert "server-done 1" in outs[1]
        assert "worker-ok 0" in outs[2]
        assert "worker-ok 1" in outs[3]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_fleet_init_non_collective_env_and_stop_worker_noop(monkeypatch):
    """init(is_collective=False) with no role maker resolves roles from
    the env (reference contract); stop_worker outside PS mode is a
    no-op, not a crash."""
    from paddle_tpu.distributed.fleet.fleet_base import Fleet
    f = Fleet()
    f.init()  # collective
    f.stop_worker()  # must not raise
    f.stop_worker()  # idempotent

    (port,) = _free_ports(1)
    for k, v in {"PADDLE_PSERVERS_IP_PORT_LIST": f"127.0.0.1:{port}",
                 "PADDLE_TRAINERS_NUM": "1", "TRAINING_ROLE": "TRAINER",
                 "PADDLE_TRAINER_ID": "0"}.items():
        monkeypatch.setenv(k, v)
    # the PS transport refuses to run tokenless (pickle on the wire)
    monkeypatch.delenv("PADDLE_PS_TOKEN", raising=False)
    with pytest.raises(RuntimeError, match="PADDLE_PS_TOKEN"):
        Fleet().init(is_collective=False)
    monkeypatch.setenv("PADDLE_PS_TOKEN", "env-tok")
    f2 = Fleet()
    f2.init(is_collective=False)
    assert f2.is_worker() and not f2.is_server()
    assert f2.worker_num() == 1
    from paddle_tpu.distributed import ps as ps_mod
    ps_mod._reset()  # no server started; just unbind the client


def test_role_maker_env_validation(monkeypatch):
    from paddle_tpu.distributed.ps import PaddleCloudRoleMaker
    monkeypatch.delenv("PADDLE_PSERVERS_IP_PORT_LIST", raising=False)
    with pytest.raises(ValueError, match="PADDLE_PSERVERS_IP_PORT_LIST"):
        PaddleCloudRoleMaker()
    monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST", "127.0.0.1:1")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    monkeypatch.setenv("TRAINING_ROLE", "BOGUS")
    with pytest.raises(ValueError, match="TRAINING_ROLE"):
        PaddleCloudRoleMaker()
    monkeypatch.setenv("TRAINING_ROLE", "TRAINER")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    rm = PaddleCloudRoleMaker()
    assert rm._is_worker() and rm._worker_index() == 1
    assert rm._worker_num() == 2 and not rm._is_first_worker()
