"""Distributed job launcher.

Capability parity with the reference launcher (reference:
python/paddle/distributed/launch/main.py:21 — `python -m
paddle.distributed.launch --nnodes ... train.py`, builds per-rank envs,
spawns/monitors workers, restarts under elastic policy
fleet/elastic/manager.py:124). TPU-native: ONE process drives all local
chips of a host (single controller), so --nproc_per_node defaults to 1.
Workers get no chip of their own from this launcher — a chip belongs to
one process at a time, and every worker that reaches the TPU claims every
local chip — so ``--nproc_per_node > 1`` is refused on a TPU host unless
the workers are pinned off it (``JAX_PLATFORMS=cpu``, as the CPU test
drills do). The launcher itself never imports jax: a parent that has
touched JAX holds the chip its workers need. The env contract sets both
the reference names (PADDLE_TRAINER_ID …) and the jax.distributed
coordinates the framework's parallel.init reads.
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time
from typing import List


def _parse(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a multi-process / multi-host job")
    p.add_argument("--nnodes", default="1",
                   help="node count, or elastic range 'min:max'")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (TPU single-controller: 1)")
    p.add_argument("--master", default=os.environ.get(
        "PADDLE_MASTER", "127.0.0.1:8765"),
        help="coordinator host:port (jax.distributed)")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic restarts per worker on failure")
    p.add_argument("--abort_grace", type=float, default=10.0,
                   help="after one worker dies restart-worthy, wait up "
                        "to this many seconds for the surviving workers "
                        "to abort coordinated (collective timeout / "
                        "lease expiry) before reaping them")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _workers_would_claim_tpu() -> bool:
    """True when worker processes started with this environment would
    initialize the TPU backend: the host exposes TPU device nodes and
    ``JAX_PLATFORMS`` does not pin the workers to another platform.
    Decided without importing jax (see the module docstring)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _worker_env(args, local_rank: int, epoch: int = 0,
                nnodes: int = None, node_rank: int = None) -> dict:
    nnodes = nnodes if nnodes is not None else args.nnodes_now
    node_rank = node_rank if node_rank is not None else args.node_rank
    world = nnodes * args.nproc_per_node
    rank = node_rank * args.nproc_per_node + local_rank
    host, _, port = args.master.rpartition(":")
    # every elastic epoch is a FRESH jax.distributed world: PJRT cannot
    # re-initialize in-process, so the epoch moves the coordinator port
    coord = f"{host}:{int(port) + 2 * epoch}" if port.isdigit() \
        else args.master
    env = dict(os.environ)
    env.update({
        # reference names (compat for user scripts)
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_MASTER": args.master,
        "PADDLE_ELASTIC_EPOCH": str(epoch),
        # jax.distributed coordinates (paddle_tpu.distributed.init reads)
        "JAX_COORDINATOR_ADDRESS": coord,
        "JAX_NUM_PROCESSES": str(world),
        "JAX_PROCESS_ID": str(rank),
    })
    # PS/RPC transports refuse to run without a shared job token (their
    # bodies are pickled). Single-node: mint one for the whole local group.
    # Multi-node: it must come in via the environment (same value on every
    # node) — the launcher only fills the gap it can fill safely.
    if args.ps_token:
        env.setdefault("PADDLE_PS_TOKEN", args.ps_token)
    return env


class _Worker:
    def __init__(self, args, local_rank: int):
        self.args = args
        self.local_rank = local_rank
        self.restarts = 0
        self.proc: subprocess.Popen | None = None
        self.log = None

    def start(self, epoch: int = 0, nnodes: int = None,
              node_rank: int = None):
        args = self.args
        cmd = [sys.executable, args.training_script,
               *args.training_script_args]
        stdout = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            self.log = open(os.path.join(
                args.log_dir, f"worker.{self.local_rank}.log"), "ab")
            stdout = self.log
        self.proc = subprocess.Popen(
            cmd, env=_worker_env(args, self.local_rank, epoch=epoch,
                                 nnodes=nnodes, node_rank=node_rank),
            stdout=stdout, stderr=subprocess.STDOUT if stdout else None)

    def wait_dead(self, timeout: float = 10.0):
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.terminate()
        try:
            self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()

    def poll(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()

    def close(self):
        if self.log:
            self.log.close()


def launch(argv=None) -> int:
    """Spawn + monitor the workers (reference elastic/manager.py watchdog
    loop). A worker failure with restarts remaining relaunches the WHOLE
    local group at the next elastic epoch — each epoch is a fresh
    jax.distributed world (coordinator port moves with the epoch), since
    a collective world cannot survive a member death in place.

    Multi-node: node 0 runs the HTTP KV master (kv_server.py, reference
    HTTPMaster) on master_port+1; all nodes barrier through sync_peers,
    then an ElasticManager (launch/elastic.py) heartbeats membership —
    scale-in/out publishes a new epoch + world, and every node's launcher
    relaunches its group with re-ranked coordinates."""
    from .elastic import parse_nnodes

    args = _parse(argv)
    if args.nproc_per_node > 1 and _workers_would_claim_tpu():
        raise SystemExit(
            f"--nproc_per_node {args.nproc_per_node} on a TPU host: every "
            f"worker would claim every local chip, and a chip belongs to "
            f"one process at a time. One process drives all local chips "
            f"(--nproc_per_node 1, shard over jax.devices() with a mesh); "
            f"for a CPU-only multi-process run set JAX_PLATFORMS=cpu.")
    nnodes_min, nnodes_max = parse_nnodes(args.nnodes)
    args.ps_token = os.environ.get("PADDLE_PS_TOKEN", "")
    if not args.ps_token and nnodes_max == 1:
        # single-node: mint one shared token for the local group. A
        # per-launcher mint would NOT match across nodes, so multi-node
        # jobs must bring the token via the environment.
        import secrets
        args.ps_token = secrets.token_hex(16)
    args.nnodes_now = nnodes_min
    kv = None
    manager = None
    kv_addr = None
    node_rank_now = args.node_rank
    if nnodes_min > 1 or nnodes_max > 1:
        from .elastic import ElasticManager
        from .kv_server import KVServer, sync_peers
        host, _, port = args.master.rpartition(":")
        if not host or not port.isdigit():
            raise SystemExit(
                f"--master must be host:port, got {args.master!r}")
        kv_addr = f"{host}:{int(port) + 1}"
        try:
            if args.node_rank == 0:
                kv = KVServer(int(port) + 1).start()
            peers = sync_peers(kv_addr, args.node_rank, nnodes_min,
                               payload=f"node{args.node_rank}")
        except BaseException:
            if kv is not None:
                kv.stop()
            raise
        print(f"[launch] {nnodes_min} nodes rendezvoused: {peers}")
        manager = ElasticManager(kv_addr, args.node_rank,
                                 nnodes=args.nnodes)
        manager.start(initial_world=list(range(nnodes_min)))

    epoch = 0
    group_restarts = 0
    done_marked: dict = {}
    master_misses = 0
    workers: List[_Worker] = [
        _Worker(args, i) for i in range(args.nproc_per_node)]
    for w in workers:
        w.start(epoch=epoch)

    def _sig(_s, _f):
        for w in workers:
            w.terminate()
        sys.exit(1)

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)

    def group_restart(new_epoch: int, nnodes: int = None,
                      node_rank: int = None):
        for w in workers:
            w.wait_dead()
        for w in workers:
            w.start(epoch=new_epoch, nnodes=nnodes, node_rank=node_rank)

    exit_code = 0
    try:
        while True:
            codes = [w.poll() for w in workers]
            if manager is not None:
                reason = manager.failed_reason()
                if reason is not None:
                    print(f"[launch] elastic: {reason}; stopping job")
                    for w in workers:
                        w.terminate()
                    return 1
                new_epoch = manager.current_epoch()
                if new_epoch > epoch:
                    world = manager.current_world() or []
                    if args.node_rank not in world:
                        print("[launch] this node was scaled out of the "
                              "job; exiting")
                        for w in workers:
                            w.terminate()
                        return 0
                    node_rank_now = world.index(args.node_rank)
                    args.nnodes_now = len(world)
                    epoch = new_epoch
                    print(f"[launch] elastic epoch {epoch}: world={world}"
                          f", this node re-ranked {node_rank_now}")
                    group_restart(epoch, nnodes=len(world),
                                  node_rank=node_rank_now)
                    continue
            if any(c is not None and c != 0 for c in codes):
                from ...fault.supervisor import (describe_exit,
                                                 restart_worthy)
                bad = next(c for c in codes if c is not None and c != 0)
                if not restart_worthy(bad):
                    # config-type deaths fail identically on every retry
                    # — don't burn the restart budget, stop the job now
                    print(f"[launch] worker failed with "
                          f"{describe_exit(bad)}; not restart-worthy; "
                          f"stopping job")
                    if manager is not None:
                        manager.mark_failed(
                            f"node {args.node_rank}: worker exit {bad} "
                            f"({describe_exit(bad)}), not restart-worthy")
                    for w in workers:
                        w.terminate()
                    return bad
                # coordinated-abort grace: the survivors' own abort
                # plane (collective timeout, lease expiry) should name
                # the culprit and exit with a verdict code — give it a
                # bounded window before reaping them with SIGTERM
                if args.abort_grace > 0:
                    deadline = time.monotonic() + args.abort_grace
                    while (any(w.poll() is None for w in workers)
                           and time.monotonic() < deadline):
                        time.sleep(0.2)
                    codes = [w.poll() for w in workers]
                # re-select with the full picture: a supervisor VERDICT
                # code (collective timeout, lease expiry, desync) is the
                # diagnosis — prefer it over the collateral deaths (gloo
                # errors, coordination-service aborts) that cascade from
                # the first exit, whatever rank order they landed in
                from ...fault.supervisor import EXIT_CODES
                nz = [c for c in codes if c is not None and c != 0]
                bad = next((c for c in nz if c in EXIT_CODES),
                           nz[0] if nz else bad)
                print(f"[launch] worker death: "
                      + ", ".join(f"rank {i}: {describe_exit(c)}"
                                  for i, c in enumerate(codes)))
                if group_restarts < args.max_restarts:
                    group_restarts += 1
                    if manager is not None:
                        # multi-node: a local bump alone would desync the
                        # coordinator port/world from the other nodes —
                        # publish the epoch through the manager so EVERY
                        # node's launcher restarts its group in step
                        world = manager.current_world() \
                            or list(range(args.nnodes_now))
                        new_epoch = manager.publish(world)
                        print(f"[launch] worker failed ({bad}); "
                              f"published job-wide elastic epoch "
                              f"{new_epoch} ({group_restarts}/"
                              f"{args.max_restarts})")
                        time.sleep(0.2)
                        continue  # epoch-poll path restarts the group
                    epoch += 1
                    print(f"[launch] worker failed ({bad}); elastic "
                          f"group restart {group_restarts}/"
                          f"{args.max_restarts} at epoch {epoch}")
                    group_restart(epoch, nnodes=args.nnodes_now,
                                  node_rank=node_rank_now)
                    continue
                print(f"[launch] worker failed with {bad}; "
                      f"restart budget exhausted; stopping job")
                if manager is not None:
                    manager.mark_failed(
                        f"node {args.node_rank}: worker exit {bad}, "
                        f"budget exhausted")
                for w in workers:
                    w.terminate()
                return bad
            if all(c == 0 for c in codes):
                if manager is None:
                    break
                # multi-node: a cleanly finished node must wait for the
                # JOB — peers may still fail and bump the epoch, which
                # relaunches this node's group too. mark_done is
                # idempotent and a PUT can blip, so re-issue it until one
                # is confirmed delivered.
                if not done_marked.get(epoch):
                    done_marked[epoch] = manager.mark_done(epoch)
                comp = manager.is_complete()
                if comp is not None and comp >= epoch:
                    break
                if manager.all_done(epoch):
                    manager.mark_complete(epoch)
                    break
                if comp is None and args.node_rank != 0:
                    # the KV master rides node 0; if it stays unreachable
                    # after we marked done, node 0 finished the job. One
                    # failed probe is NOT proof (a blip or a saturated
                    # server must not abandon a live job) — require
                    # several consecutive misses.
                    if not manager.master_alive():
                        master_misses += 1
                    else:
                        master_misses = 0
                    if master_misses >= 3:
                        print("[launch] master gone after local "
                              "completion; treating job as finished")
                        break
                # finished-and-waiting is not latency-critical: poll the
                # completion keys gently, not at the worker-exit cadence
                time.sleep(1.0)
                continue
            time.sleep(0.2)
    finally:
        for w in workers:
            w.close()
        if manager is not None:
            manager.stop()
        if kv is not None:
            kv.stop()
    return exit_code


if __name__ == "__main__":
    sys.exit(launch())
