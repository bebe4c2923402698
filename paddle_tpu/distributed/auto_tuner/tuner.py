"""Parallel-config auto tuner.

Capability parity with the reference tuner (reference:
python/paddle/distributed/auto_tuner/tuner.py + prune.py — enumerate
(dp, mp, pp, sharding) degree combinations, prune invalid ones, launch
trial runs, pick the fastest). TPU-native: a trial is a jitted probe step
on the candidate mesh (no process relaunch needed — meshes are rebuilt in
process), timed with the usual vary-the-input discipline.
"""
from __future__ import annotations

import itertools
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from .. import mesh as mesh_mod


def candidate_configs(n_devices: int, axes=("dp", "mp", "pp"),
                      max_degree: Optional[int] = None) -> List[Dict]:
    """All factorizations of n_devices over the axes (reference prune.py
    divisor enumeration)."""
    max_degree = max_degree or n_devices
    degrees = [d for d in range(1, n_devices + 1) if n_devices % d == 0
               and d <= max_degree]
    out = []
    for combo in itertools.product(degrees, repeat=len(axes)):
        if int(np.prod(combo)) == n_devices:
            out.append(dict(zip(axes, combo)))
    return out


def prune(configs: List[Dict], model_cfg: Optional[Dict] = None
          ) -> List[Dict]:
    """Drop combinations that cannot work (reference prune.py): mp must
    divide heads/hidden; pp must divide layers."""
    if not model_cfg:
        return configs
    kept = []
    for c in configs:
        mp = c.get("mp", 1)
        pp = c.get("pp", 1)
        if mp > 1:
            if model_cfg.get("num_heads", mp) % mp:
                continue
            if model_cfg.get("hidden_size", mp) % mp:
                continue
        if pp > 1 and model_cfg.get("num_layers", pp) % pp:
            continue
        kept.append(c)
    return kept


class AutoTuner:
    def __init__(self, probe_fn: Callable[[Dict], float],
                 model_cfg: Optional[Dict] = None,
                 train_cfg: Optional[Dict] = None, cluster=None):
        """probe_fn(config) -> step_time_seconds; raise to reject.
        (Warmup/repeat policy belongs to the probe — see default_probe.)
        ``train_cfg``/``cluster`` enable analytic cost-model pruning
        (cost_model.py, reference auto_parallel/static/cost_model.py):
        configs whose estimated per-chip HBM exceeds the cluster budget
        are rejected WITHOUT a trial run, and survivors are tried in
        estimated-step-time order."""
        self.probe_fn = probe_fn
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg
        self.cluster = cluster
        self.results: List[Dict] = []

    def tune(self, n_devices: Optional[int] = None,
             axes=("dp", "mp", "pp")) -> Dict:
        n = n_devices or jax.device_count()
        configs = prune(candidate_configs(n, axes), self.model_cfg)
        if not configs:
            raise ValueError("no valid parallel configs to try")
        if self.model_cfg and (self.train_cfg is not None
                               or self.cluster is not None):
            from .cost_model import prune_by_cost
            configs, rejected = prune_by_cost(
                configs, self.model_cfg, self.train_cfg, self.cluster)
            self.results.extend(rejected)
            if not configs:
                raise ValueError(
                    "cost model rejected every candidate config: "
                    + "; ".join(r["pruned"] for r in rejected[:3]))
        best = None
        for cfg in configs:
            try:
                t = self.probe_fn(dict(cfg))
            except Exception as e:     # OOM / invalid layout: record+skip
                self.results.append({**cfg, "error": str(e)[:200]})
                continue
            self.results.append({**cfg, "step_time": t})
            if best is None or t < best[1]:
                best = (cfg, t)
        if best is None:
            raise RuntimeError("every candidate config failed")
        return {**best[0], "step_time": best[1]}


def tune(probe_fn, n_devices=None, model_cfg=None, axes=("dp", "mp", "pp")):
    return AutoTuner(probe_fn, model_cfg).tune(n_devices, axes)


def default_probe(make_step: Callable[[Dict], Callable], warmup=1, iters=3):
    """Build a probe_fn from make_step(config) -> zero-arg step callable;
    times it with per-iteration perturbation-free repeats."""
    def probe(cfg: Dict) -> float:
        step = make_step(cfg)
        for _ in range(warmup):
            step()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters
    return probe
