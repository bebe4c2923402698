"""The one general traffic generator. A mix is a data file of parameters
(``benchmark/traffic/<mix>.json``); this module turns it and ``--seed`` into
the requests a serving run sends.

Every seed gets the SAME schedule of lengths and arrival gaps with other
token ids, so that the seed does not change the work: lengths and gaps are
the stratified quantiles of their distributions ((i + 0.5) / n for i < n),
paired and put in order by permutations fixed in the mix.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths: the stratified quantiles of ``spec``'s
    distribution, clipped to its ``min``..``max``."""
    qs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(q)) for q in qs])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + qs * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        vals = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", max(lo, int(vals.max()) + 1))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def length_pairs(mix: dict, n: int) -> np.ndarray:
    """``[n, 2]`` (prompt, output) lengths, the same for every seed."""
    prompts = quantile_lengths(mix["prompt_len"], n)
    outputs = quantile_lengths(mix["output_len"], n)
    pairing = np.random.default_rng(mix.get("pairing_seed", 0)).permutation(n)
    return np.stack([prompts, outputs[pairing]], axis=1)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """The stratified quantiles of the exponential gap at ``rate`` a
    second; their mean is 1 / rate to within a few percent."""
    qs = (np.arange(n) + 0.5) / n
    return -np.log1p(-qs) / rate


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """The run's requests. Open loop: ``rate_rps x seconds`` requests, each
    with the time it is due. Closed loop: cycles of ``requests_per_cycle``
    requests (``due`` None), enough of them that ``clients`` clients never
    run out; a client takes the next when its last finishes.

    The mix fixes the whole schedule (which length follows which, after
    which gap: permutations drawn from the mix's ``order_seed``), as a
    recorded trace would; the run's seed draws every token id (and the
    weights). Which request queues behind a 1500-token prompt decides a tail
    and which requests straddle the cut decides a rate, so a new order per
    seed would measure the order: measured, a shuffle per seed moved
    ``serve_tokens_per_s`` by 11 % between seeds that repeat to 0.0 %."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    order_rng = np.random.default_rng(mix.get("order_seed", 0))
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_rps"] * seconds)))
        pairs = length_pairs(mix, n)[order_rng.permutation(n)]
        gaps = poisson_gaps(mix["rate_rps"], n)[order_rng.permutation(n)]
        due = list(np.cumsum(gaps) - gaps[0] * 0.5)
    elif mix["loop"] == "closed":
        per = mix["requests_per_cycle"]
        cycle = length_pairs(mix, per)[order_rng.permutation(per)]
        pairs = np.concatenate([cycle] * mix.get("cycles", 16))
        n, due = len(pairs), [None] * len(pairs)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    out = []
    for i in range(n):
        p_len, o_len = int(pairs[i, 0]), int(pairs[i, 1])
        out.append({"index": i, "due": due[i],
                    "prompt": rng.integers(1, vocab, p_len).tolist(),
                    "max_new_tokens": o_len})
    return {"requests": out, "loop": mix["loop"],
            "clients": mix.get("clients")}
