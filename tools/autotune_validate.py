"""Validate the kernel autotuner on the real chip.

For S in {1k, 2k, 8k, 32k}, causal and not: time flash fwd and bwd with
every tile (the constants and the autotuner's candidates), printing beside
each time what that tile makes the kernels run (``flash_plan``: the grid
and the executed share of the square), then (a) the hand-tuned v5e
constants against (b) ``_tuned_blocks``' pick (a causal call's is its
constant; a non-causal call's the measured winner). The pick must match
or beat the constants
(VERDICT r4 item 3 'Done' criterion), and the cache file must round-trip.

Timing discipline: jitted closures only (steady state, no retracing),
a few distinct inputs cycled across timed calls, and every timed call
ends in a value read of its result.

Run on the chip: python tools/autotune_validate.py
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


NVAR = 3


def timeit(fn, warmup=2, iters=9):
    """fn(i) runs probe input i; median of per-call value-synced times."""
    for i in range(warmup):
        float(jnp.sum(fn(i)))
    ts = []
    for i in range(iters):
        t0 = time.perf_counter()
        float(jnp.sum(fn(warmup + i)))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def main():
    from paddle_tpu.ops.pallas import autotune as at
    from paddle_tpu.ops.pallas import flash_attention as fa

    from paddle_tpu.compile.cache import enable_jax_cache
    enable_jax_cache()      # before the first compile
    cache_file = at.cache_path()
    print(f"backend={jax.default_backend()} chip={at.chip_kind()} "
          f"cache={cache_file}")
    assert at.should_autotune(), "autotune disabled — nothing to validate"

    B, H, D = 2, 8, 128
    dt = jnp.bfloat16
    rows = []
    for S in (1024, 2048, 8192, 32768):
        b, h = (B, H) if S <= 8192 else (1, 4)   # fit 32k on one chip
        qs, ks, vs = [], [], []
        for v in range(NVAR):
            kp = jax.random.key(100 + v)
            qs.append(jax.random.normal(kp, (b, S, h, D)).astype(dt))
            ks.append(jax.random.normal(
                jax.random.fold_in(kp, 1), (b, S, h, D)).astype(dt))
            vs.append(jax.random.normal(
                jax.random.fold_in(kp, 2), (b, S, h, D)).astype(dt))
        scale = 1.0 / (D ** 0.5)

        for causal in (True, False):
            kernel_flops = 4.0 * b * h * S * S * D * (0.5 if causal else 1.0)
            reps = at.probe_reps(kernel_flops)

            def jfwd(bq, bk):
                kern = functools.partial(
                    fa._flash_fwd_bshd, causal=causal, scale=scale,
                    block_q=bq, block_k=bk)
                f = jax.jit(lambda q0, k0, v0: jax.lax.fori_loop(
                    0, reps, lambda _, q: kern(q, k0, v0)[0], q0))
                return lambda i: f(qs[i % NVAR], ks[i % NVAR], vs[i % NVAR])

            fdef = ((fa.CAUSAL_BLOCK,) * 2 if causal else
                    (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K))
            f0 = jax.jit(functools.partial(
                fa._flash_fwd_bshd, causal=causal, scale=scale,
                block_q=fdef[0], block_k=fdef[1]))
            outs, lses = zip(*(f0(qs[v], ks[v], vs[v]) for v in range(NVAR)))

            def jbwd(bq, bk):
                kern = functools.partial(
                    fa._flash_bwd_bshd, causal=causal, scale=scale,
                    block_q=bq, block_k=bk)
                f = jax.jit(lambda q0, k0, v0, o0, l0: jax.lax.fori_loop(
                    0, reps, lambda _, q: kern(q, k0, v0, o0, l0, o0)[0], q0))
                return lambda i: f(qs[i % NVAR], ks[i % NVAR], vs[i % NVAR],
                                   outs[i % NVAR], lses[i % NVAR])

            bdef = fdef if causal else (fa._bwd_block_for(S),) * 2
            for kind, make, cdef, cands in (
                    ("fwd", jfwd, fdef, fa.FWD_TILE_CANDIDATES),
                    ("bwd", jbwd, bdef, fa.BWD_TILE_CANDIDATES)):
                times = {}
                for c in dict.fromkeys([cdef] + cands):
                    try:
                        times[c] = timeit(make(*c))
                    except Exception as e:  # noqa: BLE001 — the refusal is the datum
                        print(f"{kind} S={S} causal={causal} tile={c}: "
                              f"FAILED {type(e).__name__}: {str(e)[:200]}")
                        continue
                    plan = fa.flash_plan(S, S, causal, *c, h, D)
                    print(f"{kind} S={S:>6} causal={causal!s:5} "
                          f"tile={str(c):>12} {times[c] * 1e3:8.2f}m  "
                          f"grid={plan['tiles']} "
                          f"executed_share={plan['executed_share']:.4f}")
                tuned = tuple(fa._tuned_blocks(kind, h, S, S, D, dt, causal,
                                               scale))
                rows.append((kind, S, cdef, times[cdef], tuned,
                             times[tuned]))

    print(f"\n{'pass':4} {'S':>6} {'constants':>12} {'t_const':>9} "
          f"{'tuned':>12} {'t_tuned':>9} {'speedup':>8}")
    worst = 1e9
    for kind, S, cdef, td, ctun, tt in rows:
        sp = td / tt
        worst = min(worst, sp)
        print(f"{kind:4} {S:>6} {str(cdef):>12} {td*1e3:8.2f}m "
              f"{str(tuple(ctun)):>12} {tt*1e3:8.2f}m {sp:7.3f}x")

    # cache round-trip
    with open(cache_file) as f:
        data = json.load(f)
    n = len(data)
    fresh = at.AutotuneCache(cache_file)
    for key in data:
        assert fresh.get(key) is not None
    print(f"cache round-trip ok: {n} keys persisted")
    # tolerance: "match" = within 10% (one run per side, spread unknown)
    assert worst > 0.90, f"autotuned choice lost to constants ({worst:.3f}x)"
    print(f"VALIDATED: autotuned >= constants everywhere "
          f"(worst {worst:.3f}x)")


if __name__ == "__main__":
    main()
