"""A/B: bf16-resident weights + f32 master vs f32-resident weights, on
the GPT-2 bench rung — driven through bench.py's OWN harness
(`_run_train_bench(bf16_weights=...)`) so the comparison always measures
the shipped timing/donation/sync discipline rather than a copy that can
drift.

Run on the chip: ``python tools/bench_weight_dtype.py`` (it refuses to
run without one; its numbers are device metrics).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    import paddle_tpu as paddle
    from bench import _run_train_bench, chip_peak_flops
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if jax.default_backend() != "tpu":
        sys.exit(f"bench_weight_dtype.py measures on the chip; the default "
                 f"backend is {jax.default_backend()!r}")
    from paddle_tpu.compile.cache import enable_jax_cache
    enable_jax_cache()      # before the first compile
    cfg = GPTConfig(max_seq_len=1024)
    batch, seq, iters = 8, 1024, 10
    model = GPTForCausalLM(cfg)
    params = [p for p in model.parameters() if not p.stop_gradient]

    def make_inputs(i):
        rng = np.random.RandomState(i)
        return (jnp.asarray(rng.randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int64)),)

    def loss_of(model, ids):
        _, loss = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        return loss

    results = {}
    for flag in (False, True):
        dt, loss0, loss_end, n_params, _attr = _run_train_bench(
            model, params, make_inputs, loss_of, iters,
            bf16_weights=flag)
        tok_s = batch * seq / dt
        fpt = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
        mfu = fpt * tok_s / chip_peak_flops(jax.devices()[0])
        name = "bf16_weights" if flag else "f32_weights"
        results[name] = tok_s
        print(f"{name}: {tok_s:,.0f} tok/s  step {dt*1e3:.1f} ms  "
              f"MFU {mfu:.4f}  loss {loss_end:.3f}")
    print(f"bf16/f32 speedup: "
          f"{results['bf16_weights'] / results['f32_weights']:.4f}x")


if __name__ == "__main__":
    main()
