"""Head + loss alone on the chip, at the fit cells' sizes.

What each step of "a model that is handed labels computes head and loss
chunk by chunk" gives apart from the whole training step: forward + backward
(``value_and_grad`` over the hidden rows and the table, float32 leaves under
bf16 O1 autocast, as ``Engine``'s step has them) of

  unfused   ``matmul`` then ``cross_entropy`` over ``logits[:, :-1, :]``,
            the (rows, vocabulary) float32 logits and their gradient alive
  four      ``F.fused_linear_cross_entropy`` with each chunk's logits
            rematerialised in the backward (``reduction="none"`` and a mean
            outside: the op as it was before it had a rule of its own)
  three     the op's hand-written rule (``reduction="mean"``), at several
            ``chunk_rows``

at GPT-2 medium's 8 x 1024 rows x 1024 x 50 257 and LFM2-MoE's 2 x 8192 x
2048 x 16 384. Timing: jitted closures, three inputs cycled, every timed call
ends in a read of its loss; the median of nine.

Run on the chip: python tools/head_loss_bench.py [--out FILE] (``--tiny``
checks the script on a CPU)
Outside every path a benchmark cell runs; no test imports it.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

SIZES = {"gpt2-medium": (8, 1024, 1024, 50257),
         "lfm2-8b-a1b": (2, 8192, 2048, 16384)}
CHUNKS = (2048, 4096, 8192)
NVAR = 3


def forms():
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models._head import next_token_loss
    from paddle_tpu.nn import functional as F

    def autocast(fn):
        def run(h, table, ids):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                return fn(Tensor(h), Tensor(table), Tensor(ids))._data
        return run

    def unfused(h, table, ids):
        logits = paddle.ops.matmul(h, table, transpose_y=True)
        v = logits.shape[-1]
        return F.cross_entropy(
            paddle.ops.reshape(logits[:, :-1, :], [-1, v]),
            paddle.ops.reshape(ids[:, 1:], [-1]))

    def four(h, table, ids):
        rows = F.fused_linear_cross_entropy(
            h[:, :-1, :], table, ids[:, 1:], transpose_y=True,
            reduction="none")
        return rows.mean()

    def three(chunk_rows):
        def run(h, table, ids):
            shifted = paddle.ops.concat(
                [ids[:, 1:], paddle.ops.full_like(ids[:, :1], -100)], axis=1)
            return F.fused_linear_cross_entropy(
                h, table, shifted, transpose_y=True, chunk_rows=chunk_rows)
        return run

    out = {"unfused": unfused, "four": four,
           "three(model)": lambda h, t, ids: next_token_loss(h, t, ids, True)}
    for c in CHUNKS:
        out[f"three(chunk={c})"] = three(c)
    return {name: autocast(fn) for name, fn in out.items()}


def median_ms(step, inputs, warmup=2, iters=9):
    for i in range(warmup):
        float(step(*inputs[i % NVAR])[0])
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        float(step(*inputs[(warmup + i) % NVAR])[0])
        times.append(time.perf_counter() - t0)
    return 1e3 * sorted(times)[len(times) // 2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/head_loss_bench.json")
    ap.add_argument("--tiny", action="store_true",
                    help="sizes a CPU runs: a check of the script, no timing")
    args = ap.parse_args()
    sizes = {"tiny": (2, 64, 32, 257)} if args.tiny else SIZES
    device = jax.devices()[0]
    rows = []
    for size, (b, s, hidden, vocab) in sizes.items():
        inputs = []
        for i in range(NVAR):
            k = jax.random.key(7 + i)
            inputs.append((
                jax.random.normal(k, (b, s, hidden), jnp.float32),
                0.02 * jax.random.normal(jax.random.fold_in(k, 1),
                                         (vocab, hidden), jnp.float32),
                jax.random.randint(jax.random.fold_in(k, 2), (b, s), 0,
                                   vocab)))
        flop = 2.0 * b * s * hidden * vocab
        for name, fn in forms().items():
            try:
                step = jax.jit(jax.value_and_grad(
                    fn, argnums=(0, 1))).lower(*inputs[0]).compile()
                memory = step.memory_analysis()
                ms = median_ms(step, inputs)
            except Exception as e:  # noqa: BLE001 — a form that does not fit says so
                rows.append({"size": size, "form": name,
                             "error": f"{type(e).__name__}: {e}"[:300]})
                print(json.dumps(rows[-1]), flush=True)
                continue
            rows.append({
                "size": size, "form": name, "ms": ms,
                "loss": float(step(*inputs[0])[0]),
                "one_product_ms_at_197T": 1e3 * flop / 197e12,
                "temp_bytes": int(memory.temp_size_in_bytes)})
            print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": {"platform": device.platform,
                              "kind": device.device_kind}, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
