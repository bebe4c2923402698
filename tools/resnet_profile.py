"""Where does the ResNet50 train step spend its time? (VERDICT r4 #2)

Ablation-based profile on the chip. Every measurement chains ``REPS``
iterations data-dependently inside ONE jitted program (scalar feedback:
``x_next = x * (1 + 0*loss)``), so per-call dispatch divides out; syncs
are value reads.

Run on the chip: python tools/resnet_profile.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

BATCH = int(os.environ.get("PROFILE_BATCH", "256"))
REPS = int(os.environ.get("PROFILE_REPS", "4"))


def timeit(fn, inputs, warmup=2, iters=3):
    for i in range(warmup):
        float(jnp.sum(fn(*inputs[i % len(inputs)])))
    ts = []
    for i in range(iters):
        t0 = time.perf_counter()
        float(jnp.sum(fn(*inputs[(warmup + i) % len(inputs)])))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def main():
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.compile.cache import enable_jax_cache
    from paddle_tpu.vision.models import resnet50

    enable_jax_cache()      # before the first compile
    print(f"backend={jax.default_backend()} batch={BATCH} reps={REPS}",
          flush=True)
    paddle.seed(0)
    model = resnet50()
    params = [p for p in model.parameters() if not p.stop_gradient]
    buffers = [b for _, b in model.named_buffers()]
    pa0 = [p._data for p in params]

    xs = [jnp.asarray(np.random.RandomState(i).randn(
        BATCH, 3, 224, 224).astype(np.float32)) for i in range(3)]
    ys = [jnp.asarray(np.random.RandomState(100 + i).randint(
        0, 1000, (BATCH,)).astype(np.int64)) for i in range(3)]

    def loss_fn_of(amp_on=True):
        def loss_fn(pa, x, y):
            originals = [p._data for p in params]
            buf0 = [b._data for b in buffers]
            for p, a in zip(params, pa):
                p._data = a
            try:
                if amp_on:
                    with amp.auto_cast(level="O1", dtype="bfloat16"):
                        out = model(paddle.Tensor(x))
                else:
                    out = model(paddle.Tensor(x))
                import paddle_tpu.nn.functional as F
                return F.cross_entropy(
                    out, paddle.Tensor(y))._data.astype(jnp.float32)
            finally:
                for p, o in zip(params, originals):
                    p._data = o
                for b, o in zip(buffers, buf0):
                    b._data = o
        return loss_fn

    def chained(per_iter):
        """Chain REPS iterations: the scalar result scales next input."""
        def f(pa, x, y):
            def body(i, carry):
                x, acc = carry
                s = per_iter(pa, x, y)
                return (x * (1.0 + 0.0 * s), acc + s)
            _, acc = jax.lax.fori_loop(0, REPS, body,
                                       (x, jnp.float32(0)))
            return acc
        return f

    inputs = [(pa0, x, y) for x, y in zip(xs, ys)]

    def add(name, per_iter):
        dt = timeit(jax.jit(chained(per_iter)), inputs) / REPS
        print(f"{name:34}: {dt * 1e3:8.1f} ms/iter", flush=True)
        return dt

    lf = loss_fn_of()

    def fwd_bwd(pa, x, y):
        loss, grads = jax.value_and_grad(lf)(pa, x, y)
        return loss + sum(jnp.sum(g) * 1e-12 for g in grads)

    def full_step(pa, x, y):
        loss, grads = jax.value_and_grad(lf)(pa, x, y)
        return loss + sum(jnp.sum(p - 0.1 * g) * 1e-12
                          for p, g in zip(pa, grads))

    t_step = add("train step (fwd+bwd+sgd, O1)", full_step)
    add("fwd+bwd (O1)", fwd_bwd)
    t_fwd = add("forward only (O1)", lf)
    add("forward only (f32)", loss_fn_of(amp_on=False))
    model.eval()
    add("forward only (O1, BN eval)", loss_fn_of())
    model.train()

    flops_step = 3 * BATCH * 4.1e9 * 2 / 2  # ~2x fwd for bwd; fwd 4.1GF
    print(f"-> step {t_step*1e3:.0f} ms = {BATCH/t_step:.0f} img/s; "
          f"fwd fraction {t_fwd/t_step:.2f}", flush=True)

    # isolated conv shapes (bf16, chained): achieved TF/s of XLA conv
    convs = [
        ("3x3 64->64 @56", (BATCH, 64, 56, 56), (64, 64, 3, 3)),
        ("3x3 128->128 @28", (BATCH, 128, 28, 28), (128, 128, 3, 3)),
        ("3x3 256->256 @14", (BATCH, 256, 14, 14), (256, 256, 3, 3)),
        ("3x3 512->512 @7", (BATCH, 512, 7, 7), (512, 512, 3, 3)),
    ]
    for name, xshape, wshape in convs:
        for fmt in ("NCHW", "NHWC"):
            if fmt == "NHWC":
                xsh = (xshape[0], xshape[2], xshape[3], xshape[1])
            else:
                xsh = xshape
            x = jnp.asarray(np.random.RandomState(0).randn(*xsh) * 0.1,
                            jnp.bfloat16)
            w = jnp.asarray(
                np.random.RandomState(1).randn(*wshape) * 0.05,
                jnp.bfloat16)
            dn = jax.lax.conv_dimension_numbers(
                xsh, wshape, (fmt, "OIHW", fmt))

            def conv_chain(x, w):
                def body(i, c):
                    y = jax.lax.conv_general_dilated(
                        c, w, (1, 1), "SAME", dimension_numbers=dn)
                    return y * jnp.bfloat16(0.1)
                return jax.lax.fori_loop(0, 16, body, x)

            cxs = [(x + jnp.bfloat16(0.001 * i), w) for i in range(3)]
            dt = timeit(jax.jit(conv_chain), cxs) / 16
            flops = 2 * np.prod(xshape) * wshape[0] * 9
            print(f"  conv {name:18} {fmt}: {dt*1e3:7.2f} ms  "
                  f"{flops/dt/1e12:6.1f} TF/s", flush=True)


if __name__ == "__main__":
    main()
