"""Host-overhead measurement for the SOT steady-state bypass.

An un-jitted GPT-2 eval step with a forced mid-frame host sync (the
graph-break pattern that routes to SOT partial-frame capture), measured
two ways:

* replay  — the pre-bypass behavior: every call re-runs the Python frame,
  re-records ops into segments, re-fingerprints guards (cached XLA
  programs, no recompiles)
* bypass  — the steady state: one frame-level guard check, then the
  stitched compiled segments run directly

Run on the chip: python tools/sot_bypass_bench.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.compile.cache import enable_jax_cache
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    enable_jax_cache()      # before the first compile
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=1024,
                    use_flash_attention=False)
    net = GPTForCausalLM(cfg)
    for p in net.parameters():
        p.stop_gradient = True   # eval: grad-free -> bypass-eligible

    x = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (1, 128)).astype(np.int64))

    def step(ids):
        s = float(paddle.ops.mean(
            paddle.ops.cast(ids, "float32")).numpy())  # mid-frame break
        logits = net(ids)
        if s > 1e12:
            logits = logits * 0.0
        return logits

    st = paddle.jit.to_static(step, full_graph=False)

    # warm up: record + compile (call 1), journal-match (call 2)
    jax.block_until_ready(st(x)._data)
    jax.block_until_ready(st(x)._data)
    sig = next(iter(st._sot_frames))
    n = 20

    # ---- replay steady state (pre-bypass behavior)
    ts = []
    for _ in range(n):
        st._sot_frames[sig]["stable"] = False   # force Python replay
        t0 = time.perf_counter()
        out = st(x)
        jax.block_until_ready(out._data)
        ts.append(time.perf_counter() - t0)
    replay_ms = 1e3 * float(np.median(ts))
    assert st.sot_stats["bypassed"] is False

    # ---- bypass steady state
    st(x)
    st(x)
    assert st.sot_stats["bypassed"] is True, st.sot_stats
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = st(x)
        jax.block_until_ready(out._data)
        ts.append(time.perf_counter() - t0)
    bypass_ms = 1e3 * float(np.median(ts))
    assert st.sot_stats["bypassed"] is True

    # ---- plain eager for context (per-op dispatch, no SOT at all)
    def eager_step(ids):
        logits = net(ids)
        return logits

    jax.block_until_ready(eager_step(x)._data)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = eager_step(x)
        jax.block_until_ready(out._data)
        ts.append(time.perf_counter() - t0)
    eager_ms = 1e3 * float(np.median(ts))

    print(f"GPT-2 124M eval step (B=1, S=128), {jax.default_backend()}:")
    print(f"  eager per-op dispatch : {eager_ms:8.2f} ms/call")
    print(f"  SOT replay (before)   : {replay_ms:8.2f} ms/call")
    print(f"  SOT bypass (after)    : {bypass_ms:8.2f} ms/call")
    print(f"  bypass vs replay      : {replay_ms / bypass_ms:8.2f}x "
          f"less host time")


if __name__ == "__main__":
    main()
