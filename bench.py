"""Benchmark driver hook.

Default run covers the whole BASELINE.md ladder (gpt2 + resnet50 + bert +
llama): one JSON line per rung as it lands, then a combined summary line
LAST — {"metric": "train_ladder_vs_baseline_geomean", ...} with per-rung
results in "extra" — so a driver that keeps only the final line records
the full ladder. Each rung is a full training step — forward + backward +
AdamW update compiled as ONE XLA program (the steady-state path) —
reporting tokens/s / images/s plus MFU versus the chip's peak bf16 FLOPs.
``vs_baseline`` is MFU / 0.40 for token models (the published A100
GPT-class MFU bar; BASELINE.md: the reference repo publishes no absolute
numbers) and img/s / 2080 for ResNet50.

``BENCH_MODEL=gpt2|resnet50|bert|llama`` runs a single rung and prints
exactly one JSON line.
"""
import json
import os
import sys
import time

# The ladder reports against PINNED, hand-validated kernel constants:
# one noisy first-sight autotune probe would cache a bad winner and
# silently change what this benchmark measures. The autotuner is a user
# feature, validated separately by tools/autotune_validate.py.
# BENCH_AUTOTUNE=1 opts in.
if os.environ.get("BENCH_AUTOTUNE") != "1":
    os.environ.setdefault("FLAGS_use_autotune", "0")

import jax
import jax.numpy as jnp
import numpy as np


def chip_peak_flops(device) -> float:
    # canonical spec table lives with the roofline layer
    from paddle_tpu.observability.perf import chip_peak_flops as _cpf
    return _cpf(device)


def _run_train_bench(model, params, make_inputs, loss_of, iters,
                     bf16_weights=True, moment_dtype=None):
    """Shared harness: jit fwd+bwd+AdamW as one program; each timed iter
    uses a different input batch, and the final sync is a value read of
    the chained loss. With ``bf16_weights`` float params live
    bf16-resident with an f32 master in the optimizer (mixed-precision
    discipline: halves weight HBM traffic on the hot path; measured +3%
    tok/s on GPT-2 in round 5 — the conv rung opts out, it gained
    nothing there)."""
    import paddle_tpu as paddle  # noqa: F401
    from paddle_tpu import amp

    b1, b2, eps, wd, lr = 0.9, 0.95, 1e-8, 0.1, 2.5e-4

    def bf16_resident(p):
        return bf16_weights and np.dtype(p._data.dtype) == np.float32

    # live and master are SEPARATELY donated arguments: each leaf must be
    # a distinct buffer (an aliased buffer donated twice is a runtime
    # error), so both are materialized as copies
    from paddle_tpu.optimizer.optimizer import (_moment_decode,
                                                _moment_encode)

    master = [jnp.array(p._data, copy=True) for p in params]
    live = [m.astype(jnp.bfloat16) if bf16_resident(p)
            else jnp.array(m, copy=True) for p, m in zip(params, master)]
    # free the model's ORIGINAL f32 arrays: master already holds the f32
    # copy, live the compute copy. Keeping the originals pinned costs
    # 4 B/param of dead HBM — at 1.3B params that alone is the difference
    # between fitting a 16 GB chip and RESOURCE_EXHAUSTED. (The params
    # are re-bound to traced values inside loss_fn on every step; the
    # eager payload is never read again in the bench.)
    for p, l in zip(params, live):
        p._data = l
    # moment_dtype: optimizer-state precision — "int8" stores m/v as
    # blockwise-quantized int8 (+1/256 f32 scales), the HBM knob that
    # fits the 1.4B rung on one 16 GB v5e (see optimizer.Adam)
    m_state = [_moment_encode(jnp.zeros_like(m), moment_dtype)
               for m in master]
    v_state = [_moment_encode(jnp.zeros_like(m), moment_dtype,
                              nonneg=True) for m in master]

    def train_step(live_arrays, master_arrays, m_st, v_st, step_t,
                   *inputs):
        def loss_fn(pa):
            originals = [p._data for p in params]
            for p, a in zip(params, pa):
                p._data = a
            try:
                with amp.auto_cast(level="O1", dtype="bfloat16"):
                    loss = loss_of(model, *inputs)
                return loss._data.astype(jnp.float32)
            finally:
                for p, o in zip(params, originals):
                    p._data = o

        loss, grads = jax.value_and_grad(loss_fn)(live_arrays)
        t = step_t.astype(jnp.float32)
        new_live, new_master, new_m, new_v = [], [], [], []
        for w, mw, g, m_enc, v_enc in zip(live_arrays, master_arrays,
                                          grads, m_st, v_st):
            g = g.astype(jnp.float32)
            shape = tuple(mw.shape)
            m = _moment_decode(m_enc, shape, moment_dtype)
            v = _moment_decode(v_enc, shape, moment_dtype, nonneg=True)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            m_hat = m / (1 - b1 ** t)
            v_hat = v / (1 - b2 ** t)
            mw = mw * (1 - lr * wd)
            mw = mw - lr * m_hat / (jnp.sqrt(v_hat) + eps)
            new_master.append(mw)
            new_live.append(mw.astype(w.dtype))
            new_m.append(_moment_encode(m, moment_dtype))
            new_v.append(_moment_encode(v, moment_dtype, nonneg=True))
        return loss, new_live, new_master, new_m, new_v

    jitted = jax.jit(train_step, donate_argnums=(0, 1, 2, 3))
    batches = [make_inputs(i) for i in range(iters + 1)]

    loss0, live, master, m_state, v_state = jitted(
        live, master, m_state, v_state, jnp.asarray(1, jnp.int32),
        *batches[0])
    loss0 = float(loss0)

    t0 = time.perf_counter()
    for i in range(iters):
        loss, live, master, m_state, v_state = jitted(
            live, master, m_state, v_state, jnp.asarray(2 + i, jnp.int32),
            *batches[1 + i])
    loss_end = float(loss)  # chained state: forces every iter to execute
    dt = (time.perf_counter() - t0) / iters
    n_params = sum(int(np.prod(m.shape)) for m in master)

    # attribution pass: two SYNCED steps under the span tracer (the timed
    # loop above stays async — per-step sync would change what it
    # measures).
    attribution = None
    try:
        from paddle_tpu.observability import perf as _perf

        state = {"s": (live, master, m_state, v_state), "i": 0}

        def attr_step():
            i, (lv, ms, m_s, v_s) = state["i"], state["s"]
            state["i"] += 1
            loss, *new = jitted(lv, ms, m_s, v_s,
                                jnp.asarray(2 + iters + i, jnp.int32),
                                *batches[1 + (i % iters)])
            state["s"] = tuple(new)
            return loss

        att = _perf.step_attribution(attr_step, iters=2, warmup=0,
                                     name="train_step")["total"]
        attribution = {k: round(att[k], 4) for k in
                       ("compute_frac", "collective_frac", "host_frac",
                        "idle_frac")}
        attribution["synced_step_s"] = round(att["step_s"]
                                             / max(att["n_steps"], 1), 4)
    except Exception:
        pass
    return dt, loss0, loss_end, n_params, attribution


def _env_int(name, default):
    v = os.environ.get(name)
    return default if v is None else int(v)


def _env_bool(name, default):
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("0", "false", "no", "off", "")


def _fusion_on() -> bool:
    """Ladder rungs record the graph-fusion flag state in extra, so a
    BENCH_*.json trajectory always says which regime it measured."""
    from paddle_tpu.core import flags
    return bool(flags.get_flag("enable_fusion"))


def _bench_gpt(small):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if small:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128,
                        use_flash_attention=False)
        batch, seq, iters = 2, 128, 2
    else:
        # BASELINE.md config #4: GPT-2 345M (gpt2-medium geometry)
        cfg = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                        max_seq_len=1024,
                        recompute=_env_bool("BENCH_RECOMPUTE", False),
                        fused_loss=_env_bool("BENCH_FUSED", True))
        batch, seq, iters = _env_int("BENCH_BATCH", 8), 1024, 10
    model = GPTForCausalLM(cfg)
    params = [p for p in model.parameters() if not p.stop_gradient]

    def make_inputs(i):
        rng = np.random.RandomState(i)
        return (jnp.asarray(rng.randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int64)),)

    def loss_of(model, ids):
        import paddle_tpu as paddle
        _, loss = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        return loss

    dt, loss0, loss_end, n_params, attribution = _run_train_bench(
        model, params, make_inputs, loss_of, iters)
    tokens_per_sec = batch * seq / dt
    flops_per_token = 6 * n_params + \
        12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = flops_per_token * tokens_per_sec / chip_peak_flops(
        jax.devices()[0])
    return {
        "metric": "gpt2_345m_train_tokens_per_sec_per_chip"
                  if not small else "gpt_tiny_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_time_s": round(dt, 4), "mfu": round(mfu, 4),
                  "params": n_params,
                  "device": str(getattr(jax.devices()[0], "device_kind",
                                        jax.default_backend())),
                  "attribution": attribution,
                  "fusion": _fusion_on(),
                  "loss_first": round(loss0, 3),
                  "loss_last": round(loss_end, 3)},
    }


def _bench_resnet50(small):
    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F
    from paddle_tpu.vision.models import resnet50

    batch, hw, iters = (4, 64, 2) if small else (256, 224, 10)
    model = resnet50()
    model.train()
    params = [p for p in model.parameters() if not p.stop_gradient]

    def make_inputs(i):
        rng = np.random.RandomState(i)
        return (jnp.asarray(rng.randn(batch, 3, hw, hw)
                            .astype(np.float32)),
                jnp.asarray(rng.randint(0, 1000, (batch,))
                            .astype(np.int64)))

    def loss_of(model, x, y):
        logits = model(paddle.Tensor(x))
        return F.cross_entropy(logits, paddle.Tensor(y))

    dt, loss0, loss_end, n_params, attribution = _run_train_bench(
        model, params, make_inputs, loss_of, iters, bf16_weights=False)
    imgs_per_sec = batch / dt
    # chip-relative utilization bar, consistent with the token rungs'
    # MFU-vs-0.40 treatment: ResNet50 training is ~12.3 GFLOPs/img
    # (3x the 4.1 GFLOP forward); the A100 reference 2080 img/s is
    # 2080*12.3e12/312e12 = 8.2% utilization of A100 peak bf16. Raw
    # img/s would compare chips, not frameworks.
    flops_per_img = 3 * 4.1e9
    util = flops_per_img * imgs_per_sec / chip_peak_flops(jax.devices()[0])
    a100_util = 2080 * flops_per_img / 312e12
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 1),
        "unit": "images/s",
        "vs_baseline": round(util / a100_util, 4),
        "extra": {"step_time_s": round(dt, 4), "params": n_params,
                  "batch": batch, "mfu": round(util, 4),
                  "a100_ref_util": round(a100_util, 4),
                  "attribution": attribution,
                  "fusion": _fusion_on(),
                  "loss_first": round(loss0, 3),
                  "loss_last": round(loss_end, 3)},
    }


def _bench_bert(small):
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    if small:
        cfg = BertConfig(vocab_size=512, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=256,
                         max_position_embeddings=128,
                         hidden_dropout_prob=0.0,
                         attention_probs_dropout_prob=0.0)
        batch, seq, iters = 2, 128, 2
    else:
        # vocab padded 30522 -> 30592 (next multiple of 128: MXU lane
        # alignment for the MLM head matmul, the standard GPT-2-style
        # padded-vocab trick); fused chunked head+loss
        import paddle_tpu as _p
        if not _env_bool("BENCH_FLASH", True):
            _p.set_flags({"use_pallas_kernels": False})
        cfg = BertConfig(vocab_size=_env_int("BENCH_VOCAB", 30592),
                         hidden_dropout_prob=0.0,
                         attention_probs_dropout_prob=0.0,
                         recompute=_env_bool("BENCH_RECOMPUTE", False),
                         fused_loss=_env_bool("BENCH_FUSED", True))
        batch, seq, iters = _env_int("BENCH_BATCH", 48), 512, 10
    model = BertForPretraining(cfg)
    params = [p for p in model.parameters() if not p.stop_gradient]

    def make_inputs(i):
        rng = np.random.RandomState(i)
        return (jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq))
                            .astype(np.int64)),)

    def loss_of(model, ids):
        _, _, loss = model(paddle.Tensor(ids),
                           masked_lm_labels=paddle.Tensor(ids))
        return loss

    dt, loss0, loss_end, n_params, attribution = _run_train_bench(
        model, params, make_inputs, loss_of, iters)
    tokens_per_sec = batch * seq / dt
    flops_per_token = 6 * n_params + \
        12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    mfu = flops_per_token * tokens_per_sec / chip_peak_flops(
        jax.devices()[0])
    return {
        "metric": "bert_base_mlm_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_time_s": round(dt, 4), "mfu": round(mfu, 4),
                  "params": n_params, "attribution": attribution,
                  "fusion": _fusion_on(),
                  "loss_first": round(loss0, 3),
                  "loss_last": round(loss_end, 3)},
    }


def _bench_llama(small):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, llama_tiny

    if small:
        cfg = llama_tiny(use_flash_attention=False)
        batch, seq, iters = 2, 128, 2
    else:
        # largest LLaMA that trains on one 16 GB v5e at S=2048 with
        # bf16-resident weights + f32 master + f32 Adam moments
        # (14 B/param of state) and block remat: ~770M params
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                          intermediate_size=4096, num_layers=24,
                          num_heads=12, max_seq_len=2048,
                          recompute=_env_bool("BENCH_RECOMPUTE", True),
                          fused_loss=_env_bool("BENCH_FUSED", True))
        batch, seq, iters = _env_int("BENCH_BATCH", 4), 2048, 5
    from paddle_tpu.models import LlamaForCausalLM
    model = LlamaForCausalLM(cfg)
    params = [p for p in model.parameters() if not p.stop_gradient]

    def make_inputs(i):
        rng = np.random.RandomState(i)
        return (jnp.asarray(rng.randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int64)),)

    def loss_of(model, ids):
        _, loss = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        return loss

    dt, loss0, loss_end, n_params, attribution = _run_train_bench(
        model, params, make_inputs, loss_of, iters)
    tokens_per_sec = batch * seq / dt
    flops_per_token = 6 * n_params + \
        12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = flops_per_token * tokens_per_sec / chip_peak_flops(
        jax.devices()[0])
    return {
        "metric": "llama_770m_s2048_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_time_s": round(dt, 4), "mfu": round(mfu, 4),
                  "params": n_params, "attribution": attribution,
                  "fusion": _fusion_on(),
                  "loss_first": round(loss0, 3),
                  "loss_last": round(loss_end, 3)},
    }


def _bench_llama14(small):
    """LLaMA-1.3B-class rung (BASELINE.md ladder #5 direction): the
    largest LLaMA one 16 GB v5e trains, enabled by int8 blockwise
    optimizer moments (~8 B/param of state vs 14 with f32 moments) +
    bf16-resident weights + block remat + fused chunked loss. The HBM
    budget table in README extrapolates this recipe to 7B on v5p-32."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, llama_tiny

    if small:
        cfg = llama_tiny(use_flash_attention=False)
        batch, seq, iters = 2, 128, 2
        moment_dtype = "int8"
    else:
        # LLaMA-1.3B geometry (h=2048, L=24, heads=16, inter=5504),
        # 1.345B params — the largest config that clears 1.0x baseline
        # on 16 GB (1.45B ALSO trains via BENCH_LAYERS=26 BENCH_BATCH=1,
        # measured MFU 0.354: memory fits, batch-1 underutilizes)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504,
                          num_layers=_env_int("BENCH_LAYERS", 24),
                          num_heads=16, max_seq_len=2048,
                          recompute=_env_bool("BENCH_RECOMPUTE", True),
                          fused_loss=_env_bool("BENCH_FUSED", True))
        batch, seq, iters = _env_int("BENCH_BATCH", 2), 2048, 4
        moment_dtype = os.environ.get("BENCH_MOMENT_DTYPE", "int8")
    model = LlamaForCausalLM(cfg)
    params = [p for p in model.parameters() if not p.stop_gradient]

    def make_inputs(i):
        rng = np.random.RandomState(i)
        return (jnp.asarray(rng.randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int64)),)

    def loss_of(model, ids):
        _, loss = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        return loss

    dt, loss0, loss_end, n_params, attribution = _run_train_bench(
        model, params, make_inputs, loss_of, iters,
        moment_dtype=moment_dtype)
    tokens_per_sec = batch * seq / dt
    flops_per_token = 6 * n_params + \
        12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = flops_per_token * tokens_per_sec / chip_peak_flops(
        jax.devices()[0])
    return {
        "metric": "llama_1p3b_s2048_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_time_s": round(dt, 4), "mfu": round(mfu, 4),
                  "params": n_params, "moment_dtype": moment_dtype,
                  "attribution": attribution,
                  "fusion": _fusion_on(),
                  "loss_first": round(loss0, 3),
                  "loss_last": round(loss_end, 3)},
    }


def _bench_compile_cache(small):
    """Cold-start vs warm-start compile wall time through the persistent
    compilation cache (BENCH_MODEL=compile_cache; paddle_tpu/compile/).

    Cold = first call of a fresh StaticFunction with an empty cache
    (trace + lower + XLA compile + publish). Warm = first call of another
    fresh StaticFunction over the SAME program with the populated cache
    (deserialize the executable — the path a warmed serving replica's
    first request takes). vs_baseline is the cold/warm speedup.
    """
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit.api import to_static

    tmp = tempfile.mkdtemp(prefix="pcc_bench_")
    paddle.set_flags({"FLAGS_compile_cache": True,
                      "FLAGS_compile_cache_dir": tmp})
    try:
        d = 256 if small else 1024
        paddle.seed(0)

        class _Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.a = nn.Linear(d, d)
                self.b = nn.Linear(d, d)

            def forward(self, x):
                return paddle.ops.tanh(self.b(paddle.ops.tanh(self.a(x))))

        net = _Net()
        x = paddle.to_tensor(np.random.randn(8, d).astype(np.float32))

        def first_call_seconds():
            sf = to_static(net.forward, full_graph=True)
            t0 = time.perf_counter()
            out = sf(x)
            jax.block_until_ready(out._data)
            return time.perf_counter() - t0

        cold = first_call_seconds()   # miss: trace+lower+compile+publish
        warm = first_call_seconds()   # hit: deserialize the executable
    finally:
        paddle.set_flags({"FLAGS_compile_cache": False,
                          "FLAGS_compile_cache_dir": ""})
        shutil.rmtree(tmp, ignore_errors=True)
    speedup = cold / max(warm, 1e-9)
    return {
        "metric": "compile_cache_warm_speedup",
        "value": round(speedup, 3),
        "unit": "x_cold_start",
        "vs_baseline": round(speedup, 3),
        "extra": {"cold_start_s": round(cold, 4),
                  "warm_start_s": round(warm, 4),
                  "hidden": d, "host": jax.default_backend()},
    }


def _bench_serving(small):
    """Continuous-batching serving throughput (BENCH_MODEL=serving).

    Measures aggregate decode tokens/s of the paged-KV engine over a
    mixed-length request burst, against the SAME model decoding the same
    requests one at a time (single stream) — so vs_baseline is the
    continuous-batching speedup on this chip, an apples-to-apples ratio
    that needs no external reference number. bf16 weights/KV.
    """
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LlamaPagedEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if small:
        cfg = LlamaConfig(vocab_size=97, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          max_seq_len=256, use_flash_attention=False)
        n_req, new_tokens, max_batch = 4, 8, 2
        prompt_lens = (5, 9, 3, 7)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_layers=16,
                          num_heads=16, max_seq_len=1024,
                          use_flash_attention=False)
        n_req = _env_int("BENCH_REQUESTS", 24)
        new_tokens = _env_int("BENCH_NEW_TOKENS", 96)
        max_batch = _env_int("BENCH_BATCH", 8)
        rng = np.random.RandomState(7)
        prompt_lens = rng.randint(32, 192, size=n_req)
    model = LlamaForCausalLM(cfg)
    if not small:
        for p in model.parameters():  # bf16 weights: serving discipline
            if np.dtype(p._data.dtype) == np.float32:
                p._swap_payload(p._data.astype(jnp.bfloat16))
    rng = np.random.RandomState(11)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, size=int(n))]
               for n in prompt_lens]

    def engine(batch):
        return LlamaPagedEngine(
            model, max_batch=batch, block_size=32,
            num_blocks=max(64, (max(len(p) for p in prompts)
                                + new_tokens) // 32 * batch * 2),
            max_blocks_per_seq=64)

    # ONE engine per mode, reused across requests — fresh engines would
    # re-jit their closures and the timings would measure compilation
    eng = engine(max_batch)
    e1 = engine(1)

    # warmup: compile prefill+decode programs for both engines
    for e in (eng, e1):
        e.add_request(prompts[0], max_new_tokens=4)
        e.run_to_completion()

    # continuous batching: one burst, all requests queued up front
    t0 = time.perf_counter()
    rids = [eng.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    out = eng.run_to_completion()
    dt_batched = time.perf_counter() - t0
    total_new = sum(len(out[r]) for r in rids)

    # single stream: same requests through the single-slot engine, one
    # at a time (no batching, no recompiles)
    t0 = time.perf_counter()
    single_total = 0
    for p in prompts:
        rid = e1.add_request(p, max_new_tokens=new_tokens)
        single_total += len(e1.run_to_completion()[rid])
    dt_single = time.perf_counter() - t0

    batched_tps = total_new / dt_batched
    single_tps = single_total / dt_single
    return {
        "metric": "llama_serving_decode_tokens_per_sec_per_chip",
        "value": round(batched_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(batched_tps / max(single_tps, 1e-9), 4),
        "extra": {"requests": int(n_req), "new_tokens": int(new_tokens),
                  "max_batch": int(max_batch),
                  "single_stream_tokens_per_sec": round(single_tps, 1),
                  "batched_wall_s": round(dt_batched, 3),
                  "single_wall_s": round(dt_single, 3)},
    }


def _bench_serving_resilience(small):
    """Serving-resilience rung (BENCH_MODEL=serving_resilience).

    Open-loop Poisson goodput-vs-offered-load curve through the paged
    engine with admission control + deadlines armed: a capacity probe
    (saturating arrivals, no deadlines) sizes the ladder, then 0.5x /
    1x / 2x capacity points run with SLO deadlines and a queue
    high-water mark, recording p50/p99 TTFT, inter-token latency,
    goodput, and shed/deadline-miss counts per point. vs_baseline is
    goodput retention under 2x overload (goodput@2x / goodput@1x) — a
    replica that collapses under overload scores near 0, one that sheds
    cleanly holds ~1.
    """
    import paddle_tpu as paddle
    from paddle_tpu.inference import PagedEngine, ResilienceConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from tools.loadgen import run_load

    paddle.seed(7)
    if small:
        cfg = LlamaConfig(vocab_size=97, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          max_seq_len=256, use_flash_attention=False)
        n_req, new_tokens, max_batch = 16, 6, 4
        prompt_range = (4, 16)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_layers=16,
                          num_heads=16, max_seq_len=1024,
                          use_flash_attention=False)
        n_req = _env_int("BENCH_REQUESTS", 48)
        new_tokens = _env_int("BENCH_NEW_TOKENS", 64)
        max_batch = _env_int("BENCH_BATCH", 8)
        prompt_range = (32, 160)
    model = LlamaForCausalLM(cfg)
    if not small:
        for p in model.parameters():  # bf16 weights: serving discipline
            if np.dtype(p._data.dtype) == np.float32:
                p._swap_payload(p._data.astype(jnp.bfloat16))
    blocks_needed = (prompt_range[1] + new_tokens + 31) // 32
    eng = PagedEngine(
        model, max_batch=max_batch, block_size=32,
        num_blocks=max(64, blocks_needed * max_batch * 2),
        max_blocks_per_seq=max(blocks_needed + 1, 8),
        resilience=ResilienceConfig(max_queue=4 * n_req,
                                    queue_high_water=4 * max_batch))
    eng.warmup(prompt_len=prompt_range[1] // 2,
               max_new_tokens=new_tokens)

    common = dict(n_requests=n_req, vocab_size=cfg.vocab_size,
                  prompt_len_range=prompt_range,
                  max_new_tokens=new_tokens, seed=13)
    # capacity probe: saturating arrivals, no deadlines — how fast can
    # this replica actually drain the stream
    probe = run_load(eng, offered_rps=10_000.0, **common)
    cap_rps = max(probe["goodput_requests_per_sec"], 1e-3)
    # SLO knobs sized from the probe so the ladder is chip-relative:
    # generous at 1x, binding under 2x overload queue delay
    ttft_dl = max((probe["p99_ttft_s"] or 0.01) * 8, 1e-3)
    total_dl = ttft_dl + 4 * new_tokens * (probe["p99_itl_s"] or 0.01)
    curve = []
    for mult in (0.5, 1.0, 2.0):
        pt = run_load(eng, offered_rps=mult * cap_rps,
                      ttft_deadline_s=ttft_dl, deadline_s=total_dl,
                      **common)
        pt["load_multiplier"] = mult
        curve.append(pt)
    eng.drain()
    health = eng.health()
    at_1x = curve[1]["goodput_tokens_per_sec"]
    at_2x = curve[2]["goodput_tokens_per_sec"]
    return {
        "metric": "serving_resilience_goodput_tokens_per_sec",
        "value": round(at_1x, 2),
        "unit": "tokens/s",
        # overload retention: sheds/misses must bound latency without
        # collapsing useful throughput (zero 1x goodput scores 0, not inf)
        "vs_baseline": round(at_2x / at_1x, 4) if at_1x > 0 else 0.0,
        "extra": {
            "capacity_requests_per_sec": round(cap_rps, 3),
            "ttft_deadline_s": round(ttft_dl, 5),
            "total_deadline_s": round(total_dl, 5),
            "goodput_vs_offered_load": curve,
            "final_replica_state": health["state"],
            "kv_blocks_leaked": (health["kv_blocks_total"]
                                 - health["kv_blocks_free"]),
        },
    }


def _bench_serving_router(small):
    """Multi-replica serving-tier rung (BENCH_MODEL=serving_router;
    paddle_tpu/serving/).

    Three questions, one rung:

    1. **Goodput scaling vs R** — the open-loop Poisson stream through
       the Router at saturating arrivals for R=1 and R=2 replicas (the
       replicas share one model, so compiled tick programs are shared).
       vs_baseline is goodput(R=2)/goodput(R=1): ~linear (≈2) on real
       chips where each replica owns a device; ≈1 on the CPU smoke host
       where all replicas share one core's compute — the frozen CPU
       value is a no-regression floor, the TPU ladder refreezes per
       PERF.md §7.
    2. **2x-overload SLO curve at R=2** — deadlines sized from the
       capacity probe, 0.5x/1x/2x offered load; overload must shed AT
       THE ROUTER (``shed_at_router``), never inside a replica
       (replicas run without a high-water mark), with p99 TTFT held.
    3. **int8-KV / speculative parity + efficiency** — greedy tokens
       from a ``kv_dtype="int8"`` engine and a ``speculate="ngram"``
       engine must equal the baseline engine's exactly; records the
       KV-bytes-per-token shrink (resident-batch multiplier) and the
       draft acceptance rate.
    """
    import paddle_tpu as paddle
    from paddle_tpu.inference import PagedEngine, ResilienceConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Router, SchedulerConfig
    from tools.loadgen import run_load

    paddle.seed(7)
    if small:
        cfg = LlamaConfig(vocab_size=97, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          max_seq_len=256, use_flash_attention=False)
        n_req, new_tokens, max_batch = 16, 6, 4
        prompt_range = (4, 16)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_layers=16,
                          num_heads=16, max_seq_len=1024,
                          use_flash_attention=False)
        n_req = _env_int("BENCH_REQUESTS", 48)
        new_tokens = _env_int("BENCH_NEW_TOKENS", 64)
        max_batch = _env_int("BENCH_BATCH", 8)
        prompt_range = (32, 160)
    model = LlamaForCausalLM(cfg)
    if not small:
        for p in model.parameters():  # bf16 weights: serving discipline
            if np.dtype(p._data.dtype) == np.float32:
                p._swap_payload(p._data.astype(jnp.bfloat16))
    blocks_needed = (prompt_range[1] + new_tokens + 31) // 32

    def mk_replica(max_queue):
        # phase-split on (one chunk batch worth of prefill per tick) and
        # NO replica-side high-water mark: the router owns shedding
        return PagedEngine(
            model, max_batch=max_batch, block_size=32,
            num_blocks=max(64, blocks_needed * max_batch * 2),
            max_blocks_per_seq=max(blocks_needed + 1, 8),
            scheduler=SchedulerConfig(prefill_token_budget=32 * max_batch),
            resilience=ResilienceConfig(max_queue=max_queue,
                                        queue_high_water=None))

    common = dict(n_requests=n_req, vocab_size=cfg.vocab_size,
                  prompt_len_range=prompt_range,
                  max_new_tokens=new_tokens, seed=13)
    # --- goodput scaling vs R (saturating arrivals, no deadlines) ---
    goodput_vs_r = {}
    for r in (1, 2):
        # deep queues for the capacity probe: it measures drain rate.
        # 2x the request count here — the scaling ratio is the frozen
        # headline and short probes are noisy on the CPU smoke host
        tier = Router([mk_replica(8 * n_req) for _ in range(r)]).warmup()
        pt = run_load(tier, offered_rps=10_000.0,
                      **dict(common, n_requests=2 * n_req))
        tier.drain()
        goodput_vs_r[r] = pt
    g1 = goodput_vs_r[1]["goodput_tokens_per_sec"]
    g2 = goodput_vs_r[2]["goodput_tokens_per_sec"]
    scaling = (g2 / g1) if g1 > 0 else 0.0
    cap_rps = max(goodput_vs_r[2]["goodput_requests_per_sec"], 1e-3)
    ttft_dl = max((goodput_vs_r[2]["p99_ttft_s"] or 0.01) * 8, 1e-3)
    total_dl = ttft_dl + 4 * new_tokens * (
        goodput_vs_r[2]["p99_itl_s"] or 0.01)

    # --- 2x-overload SLO curve at R=2, shedding at the router ---
    curve = []
    replica_side_shed = 0
    # the final point is an instantaneous burst of 4x the request count:
    # arrivals the tier can NEVER absorb must shed at the router (bounded
    # replica queues bounce them back), not pile into replica queues
    points = [(0.5, n_req), (1.0, n_req), (2.0, n_req),
              ("burst", 4 * n_req)]
    for mult, n in points:
        # bounded queues for the SLO curve: past-capacity arrivals must
        # bounce off replica admission and shed at the router
        tier = Router([mk_replica(max(max_batch, 4))
                       for _ in range(2)]).warmup()
        rate = 10_000.0 if mult == "burst" else mult * cap_rps
        pt = run_load(tier, offered_rps=rate,
                      ttft_deadline_s=ttft_dl, deadline_s=total_dl,
                      **dict(common, n_requests=n))
        tier.drain()
        pt["load_multiplier"] = mult
        pt["shed_at_router"] = pt["router"]["shed_at_router"]
        # replica-internal sheds must stay 0 — overload policy lives at
        # the router (replicas have no high-water mark; their bounded
        # queues surface as router retries, not drops)
        replica_side_shed += pt["shed"] - pt["shed_at_router"]
        curve.append(pt)
    at_1x = curve[1]["goodput_tokens_per_sec"]
    at_2x = curve[2]["goodput_tokens_per_sec"]

    # --- int8-KV + speculative parity against the baseline engine ---
    rng = np.random.RandomState(5)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, size=n)]
               for n in rng.randint(prompt_range[0], prompt_range[1],
                                    size=4)]

    def greedy_tokens(**kw):
        eng = PagedEngine(model, max_batch=max_batch, block_size=32,
                          num_blocks=max(64, blocks_needed * max_batch * 2),
                          max_blocks_per_seq=max(blocks_needed + 1, 8),
                          **kw)
        rids = [eng.add_request(p, max_new_tokens=new_tokens)
                for p in prompts]
        out = eng.run_to_completion()
        return [out[rid] for rid in rids], eng

    base_toks, base_eng = greedy_tokens()
    int8_toks, int8_eng = greedy_tokens(kv_dtype="int8")
    spec_toks, spec_eng = greedy_tokens(speculate="ngram", speculate_k=4)

    return {
        "metric": "serving_router_goodput_scaling",
        "value": round(scaling, 4),
        "unit": "x_R1",
        # overload retention through the ROUTER's shedding (same shape
        # as the serving_resilience rung, now tier-level)
        "vs_baseline": round(at_2x / at_1x, 4) if at_1x > 0 else 0.0,
        "extra": {
            "goodput_tokens_per_sec_R1": round(g1, 2),
            "goodput_tokens_per_sec_R2": round(g2, 2),
            "capacity_requests_per_sec_R2": round(cap_rps, 3),
            "ttft_deadline_s": round(ttft_dl, 5),
            "total_deadline_s": round(total_dl, 5),
            "goodput_vs_offered_load_R2": curve,
            "shed_at_router_total": sum(
                pt["shed_at_router"] for pt in curve),
            "replica_side_shed_total": replica_side_shed,
            "int8_kv_parity": int8_toks == base_toks,
            "int8_kv_bytes_per_token": int8_eng.kv_bytes_per_token,
            "base_kv_bytes_per_token": base_eng.kv_bytes_per_token,
            "resident_batch_multiplier": round(
                base_eng.kv_bytes_per_token
                / int8_eng.kv_bytes_per_token, 3),
            "speculative_parity": spec_toks == base_toks,
            "spec_acceptance_rate": round(
                spec_eng.spec_accepted / spec_eng.spec_proposed, 4)
            if spec_eng.spec_proposed else None,
        },
    }


def _bench_serving_reqtrace(small):
    """Request-trace overhead rung (BENCH_MODEL=serving_reqtrace;
    paddle_tpu/observability/reqtrace.py). The SAME steady-state decode
    tick — a full batch of long-running requests, so every tick records
    one decode_tick event per slot plus the per-token exemplar/TTFT
    bookkeeping — timed with ``FLAGS_reqtrace`` fully OFF vs fully ON.
    value = off/on tick-time ratio (1.0 = free); the acceptance bar is
    overhead < 2%. Paired per-tick A/B with alternating order (the
    round-14 fleet_observability estimator: median over ALL signed pair
    diffs, so host drift cancels inside pairs and slot-position bias
    across them)."""
    import paddle_tpu as paddle
    from paddle_tpu.core import flags
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import reqtrace

    paddle.seed(7)
    if small:
        cfg = LlamaConfig(vocab_size=97, hidden_size=64,
                          intermediate_size=128, num_layers=2,
                          num_heads=4, max_seq_len=4096,
                          use_flash_attention=False)
        pairs, max_batch = 300, 4
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_layers=16,
                          num_heads=16, max_seq_len=4096,
                          use_flash_attention=False)
        pairs, max_batch = _env_int("BENCH_REQTRACE_PAIRS", 150), 8
    model = LlamaForCausalLM(cfg)
    warm = 20
    ticks_needed = warm + 2 * pairs + 16
    prompt_len = 8
    bs = 16
    bps = -(-(prompt_len + ticks_needed + bs) // bs) + 1
    eng = PagedEngine(model, max_batch=max_batch, block_size=bs,
                      num_blocks=max_batch * bps + 2,
                      max_blocks_per_seq=bps)
    rng = np.random.RandomState(3)
    for _ in range(max_batch):
        eng.add_request(
            [int(t) for t in rng.randint(1, cfg.vocab_size,
                                         size=prompt_len)],
            max_new_tokens=ticks_needed)

    prev = flags.get_flag("reqtrace")
    t_off, diffs = [], []

    def one_tick():
        t0 = time.perf_counter()
        eng.step()
        return time.perf_counter() - t0

    try:
        flags.set_flags({"reqtrace": True})
        for _ in range(warm):          # compiles + steady decode shape
            eng.step()
        for i in range(pairs):
            if i % 2 == 0:
                flags.set_flags({"reqtrace": False})
                d_off = one_tick()
                flags.set_flags({"reqtrace": True})
                d_on = one_tick()
            else:
                flags.set_flags({"reqtrace": True})
                d_on = one_tick()
                flags.set_flags({"reqtrace": False})
                d_off = one_tick()
            t_off.append(d_off)
            diffs.append(d_on - d_off)
        recorded = sum(len(tl["events"]) for tl in
                       reqtrace.RECORDER.live_timelines())
    finally:
        flags.set_flags({"reqtrace": prev})
        eng.drain()
        # the measurement's torn half-traced timelines and exemplars
        # must not pollute the process stores a later rung might inspect
        reqtrace.RECORDER.clear()
        reqtrace.EXEMPLARS.clear()
    off = float(np.median(t_off))
    on = off + float(np.median(diffs))
    ratio = off / max(on, 1e-12)
    overhead_pct = (on / max(off, 1e-12) - 1.0) * 100.0
    return {
        "metric": "serving_reqtrace_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "x_untraced",
        "vs_baseline": round(ratio, 4),
        "extra": {"overhead_pct": round(overhead_pct, 3),
                  "tick_off_us": round(off * 1e6, 1),
                  "tick_on_us": round(on * 1e6, 1),
                  "ticks_per_config": pairs,
                  "batch": max_batch,
                  "events_recorded": recorded,
                  "within_budget": bool(overhead_pct < 2.0)},
    }


def _bench_verifier_overhead(small):
    """Program-verifier overhead rung (BENCH_MODEL=verifier_overhead;
    paddle_tpu/static/verifier.py). The verifier runs ONCE per new
    compile signature, so its budget is a fraction of trace+lower —
    not of the step. Measures (a) trace+lower wall of the GPT ladder
    block's recorded program (fresh jax.jit + .lower per rep, verifier
    off) and (b) the full verifier pass stack over the same recorded
    op list; value = trace_lower / (trace_lower + verify) (1.0 = free),
    acceptance bar: verify < 2% of trace+lower."""
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.core import flags
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import functional as F
    from paddle_tpu.static import verifier
    import paddle_tpu.ops as pops

    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=16, use_flash_attention=False))

    def record_once():
        """One program capture of the GPT block + loss (pays the
        recorder — and, in warn mode, the per-op provenance walk)."""
        prog = static.Program()
        with static.program_guard(prog):
            ids = static.data("ids", [2, 8], "int64")
            logits = model(ids)
            if isinstance(logits, (tuple, list)):
                logits = logits[0]
            v = logits.shape[-1]
            loss = F.cross_entropy(
                pops.reshape(logits[:, :-1, :], [-1, v]),
                pops.reshape(ids[:, 1:], [-1])).mean()
        return prog, [id(loss)]

    prev = flags.get_flag("verify_programs")
    reps = 5 if small else _env_int("BENCH_VERIFIER_REPS", 10)
    try:
        # per-op recording cost of the default-on warn mode: the
        # dispatch recorder pays mode() + the bounded user_loc stack
        # walk per op — measured as record-on minus record-off
        t_rec = {}
        for mode_ in ("off", "warn"):
            flags.set_flags({"verify_programs": mode_})
            samples = []
            for _ in range(reps):
                t0 = time.perf_counter()
                prog, fetch_ids = record_once()
                samples.append(time.perf_counter() - t0)
            t_rec[mode_] = float(np.median(samples))

        flags.set_flags({"verify_programs": "off"})
        prog, fetch_ids = record_once()     # loc-free timing substrate
        names = sorted(prog.feed_vars)
        feed_ids = [prog.feed_vars[n] for n in names]
        cap_ids = list(prog._captured.keys())
        cap_arrays = [t._data for t in prog._captured.values()]
        feeds = [jnp.zeros(tuple(abs(s) for s in prog._feed_shapes[n]),
                           dtype=np.dtype(prog._feed_dtypes[n]))
                 for n in names]

        t_tl = []
        for _ in range(reps):
            def replay(feed_arrays, caps):
                env = prog._replay_by_ids(feed_ids, feed_arrays,
                                          cap_ids, caps)
                return [env[i] for i in fetch_ids]

            t0 = time.perf_counter()
            jax.jit(replay).lower(feeds, cap_arrays)
            t_tl.append(time.perf_counter() - t0)

        t_v = []
        report = None
        for _ in range(reps * 4):
            t0 = time.perf_counter()
            report = verifier.check(prog, fetch_ids=fetch_ids)
            t_v.append(time.perf_counter() - t0)
        assert report is not None and not report.findings, \
            "ladder program must verify clean"
    finally:
        flags.set_flags({"verify_programs": prev})
    trace_lower = float(np.median(t_tl))
    verify = float(np.median(t_v))
    record = max(0.0, t_rec["warn"] - t_rec["off"])
    overhead = verify + record
    ratio = trace_lower / max(trace_lower + overhead, 1e-12)
    overhead_pct = overhead / max(trace_lower, 1e-12) * 100.0
    return {
        "metric": "verifier_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "x_unverified_compile",
        "vs_baseline": round(ratio, 4),
        "extra": {"overhead_pct": round(overhead_pct, 3),
                  "trace_lower_ms": round(trace_lower * 1e3, 2),
                  "verify_ms": round(verify * 1e3, 3),
                  "record_overhead_ms": round(record * 1e3, 3),
                  "ops": len(prog.global_block().ops),
                  "within_budget": bool(overhead_pct < 2.0)},
    }


def _bench_static_analysis(small):
    """Static memory-analyzer rung (BENCH_MODEL=static_analysis;
    paddle_tpu/static/liveness.py). Like the verifier rung, the
    analyzer runs ONCE per new compile signature, so its budget is a
    fraction of trace+lower. Measures (a) trace+lower wall of the GPT
    ladder block's recorded program (fresh jax.jit + .lower per rep)
    and (b) the full round-22 static stack over the same op list —
    liveness intervals + peak curve (peak_report), the TPU75x alias
    pass, and the TPU9xx capacity pass; value =
    trace_lower / (trace_lower + analysis) (1.0 = free), acceptance
    bar: analysis < 2% of trace+lower."""
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.core import flags
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import functional as F
    from paddle_tpu.static import liveness, verifier
    import paddle_tpu.ops as pops

    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=16, use_flash_attention=False))

    prev = flags.get_flag("verify_programs")
    reps = 5 if small else _env_int("BENCH_STATIC_ANALYSIS_REPS", 10)
    try:
        flags.set_flags({"verify_programs": "off"})
        prog = static.Program()
        with static.program_guard(prog):
            ids = static.data("ids", [2, 8], "int64")
            logits = model(ids)
            if isinstance(logits, (tuple, list)):
                logits = logits[0]
            v = logits.shape[-1]
            loss = F.cross_entropy(
                pops.reshape(logits[:, :-1, :], [-1, v]),
                pops.reshape(ids[:, 1:], [-1])).mean()
        fetch_ids = [id(loss)]
        names = sorted(prog.feed_vars)
        feed_ids = [prog.feed_vars[n] for n in names]
        cap_ids = list(prog._captured.keys())
        cap_arrays = [t._data for t in prog._captured.values()]
        feeds = [jnp.zeros(tuple(abs(s) for s in prog._feed_shapes[n]),
                           dtype=np.dtype(prog._feed_dtypes[n]))
                 for n in names]

        t_tl = []
        for _ in range(reps):
            def replay(feed_arrays, caps):
                env = prog._replay_by_ids(feed_ids, feed_arrays,
                                          cap_ids, caps)
                return [env[i] for i in fetch_ids]

            t0 = time.perf_counter()
            jax.jit(replay).lower(feeds, cap_arrays)
            t_tl.append(time.perf_counter() - t0)

        t_a = []
        rep_out = None
        peak = None
        for _ in range(reps * 4):
            t0 = time.perf_counter()
            rep_out = verifier.Report(label="bench_static")
            liveness.alias_pass(prog, rep_out, fetch_ids=fetch_ids)
            liveness.memory_pass(prog, rep_out, fetch_ids=fetch_ids)
            peak = liveness.peak_report(prog, fetch_ids=fetch_ids)
            t_a.append(time.perf_counter() - t0)
        assert rep_out is not None and not rep_out.findings, \
            "ladder program must analyze clean"
        assert peak is not None and peak["peak_bytes"] > 0
    finally:
        flags.set_flags({"verify_programs": prev})
    trace_lower = float(np.median(t_tl))
    analysis = float(np.median(t_a))
    ratio = trace_lower / max(trace_lower + analysis, 1e-12)
    overhead_pct = analysis / max(trace_lower, 1e-12) * 100.0
    return {
        "metric": "static_analysis_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "x_unanalyzed_compile",
        "vs_baseline": round(ratio, 4),
        "extra": {"overhead_pct": round(overhead_pct, 3),
                  "trace_lower_ms": round(trace_lower * 1e3, 2),
                  "analysis_ms": round(analysis * 1e3, 3),
                  "static_peak_bytes": peak["peak_bytes"],
                  "peak_op": peak["peak_op"]["name"],
                  "ops": len(prog.global_block().ops),
                  "within_budget": bool(overhead_pct < 2.0)},
    }


def _bench_spmd_auto(small):
    """SPMD auto-sharding rung (BENCH_MODEL=spmd_auto;
    paddle_tpu/distributed/spmd/). The SAME weights run one GPT
    fwd+bwd step two ways on the same (data, tp) mesh: (a) the
    hand-built fleet TP layers (ColumnParallel/RowParallel +
    VocabParallelEmbedding), (b) the plain model auto-sharded by the
    propagation subsystem. Records loss parity, both step times, their
    ratio (vs_baseline: >= 1 means auto is at least as fast as the
    hand-built path), fallback count (must be 0), and the round-12
    per-step attribution of the auto step."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fleet_pkg
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import mesh as mesh_mod, spmd
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    n_dev = jax.device_count()
    tp = 2 if n_dev >= 2 else 1
    data = max(n_dev // tp, 1)
    if small:
        cfg_kw = dict(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, max_seq_len=128,
                      use_flash_attention=False)
        batch, seq, iters = 4, 128, 3
    else:
        cfg_kw = dict(hidden_size=1024, num_layers=24, num_heads=16,
                      max_seq_len=1024)
        batch, seq, iters = _env_int("BENCH_BATCH", 8), 1024, 5
    rng = np.random.RandomState(0)
    ids = rng.randint(0, GPTConfig(**cfg_kw).vocab_size,
                      (batch, seq)).astype(np.int64)

    def step_fn_for(model, mesh=None):
        params = [p for p in model.parameters() if not p.stop_gradient]

        def f(pa, ids_a):
            originals = [p._data for p in params]
            for p, a in zip(params, pa):
                p._data = a
            try:
                if mesh is None:
                    t = paddle.Tensor(ids_a)
                    _, loss = model(t, labels=t)
                    return loss._data
                sc = spmd.trace_scope(mesh)
                with sc:
                    for p in params:
                        spec = spmd.param_spec_of(p)
                        if spec is not None:
                            sc.seed(p, spec)
                    t = paddle.Tensor(ids_a)
                    sc.seed(t, P("data"))
                    _, loss = model(t, labels=t)
                stats["scope"] = dict(sc.stats)
                return loss._data
            finally:
                for p, o in zip(params, originals):
                    p._data = o

        stats = {}
        grad_f = jax.jit(jax.value_and_grad(f))
        pa = [p._data for p in params]
        return grad_f, pa, stats

    def timed(grad_f, pa):
        loss, grads = grad_f(pa, ids)       # compile + warm
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, grads = grad_f(pa, ids)
        jax.block_until_ready(grads)
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / iters, float(loss)

    prev_mesh = mesh_mod._global_mesh
    try:
        # (a) hand-built fleet TP path
        strategy = fleet_pkg.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": data, "mp_degree": tp}
        fleet_pkg.fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(1234)
        tp_model = GPTForCausalLM(GPTConfig(mp_degree=tp, **cfg_kw))
        state = {k: np.asarray(v.numpy())
                 for k, v in tp_model.state_dict().items()}
        fleet_f, fleet_pa, _ = step_fn_for(tp_model)
        fleet_dt, fleet_loss = timed(fleet_f, fleet_pa)

        # (b) plain model auto-sharded over the same mesh, SAME weights
        mesh_mod._global_mesh = None
        mesh = mesh_mod.build_mesh({"data": data, "tp": tp})
        mesh_mod.set_mesh(mesh)
        paddle.seed(1234)
        auto_model = GPTForCausalLM(GPTConfig(**cfg_kw))
        auto_model.set_state_dict(state)
        spmd.shard_params(auto_model, mesh, [
            (r".*qkv_proj\.weight", P(None, "tp")),
            (r".*qkv_proj\.bias", P("tp")),
            (r".*fc1\.weight", P(None, "tp")),
            (r".*fc1\.bias", P("tp")),
            (r".*(out_proj|fc2)\.weight", P("tp", None)),
            (r".*wte\.weight", P("tp", None)),
        ])
        auto_f, auto_pa, auto_stats = step_fn_for(auto_model, mesh=mesh)
        auto_dt, auto_loss = timed(auto_f, auto_pa)

        # per-step device attribution of the auto path (round-12 layer)
        attribution = None
        try:
            from paddle_tpu.observability import perf as _perf
            att = _perf.step_attribution(
                lambda: jax.block_until_ready(
                    auto_f(auto_pa, ids)[0]),
                iters=2, warmup=0, name="spmd_auto_step")["total"]
            attribution = {k: round(att[k], 4) for k in
                           ("compute_frac", "collective_frac",
                            "host_frac", "idle_frac")}
        except Exception:
            pass
    finally:
        mesh_mod._global_mesh = prev_mesh

    scope = auto_stats.get("scope", {})
    parity = abs(auto_loss - fleet_loss) <= 1e-3 * max(
        abs(fleet_loss), 1.0)
    return {
        "metric": "spmd_auto_vs_fleet_tp_step_ratio",
        "value": round(fleet_dt / max(auto_dt, 1e-9), 4),
        "unit": "x_fleet_tp",
        # parity is the gate: a fast-but-wrong program scores 0
        "vs_baseline": round(fleet_dt / max(auto_dt, 1e-9), 4)
        if parity else 0.0,
        "extra": {"mesh": {"data": data, "tp": tp},
                  "auto_step_s": round(auto_dt, 4),
                  "fleet_tp_step_s": round(fleet_dt, 4),
                  "loss_auto": round(auto_loss, 5),
                  "loss_fleet_tp": round(fleet_loss, 5),
                  "loss_parity": bool(parity),
                  "fallback_ops": scope.get("fallback", {}),
                  "ops_annotated": scope.get("annotated"),
                  "attribution": attribution},
    }


def _bench_embedding(small):
    """Giant-embedding rung (BENCH_MODEL=embedding;
    paddle_tpu/distributed/embedding/ + models/dlrm.py). The SAME DLRM
    weights run one fwd+bwd step two ways: (a) table replicated (the
    baseline — only possible at smoke scale), (b) table row-sharded
    over the (data, fsdp) mesh with dedup-before-exchange lookups.
    Three gates ride the score:

    * loss parity (rtol 1e-3) between the sharded and replicated step,
    * the static capacity proof: on the virtual 8-chip pod mesh the
      liveness analyzer shows the replicated program over a synthetic
      per-chip HBM budget while the row-sharded placement (zero
      replicate-fallbacks on the embedding path) fits under it,
    * the dedup win: modeled exchange bytes for the deduped rows <
      naive per-id gather bytes on a zipf id batch (the live
      paddle_tpu_embedding_unique_ratio gauge rides in extra).

    Value = replicated/sharded step-time ratio — a no-regression floor
    at smoke scale (dedup costs a sort); on a real pod the replicated
    baseline cannot even materialize the table, which is the point."""
    import types

    import paddle_tpu as paddle
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import static
    from paddle_tpu.distributed import embedding as emb
    from paddle_tpu.distributed import mesh as mesh_mod, spmd
    from paddle_tpu.distributed.spmd.propagate import propagate_program
    from paddle_tpu.models import DLRM, DLRMConfig
    from paddle_tpu.observability import metrics as _metrics
    from paddle_tpu.static import liveness

    n_dev = jax.device_count()
    data = 2 if n_dev >= 4 else 1
    fsdp = max(n_dev // data, 1)
    if small:
        cfg_kw = dict(num_embeddings=65536, embedding_dim=64,
                      n_dense=8, n_sparse=8, bag_size=4,
                      bottom_mlp=(32,), top_mlp=(64,))
        batch, iters = 64, 3
    else:
        cfg_kw = dict(num_embeddings=4_000_000, embedding_dim=128,
                      n_dense=13, n_sparse=26, bag_size=8,
                      bottom_mlp=(512, 256), top_mlp=(512, 256))
        batch, iters = _env_int("BENCH_BATCH", 1024), 5
    cfg = DLRMConfig(**cfg_kw)
    F_, L = cfg.n_sparse, cfg.bag_size
    rng = np.random.RandomState(0)
    dense_np = rng.randn(batch, cfg.n_dense).astype(np.float32)
    # zipf ids: the recsys regime dedup exists for — a few hot rows
    # dominate, so uniques << total lookups
    ids_np = (rng.zipf(1.5, (batch, F_, L)) - 1) % cfg.num_embeddings
    ids_np = ids_np.astype(np.int64)
    labels_np = rng.randint(0, 2, (batch,)).astype(np.float32)

    def step_fn_for(model, mesh=None):
        params = [p for p in model.parameters() if not p.stop_gradient]

        def f(pa, dense_a, ids_a, labels_a):
            originals = [p._data for p in params]
            for p, a in zip(params, pa):
                p._data = a
            try:
                if mesh is None:
                    return model.loss(paddle.Tensor(dense_a),
                                      paddle.Tensor(ids_a),
                                      paddle.Tensor(labels_a))._data
                sc = spmd.trace_scope(mesh)
                with sc:
                    for p in params:
                        spec = spmd.param_spec_of(p)
                        if spec is not None:
                            sc.seed(p, spec)
                    d = paddle.Tensor(dense_a)
                    i = paddle.Tensor(ids_a)
                    y = paddle.Tensor(labels_a)
                    sc.seed(d, P("data"))
                    sc.seed(i, P("data"))
                    sc.seed(y, P("data"))
                    loss = model.loss(d, i, y)
                stats["scope"] = dict(sc.stats)
                return loss._data
            finally:
                for p, o in zip(params, originals):
                    p._data = o

        stats = {}
        grad_f = jax.jit(jax.value_and_grad(f))
        pa = [p._data for p in params]
        return grad_f, pa, stats

    def timed(grad_f, pa):
        loss, grads = grad_f(pa, dense_np, ids_np, labels_np)
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, grads = grad_f(pa, dense_np, ids_np, labels_np)
        jax.block_until_ready(grads)
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / iters, float(loss)

    prev_mesh = mesh_mod._global_mesh
    prev_metrics = paddle.get_flags(["FLAGS_enable_metrics"])[
        "FLAGS_enable_metrics"]
    try:
        # (a) replicated baseline: same weights, table on every chip
        paddle.seed(1234)
        repl_model = DLRM(cfg)
        state = {k: np.asarray(v.numpy())
                 for k, v in repl_model.state_dict().items()}
        repl_f, repl_pa, _ = step_fn_for(repl_model)
        repl_dt, repl_loss = timed(repl_f, repl_pa)

        # (b) table row-sharded over (data, fsdp), dedup lookups
        mesh_mod._global_mesh = None
        mesh = mesh_mod.build_mesh({"data": data, "fsdp": fsdp})
        mesh_mod.set_mesh(mesh)
        paddle.seed(1234)
        shard_model = DLRM(cfg, mesh=mesh)
        shard_model.set_state_dict(state)
        shard_model.shard_(mesh)      # re-pin: set_state_dict swaps payloads
        paddle.set_flags({"FLAGS_enable_metrics": True})
        # one eager lookup feeds the dedup gauges (the jitted step's
        # tracer skips host-side metric reads by design)
        shard_model.embedding.bag(paddle.Tensor(ids_np))
        ureg = _metrics.REGISTRY.get("paddle_tpu_embedding_unique_ratio")
        unique_ratio_gauge = ureg.value() if ureg is not None else None
        shard_f, shard_pa, shard_stats = step_fn_for(shard_model,
                                                     mesh=mesh)
        shard_dt, shard_loss = timed(shard_f, shard_pa)
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": prev_metrics})
        mesh_mod._global_mesh = prev_mesh

    # ---- static capacity proof on the virtual pod mesh -------------
    # The proof is device-independent: propagation + liveness only read
    # axis SIZES, so the 8-chip (data=2, fsdp=4) pod is analyzed even
    # when the smoke host has one device.
    pod = types.SimpleNamespace(shape={"data": 2, "fsdp": 4})
    table_param = shard_model.embedding.weight
    prog = static.Program()
    with static.program_guard(prog):
        d_s = static.data("dense", [batch, cfg.n_dense], "float32")
        i_s = static.data("ids", [batch, F_, L], "int64")
        y_s = static.data("labels", [batch], "float32")
        out = shard_model.loss(d_s, i_s, y_s)
    fetch = [id(out)]
    in_specs = {"dense": P("data"), "ids": P("data"),
                "labels": P("data")}

    def pod_table_spec(t):
        return ("fsdp", None) if t is table_param else None

    plan = propagate_program(prog, pod, in_specs,
                             param_specs=pod_table_spec)
    emb_ops = ("embedding", "embedding_bag", "scatter_add")
    emb_fallbacks = {k: v for k, v in plan.fallback_ops.items()
                     if k in emb_ops}
    rep_shard = liveness.peak_report(prog, fetch_ids=fetch, plan=plan,
                                     mesh=pod)
    rep_repl = liveness.peak_report(prog, fetch_ids=fetch)
    # synthetic per-chip budget between the two peaks: the replicated
    # program provably does NOT fit where the sharded one does
    budget = (rep_shard["peak_bytes"] * rep_repl["peak_bytes"]) ** 0.5
    liveness_ok = (rep_repl["peak_bytes"] > budget
                   > rep_shard["peak_bytes"])

    # ---- dedup exchange model on the zipf batch --------------------
    stats = emb.dedup_stats(ids_np)
    pod_shards = 4                    # the pod proof's fsdp extent
    ex_bytes = emb.exchange_bytes(stats["n_unique"], cfg.embedding_dim,
                                  pod_shards)
    naive_bytes = emb.naive_gather_bytes(stats["n_ids"],
                                         cfg.embedding_dim, pod_shards)
    dedup_ok = ex_bytes < naive_bytes

    parity = abs(shard_loss - repl_loss) <= 1e-3 * max(
        abs(repl_loss), 1.0)
    gate = (parity and liveness_ok and dedup_ok
            and not emb_fallbacks)
    scope = shard_stats.get("scope", {})
    ratio = repl_dt / max(shard_dt, 1e-9)
    return {
        "metric": "embedding_sharded_vs_replicated_step_ratio",
        "value": round(ratio, 4),
        "unit": "x_replicated",
        # parity + capacity proof + dedup win gate the score: a
        # fast-but-wrong (or secretly replicated) program scores 0
        "vs_baseline": round(ratio, 4) if gate else 0.0,
        "extra": {
            "mesh": {"data": data, "fsdp": fsdp},
            "table": {"rows": cfg.num_embeddings,
                      "dim": cfg.embedding_dim,
                      "bytes": cfg.num_embeddings
                      * cfg.embedding_dim * 4},
            "sharded_step_s": round(shard_dt, 4),
            "replicated_step_s": round(repl_dt, 4),
            "loss_sharded": round(shard_loss, 5),
            "loss_replicated": round(repl_loss, 5),
            "loss_parity": bool(parity),
            "unique_ratio": round(stats["unique_ratio"], 4),
            "unique_ratio_gauge": unique_ratio_gauge,
            "exchange_bytes": int(ex_bytes),
            "naive_gather_bytes": int(naive_bytes),
            "dedup_shrinks_exchange": bool(dedup_ok),
            "pod_proof": {
                "budget_bytes": int(budget),
                "replicated_peak": int(rep_repl["peak_bytes"]),
                "sharded_peak": int(rep_shard["peak_bytes"]),
                "replicated_fits": bool(
                    rep_repl["peak_bytes"] <= budget),
                "sharded_fits": bool(
                    rep_shard["peak_bytes"] <= budget)},
            "embedding_fallbacks": emb_fallbacks,
            "fallback_ops": dict(plan.fallback_ops),
            "ops_annotated": scope.get("annotated"),
        },
    }


def _bench_planner_vs_manual(small):
    """Auto-parallel planner rung (BENCH_MODEL=planner_vs_manual;
    paddle_tpu/distributed/planner/). The SAME GPT weights run one
    fwd+bwd step four ways on one (data, tp) mesh: (a) the hand-built
    fleet TP layers, (b) manual megatron-TP placement via
    spmd.shard_params (the spmd_auto rung's placement), (c) manual
    FSDP placement (every param dim 0 over the model axis), (d) the
    PLANNER-emitted placement (candidate search scored by the cost
    model, no human in the loop). value = best-manual step time /
    planner step time (>= 1 means the planner matched or beat the best
    hand-written placement); loss parity vs the fleet path gates the
    score, and the winning plan must report zero replicate-fallbacks
    (extra.planner_fallbacks)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fleet_pkg
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import (mesh as mesh_mod, planner,
                                        spmd)
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    n_dev = jax.device_count()
    tp = 2 if n_dev >= 2 else 1
    data = max(n_dev // tp, 1)
    if small:
        cfg_kw = dict(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, max_seq_len=128,
                      use_flash_attention=False)
        batch, seq, iters = 4, 128, 3
    else:
        cfg_kw = dict(hidden_size=1024, num_layers=24, num_heads=16,
                      max_seq_len=1024)
        batch, seq, iters = _env_int("BENCH_BATCH", 8), 1024, 5
    rng = np.random.RandomState(0)
    ids = rng.randint(0, GPTConfig(**cfg_kw).vocab_size,
                      (batch, seq)).astype(np.int64)

    def step_fn_for(model, mesh=None, in_spec=None):
        params = [p for p in model.parameters() if not p.stop_gradient]

        def f(pa, ids_a):
            originals = [p._data for p in params]
            for p, a in zip(params, pa):
                p._data = a
            try:
                if mesh is None:
                    t = paddle.Tensor(ids_a)
                    _, loss = model(t, labels=t)
                    return loss._data
                sc = spmd.trace_scope(mesh)
                with sc:
                    for p in params:
                        spec = spmd.param_spec_of(p)
                        if spec is not None:
                            sc.seed(p, spec)
                    t = paddle.Tensor(ids_a)
                    sc.seed(t, in_spec if in_spec is not None
                            else P("data"))
                    _, loss = model(t, labels=t)
                stats["scope"] = dict(sc.stats)
                return loss._data
            finally:
                for p, o in zip(params, originals):
                    p._data = o

        stats = {}
        grad_f = jax.jit(jax.value_and_grad(f))
        pa = [p._data for p in params]
        return grad_f, pa, stats

    def warm(grad_f, pa):
        loss, grads = grad_f(pa, ids)       # compile + warm
        jax.block_until_ready(grads)
        return float(loss)

    def timed_interleaved(progs, rounds=4):
        """progs: {name: (grad_f, pa)} — measure in interleaved chunks
        (a,b,c,d, a,b,c,d, ...), min of chunk means per program, so
        host drift hits every program equally instead of whichever ran
        last."""
        best = {name: float("inf") for name in progs}
        for _ in range(rounds):
            for name, (grad_f, pa) in progs.items():
                t0 = time.perf_counter()
                for _ in range(iters):
                    loss, grads = grad_f(pa, ids)
                jax.block_until_ready(grads)
                jax.block_until_ready(loss)
                dt = (time.perf_counter() - t0) / iters
                best[name] = min(best[name], dt)
        return best

    def fresh_model(state):
        paddle.seed(1234)
        m = GPTForCausalLM(GPTConfig(**cfg_kw))
        m.set_state_dict(state)
        return m

    prev_mesh = mesh_mod._global_mesh
    try:
        # (a) hand-built fleet TP path — the weights source of truth
        strategy = fleet_pkg.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": data, "mp_degree": tp}
        fleet_pkg.fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(1234)
        tp_model = GPTForCausalLM(GPTConfig(mp_degree=tp, **cfg_kw))
        state = {k: np.asarray(v.numpy())
                 for k, v in tp_model.state_dict().items()}
        fleet_f, fleet_pa, _ = step_fn_for(tp_model)
        fleet_loss = warm(fleet_f, fleet_pa)

        mesh_mod._global_mesh = None
        mesh = mesh_mod.build_mesh({"data": data, "tp": tp})
        mesh_mod.set_mesh(mesh)

        # (b) manual megatron-TP placement (spmd_auto rung's rules)
        man_tp = fresh_model(state)
        spmd.shard_params(man_tp, mesh, [
            (r".*qkv_proj\.weight", P(None, "tp")),
            (r".*qkv_proj\.bias", P("tp")),
            (r".*fc1\.weight", P(None, "tp")),
            (r".*fc1\.bias", P("tp")),
            (r".*(out_proj|fc2)\.weight", P("tp", None)),
            (r".*wte\.weight", P("tp", None)),
        ])
        tp_f, tp_pa, _ = step_fn_for(man_tp, mesh=mesh)
        man_tp_loss = warm(tp_f, tp_pa)

        # (c) manual FSDP placement (every param dim 0 over the model
        # axis, batch over both axes)
        man_fs = fresh_model(state)
        spmd.shard_params(man_fs, mesh, [
            (r".*\.weight", P("tp")), (r".*\.bias", P("tp"))])
        fs_f, fs_pa, _ = step_fn_for(man_fs, mesh=mesh,
                                     in_spec=P(("data", "tp")))
        man_fs_loss = warm(fs_f, fs_pa)

        # (d) the planner's own placement — search + cost model
        plan_model = fresh_model(state)

        def plan_loss(x):
            _, loss = plan_model(x, labels=x)
            return loss

        res = planner.plan(plan_loss, mesh, example_inputs=(ids,),
                           model=plan_model)
        res.apply(plan_model)
        batch_entry = res.batch_entry
        pl_f, pl_pa, pl_stats = step_fn_for(
            plan_model, mesh=mesh,
            in_spec=P(batch_entry) if batch_entry is not None else P())
        planner_loss = warm(pl_f, pl_pa)

        dts = timed_interleaved({
            "fleet": (fleet_f, fleet_pa), "man_tp": (tp_f, tp_pa),
            "man_fs": (fs_f, fs_pa), "planner": (pl_f, pl_pa)})
        fleet_dt, man_tp_dt = dts["fleet"], dts["man_tp"]
        man_fs_dt, planner_dt = dts["man_fs"], dts["planner"]
    finally:
        mesh_mod._global_mesh = prev_mesh

    scope = pl_stats.get("scope", {})
    parity = abs(planner_loss - fleet_loss) <= 1e-3 * max(
        abs(fleet_loss), 1.0)
    zero_fallbacks = not scope.get("fallback")
    best_manual = min(fleet_dt, man_tp_dt, man_fs_dt)
    ratio = best_manual / max(planner_dt, 1e-9)
    return {
        "metric": "planner_vs_manual_step_ratio",
        "value": round(ratio, 4),
        "unit": "x_best_manual",
        # parity AND zero replicate-fallbacks are the gate: a
        # fast-but-wrong placement, or one the propagator could not
        # fully see, scores 0
        "vs_baseline": round(ratio, 4)
        if (parity and zero_fallbacks) else 0.0,
        "extra": {"mesh": {"data": data, "tp": tp},
                  "planner_winner": res.winner.candidate.name,
                  "planner_step_s": round(planner_dt, 4),
                  "fleet_tp_step_s": round(fleet_dt, 4),
                  "manual_tp_step_s": round(man_tp_dt, 4),
                  "manual_fsdp_step_s": round(man_fs_dt, 4),
                  "loss_planner": round(planner_loss, 5),
                  "loss_fleet_tp": round(fleet_loss, 5),
                  "loss_manual_tp": round(man_tp_loss, 5),
                  "loss_manual_fsdp": round(man_fs_loss, 5),
                  "loss_parity": bool(parity),
                  "planner_fallbacks": scope.get("fallback", {}),
                  "candidates_scored": len(res.ranked),
                  "candidates_rejected": len(res.rejected),
                  "modeled_winner_step_s": round(
                      res.winner.score.total_s, 6)},
    }


def _bench_fusion(small):
    """Graph-fusion rung (BENCH_MODEL=fusion; paddle_tpu/compile/fusion/).

    The SAME GPT transformer block — rms_norm → q/k projections →
    rotary embedding (attention prologue), layernorm → FFN → gelu →
    down-projection (MLP), residual add → rms_norm — measured fused vs
    unfused in the two regimes it actually runs in:

    * ``train``: the full fwd+bwd step through
      ``to_static(full_graph=True)`` + ``jax.value_and_grad`` — with
      ``FLAGS_enable_fusion`` on, the pass rewrites the traced program
      (rope_proj x2 + norm_linear + residual_norm) before the single
      XLA compile. Loss parity between the two programs gates the leg.
    * ``eager``: the block's forward dispatched op-by-op (the
      decode/serving regime the reference's fused_ops.yaml hot set
      targets) — the unfused chain is 10 dispatches / 10 program
      boundaries; the fused-op spelling is 4. Output parity gates it.

    value = geomean of the two fused-vs-unfused step-time ratios;
    vs_baseline is the same, zeroed if either parity gate fails (a
    fast-but-wrong rewrite scores 0, not a speedup). The acceptance
    bar in tools/perf_baseline.json is >= 1.10x.

    Timing: both programs are compiled/warmed up front, then measured
    in INTERLEAVED chunks (u,f,u,f,…) with min-of-chunk-means per leg —
    drift inside a ladder run (allocator state, co-tenant load, turbo)
    hits both programs equally instead of biasing whichever leg ran
    second, and the min is the contention-free estimate a ratio wants.
    """
    import paddle_tpu as paddle
    import paddle_tpu.ops as ops
    from paddle_tpu import nn
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import llama
    from paddle_tpu.nn import functional as F

    if small:
        B, S, H, FF, heads, iters = 4, 128, 256, 1024, 4, 10
    else:
        B, S, H, FF, heads, iters = 8, 512, 1024, 4096, 16, 20
    hd = H // heads
    paddle.seed(0)
    q_proj, k_proj = nn.Linear(H, H), nn.Linear(H, H)
    ln2 = nn.LayerNorm(H)
    fc1, fc2 = nn.Linear(H, FF), nn.Linear(FF, H)
    layers = (q_proj, k_proj, ln2, fc1, fc2)
    params = [p for m in layers for p in m.parameters()]
    rng = np.random.RandomState(0)
    # distinct inputs per timed iter (replay-caching backends fake the
    # timing on repeat-identical executions; see _run_train_bench)
    xs = [(rng.randn(B, S, H) * 0.5).astype(np.float32)
          for _ in range(3)]

    def block(xt):
        # attention prologue: the input norm feeds BOTH projections
        # (multi-consumer → stays), each projection+reshape+rope chain
        # fuses to ONE fused_rope_proj
        hn = F.rms_norm(xt)
        q = llama.rotary_embedding(
            ops.reshape(q_proj(hn), [B, S, heads, hd]))
        k = llama.rotary_embedding(
            ops.reshape(k_proj(hn), [B, S, heads, hd]))
        # MLP: layernorm → linear → gelu fuses to fused_norm_linear
        h = fc2(F.gelu(fc1(ln2(xt))))
        # residual add + rms_norm fuses to fused_residual_norm (the sum
        # is re-emitted, so the residual stream stays a real value)
        s = xt + h
        y = F.rms_norm(s)
        return y + ops.reshape(q, [B, S, H]) + ops.reshape(k, [B, S, H])

    def build_train(fused):
        paddle.set_flags({"FLAGS_enable_fusion": fused})
        sf = paddle.jit.to_static(block, full_graph=True)

        def f(pa, xa):
            originals = [p._data for p in params]
            for p, a in zip(params, pa):
                p._data = a
            try:
                out = sf(Tensor(xa))._data
                return (out * out).mean()
            finally:
                for p, o in zip(params, originals):
                    p._data = o

        g = jax.jit(jax.value_and_grad(f))
        pa = [p._data for p in params]
        loss, grads = g(pa, xs[0])          # compile + warm (flag is
        jax.block_until_ready(grads)        # read at THIS trace)
        return (g, pa, float(loss),
                (sf.fusion_stats or {}).get("rewritten", {}))

    def train_chunk(g, pa):
        t0 = time.perf_counter()
        for i in range(iters):
            loss, grads = g(pa, xs[i % len(xs)])
        jax.block_until_ready(grads)
        return (time.perf_counter() - t0) / iters

    def eager_unfused(xa):
        xt = Tensor(xa)
        hn = F.rms_norm(xt)
        q = llama.rotary_embedding(
            ops.reshape(F.linear(hn, q_proj.weight, q_proj.bias),
                        [B, S, heads, hd]))
        k = llama.rotary_embedding(
            ops.reshape(F.linear(hn, k_proj.weight, k_proj.bias),
                        [B, S, heads, hd]))
        h = fc2(F.gelu(fc1(ln2(xt))))
        s = xt + h
        y = F.rms_norm(s)
        return y + ops.reshape(q, [B, S, H]) + ops.reshape(k, [B, S, H])

    def eager_fused(xa):
        xt = Tensor(xa)
        hn = F.rms_norm(xt)
        q = F.fused_rope_proj(hn, q_proj.weight, q_proj.bias,
                              num_heads=heads)
        k = F.fused_rope_proj(hn, k_proj.weight, k_proj.bias,
                              num_heads=heads)
        h = fc2(F.fused_norm_linear(
            xt, fc1.weight, fc1.bias, ln2.weight, ln2.bias,
            activation="gelu", norm_type="layer_norm"))
        y, _s = F.fused_residual_norm(xt, h, norm_type="rms_norm",
                                      epsilon=1e-6)
        return y + ops.reshape(q, [B, S, H]) + ops.reshape(k, [B, S, H])

    def eager_chunk(fn):
        t0 = time.perf_counter()
        for i in range(iters):
            out = fn(xs[i % len(xs)])
        out.numpy()                          # value read drains the queue
        return (time.perf_counter() - t0) / iters

    prior_fusion = _fusion_on()          # BENCH_FUSION=1 ladder opt-in
    try:
        g_u, pa_u, loss_u, _ = build_train(False)
        g_f, pa_f, loss_f, patterns = build_train(True)
    finally:
        paddle.set_flags({"FLAGS_enable_fusion": prior_fusion})
    # both programs are compiled now (the fused trace already happened;
    # the flag no longer matters) — interleave the measurement
    chunks = 4
    t_u, t_f = [], []
    for _ in range(chunks):
        t_u.append(train_chunk(g_u, pa_u))
        t_f.append(train_chunk(g_f, pa_f))
    dt_u, dt_f = min(t_u), min(t_f)

    e_out_u = eager_unfused(xs[0]).numpy()   # warm + parity reference
    e_out_f = eager_fused(xs[0]).numpy()
    e_u, e_f = [], []
    for _ in range(chunks):
        e_u.append(eager_chunk(eager_unfused))
        e_f.append(eager_chunk(eager_fused))
    e_dt_u, e_dt_f = min(e_u), min(e_f)

    train_ratio = dt_u / max(dt_f, 1e-12)
    eager_ratio = e_dt_u / max(e_dt_f, 1e-12)
    loss_parity = abs(loss_u - loss_f) <= 1e-3 * max(abs(loss_u), 1.0)
    scale = max(float(np.abs(e_out_u).max()), 1e-6)
    eager_parity = float(np.abs(e_out_u - e_out_f).max()) <= 1e-3 * scale
    value = float(np.sqrt(train_ratio * eager_ratio))
    return {
        "metric": "fusion_fused_vs_unfused_step_ratio",
        "value": round(value, 4),
        "unit": "x_unfused",
        # parity is the gate: a fast-but-wrong rewrite scores 0
        "vs_baseline": round(value, 4)
        if (loss_parity and eager_parity and patterns) else 0.0,
        "extra": {
            "block": f"B{B} S{S} H{H} FF{FF} heads{heads}",
            "patterns": patterns,
            "train_unfused_step_s": round(dt_u, 5),
            "train_fused_step_s": round(dt_f, 5),
            "train_ratio": round(train_ratio, 4),
            "train_loss_unfused": round(loss_u, 6),
            "train_loss_fused": round(loss_f, 6),
            "loss_parity": bool(loss_parity),
            "eager_unfused_step_s": round(e_dt_u, 5),
            "eager_fused_step_s": round(e_dt_f, 5),
            "eager_ratio": round(eager_ratio, 4),
            "eager_parity": bool(eager_parity),
        },
    }


def _bench_fleet_observability(small):
    """Fleet-observability overhead rung (BENCH_MODEL=fleet_observability;
    paddle_tpu/observability/fleet.py + flight.py). The SAME step loop —
    a jitted matmul step plus one eager collective per step (so the
    flight recorder is actually on the path) — timed with the beacon +
    flight recorder fully OFF vs fully ON (beacon window 16, one probe
    step per window, straggler reduction each window). value = off/on
    step-time ratio (1.0 = free); the acceptance bar is overhead < 2%.
    A/B/A/B interleaved with min-of-passes so machine drift can't fake a
    regression either way."""
    import paddle_tpu as paddle
    from paddle_tpu.core import flags
    from paddle_tpu.distributed.communication import collective as C
    from paddle_tpu.observability import fleet, flight

    # step sized to the small end of REAL training steps (~ms-scale);
    # the beacon's absolute cost is µs-level, so judging it against a
    # sub-ms toy step would overstate the relative overhead 10x
    D, B = (768, 256) if small else (2048, 512)
    # the per-step cost sits near the host noise floor (~±30µs pair
    # jitter on a shared box), so the median needs many pairs to
    # resolve a <2% effect on a ~ms step; pairs cost ~2 steps each
    iters = 600 if small else 200
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(D, D) * 0.01, jnp.float32)
    x0 = jnp.asarray(rng.randn(B, D), jnp.float32)
    step = jax.jit(lambda x: jnp.tanh(x @ w))
    tok = paddle.to_tensor(np.zeros(64, np.float32))

    OFF = {"flight_recorder": False, "fleet_beacon": False}
    ON = {"flight_recorder": True, "fleet_beacon": True}

    def one_step(instrumented, b):
        t0 = time.perf_counter()
        if instrumented:
            b.step_begin()
        y = step(x0)
        C.all_reduce(tok)
        jax.block_until_ready(y)
        if instrumented:
            b.step_end()
        return time.perf_counter() - t0

    # PAIRED per-step A/B, alternating order: each iteration times one
    # uninstrumented and one instrumented step back to back (off-first
    # on even iterations, on-first on odd), so host-load drift cancels
    # inside every pair and slot-position bias cancels across pairs; the
    # median pair-difference is the beacon's true cost even when
    # scheduler noise is 10x larger than it. (A plain before/after
    # split measures the machine, not the beacon.)
    prev = {k: flags.get_flag(k) for k in ("flight_recorder",
                                           "fleet_beacon")}
    t_off, diffs = [], []
    try:
        bcn = fleet.reset_beacon(window=16)
        for _ in range(5):                       # warm compiles/caches
            jax.block_until_ready(step(x0))
            C.all_reduce(tok)
        for i in range(iters):
            if i % 2 == 0:
                flags.set_flags(OFF)
                d_off = one_step(False, bcn)
                flags.set_flags(ON)
                d_on = one_step(True, bcn)
            else:
                flags.set_flags(ON)
                d_on = one_step(True, bcn)
                flags.set_flags(OFF)
                d_off = one_step(False, bcn)
            t_off.append(d_off)
            diffs.append(d_on - d_off)
        entries = len(flight.RECORDER.tail())
    finally:
        flags.set_flags(prev)
        fleet.reset_beacon()
    off = float(np.median(t_off))
    # median over ALL paired diffs: the pairing already cancels drift
    # and the diffs are signed two-sided noise, so min-of-chunk-medians
    # would systematically pick the most-negative chunk and under-report
    # the instrumentation cost the gate exists to catch
    on = off + float(np.median(diffs))
    n_steps = iters                  # steps PER CONFIG (one each/pair)
    ratio = off / max(on, 1e-12)
    overhead_pct = (on / max(off, 1e-12) - 1.0) * 100.0
    return {
        "metric": "fleet_observability_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "x_uninstrumented",
        "vs_baseline": round(ratio, 4),
        "extra": {"overhead_pct": round(overhead_pct, 3),
                  "step_off_us": round(off * 1e6, 1),
                  "step_on_us": round(on * 1e6, 1),
                  "beacon_window": 16,
                  "steps_per_config": n_steps,
                  "windows_flushed": bcn.windows,
                  "flight_ring_entries": entries,
                  "within_budget": bool(overhead_pct < 2.0)},
    }


def _bench_goodput_overhead(small):
    """Goodput-ledger + sentinel overhead rung (BENCH_MODEL=
    goodput_overhead; paddle_tpu/observability/goodput.py +
    sentinel.py). The SAME jitted step timed bare vs with the full
    per-step job-health plane on the path — ledger step brackets
    (clock reads + billed-overlap accounting) and the sentinel's
    median/MAD + EWMA update per step. value = off/on step-time ratio
    (1.0 = free); the acceptance bar is overhead < 2% of the
    un-instrumented loop, same discipline as the fleet_observability
    and serving_reqtrace rungs (paired per-step A/B, alternating
    order, median over ALL signed pair diffs)."""
    import io

    from paddle_tpu.core import flags
    from paddle_tpu.observability import goodput, sentinel

    # step sized to the small end of REAL training steps (~ms-scale),
    # like the fleet rung: the ledger's absolute cost is µs-level
    D, B = (768, 256) if small else (2048, 512)
    iters = 600 if small else 200
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(D, D) * 0.01, jnp.float32)
    x0 = jnp.asarray(rng.randn(B, D), jnp.float32)
    step = jax.jit(lambda x: jnp.tanh(x @ w))

    OFF = {"goodput": False, "sentinel": False}
    ON = {"goodput": True, "sentinel": True}

    def one_step(instrumented, led, snt):
        t0 = time.perf_counter()
        if instrumented:
            led.step_begin()
        y = step(x0)
        jax.block_until_ready(y)
        if instrumented:
            snt.observe_step(led.step_end(), loss=0.0)
        return time.perf_counter() - t0

    prev = {k: flags.get_flag(k) for k in ("goodput", "sentinel")}
    t_off, diffs = [], []
    try:
        flags.set_flags(ON)
        led = goodput.reset_ledger().run_begin()
        # incidents print nowhere: overhead is what this rung measures,
        # and a GC-pause spike must not spam the bench log
        snt = sentinel.reset(stream=io.StringIO())
        for _ in range(5):                       # warm compiles/caches
            jax.block_until_ready(step(x0))
        for i in range(iters):
            if i % 2 == 0:
                flags.set_flags(OFF)
                d_off = one_step(False, led, snt)
                flags.set_flags(ON)
                d_on = one_step(True, led, snt)
            else:
                flags.set_flags(ON)
                d_on = one_step(True, led, snt)
                flags.set_flags(OFF)
                d_off = one_step(False, led, snt)
            t_off.append(d_off)
            diffs.append(d_on - d_off)
        incidents = len(snt.incidents())
        ledger_steps = led.snapshot()["steps"]
    finally:
        flags.set_flags(prev)
        goodput.reset_ledger()
        sentinel.reset()
    off = float(np.median(t_off))
    # median over ALL paired diffs (see the fleet rung's rationale)
    on = off + float(np.median(diffs))
    ratio = off / max(on, 1e-12)
    overhead_pct = (on / max(off, 1e-12) - 1.0) * 100.0
    return {
        "metric": "goodput_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "x_uninstrumented",
        "vs_baseline": round(ratio, 4),
        "extra": {"overhead_pct": round(overhead_pct, 3),
                  "step_off_us": round(off * 1e6, 1),
                  "step_on_us": round(on * 1e6, 1),
                  "steps_per_config": iters,
                  "ledger_steps": ledger_steps,
                  "sentinel_incidents": incidents,
                  "within_budget": bool(overhead_pct < 2.0)},
    }


_MTTR_CHILD = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from paddle_tpu.fault import CheckpointManager, capture_train_state
from paddle_tpu.fault.checkpoint_manager import auto_resume

out = sys.argv[1]
epoch = int(os.environ.get("PADDLE_ELASTIC_EPOCH", "0") or 0)

class Net:
    def __init__(self):
        self.w = np.zeros(8, np.float32)
    def state_dict(self):
        return {"w": self.w.copy()}
    def set_state_dict(self, sd):
        self.w = np.asarray(sd["w"], np.float32).copy()

net = Net()
mgr = CheckpointManager(os.path.join(out, "ckpt"), keep_n=3)
start = 0
if epoch > 0:
    meta = auto_resume(mgr, network=net)
    start = int(meta["step"]) if meta else 0
    print("MTTR_RESUMED step=%d t=%.6f" % (start, time.time()),
          flush=True)
for s in range(start + 1, 9):
    if epoch == 0 and s == 5:
        print("MTTR_CRASH t=%.6f" % time.time(), flush=True)
        os.kill(os.getpid(), 9)
    net.w += 0.1
    mgr.save(capture_train_state(network=net), step=s)
print("MTTR_DONE", flush=True)
'''


def _bench_fault_recovery(small):
    """Self-healing-fleet rung (BENCH_MODEL=fault_recovery;
    paddle_tpu/fault/supervisor.py). Two measurements:

    (1) disarmed-vs-armed A/B — the SAME jitted step timed with the
    fault plane off (FLAGS_collective_timeout_s=0, no monitor thread,
    no supervisor tick on the path) vs fully armed (monitor thread
    live + the per-step supervisor heartbeat tick the hapi loop
    issues). The supervisor's background publish thread runs during
    BOTH configs (it is per-interval, not per-step, so its cost
    cancels in the pair diffs). value = off/on step-time ratio (1.0 =
    free); acceptance bar: overhead < 2%, same paired-median
    discipline as the goodput rung.

    (2) MTTR — a real subprocess trainer under the elastic launcher is
    SIGKILLed mid-step at epoch 0 and relaunched with
    ``--max_restarts 1``; the wall from the crash stamp to the
    relaunched process's restored-step stamp is the measured
    mean-time-to-recovery. Reported in extra, NOT gated: it is
    dominated by interpreter + jax import time, a machine property."""
    import socket
    import subprocess
    import tempfile

    from paddle_tpu.core import flags
    from paddle_tpu.fault import supervisor as sup

    D, B = (768, 256) if small else (2048, 512)
    iters = 600 if small else 200
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(D, D) * 0.01, jnp.float32)
    x0 = jnp.asarray(rng.randn(B, D), jnp.float32)
    step = jax.jit(lambda x: jnp.tanh(x @ w))

    tmp = tempfile.mkdtemp(prefix="fault_bench_")
    lease = sup.FileLease(os.path.join(tmp, "leases"), rank=0, world=1,
                          ttl=600.0)
    svr = sup.Supervisor(lease, interval=5.0).start()

    def one_step(armed, i):
        t0 = time.perf_counter()
        if armed:
            sup.tick(i)
        y = step(x0)
        jax.block_until_ready(y)
        return time.perf_counter() - t0

    prev = flags.get_flag("collective_timeout_s")
    t_off, diffs = [], []
    try:
        for _ in range(5):                       # warm compiles/caches
            jax.block_until_ready(step(x0))
        for i in range(iters):
            if i % 2 == 0:
                flags.set_flags({"collective_timeout_s": 0.0})
                d_off = one_step(False, i)
                flags.set_flags({"collective_timeout_s": 2.0})
                d_on = one_step(True, i)
            else:
                flags.set_flags({"collective_timeout_s": 2.0})
                d_on = one_step(True, i)
                flags.set_flags({"collective_timeout_s": 0.0})
                d_off = one_step(False, i)
            t_off.append(d_off)
            diffs.append(d_on - d_off)
    finally:
        flags.set_flags({"collective_timeout_s": prev})
        svr.stop()
    off = float(np.median(t_off))
    on = off + float(np.median(diffs))
    ratio = off / max(on, 1e-12)
    overhead_pct = (on / max(off, 1e-12) - 1.0) * 100.0

    # -------- MTTR: kill -> elastic restart -> consensus-free resume
    child = os.path.join(tmp, "mttr_child.py")
    with open(child, "w") as f:
        f.write(_MTTR_CHILD)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    # the relaunched workers run on the CPU: a chip belongs to one
    # process, and this bench process already holds it
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    mttr_s, mttr_rc = None, None
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--master", f"127.0.0.1:{port}",
             "--max_restarts", "1", "--abort_grace", "2",
             child, tmp],
            env=env, capture_output=True, text=True, timeout=300)
        mttr_rc = proc.returncode
        stamps = {}
        for line in proc.stdout.splitlines():
            if line.startswith("MTTR_CRASH"):
                stamps["crash"] = float(line.rsplit("t=", 1)[1])
            elif line.startswith("MTTR_RESUMED"):
                stamps["resumed"] = float(line.rsplit("t=", 1)[1])
        if mttr_rc == 0 and "crash" in stamps and "resumed" in stamps:
            mttr_s = stamps["resumed"] - stamps["crash"]
    except (subprocess.TimeoutExpired, OSError):
        pass

    return {
        "metric": "fault_recovery_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "x_disarmed",
        "vs_baseline": round(ratio, 4),
        "extra": {"overhead_pct": round(overhead_pct, 3),
                  "step_off_us": round(off * 1e6, 1),
                  "step_on_us": round(on * 1e6, 1),
                  "steps_per_config": iters,
                  "within_budget": bool(overhead_pct < 2.0),
                  "mttr_s": (round(mttr_s, 3)
                             if mttr_s is not None else None),
                  "mttr_recovered": bool(mttr_rc == 0
                                         and mttr_s is not None)},
    }


def _bench_dispatch(small):
    """Per-op eager dispatch latency (VERDICT: SURVEY §7 hard part #1).

    Measures µs/op for a 128×128 matmul in a Python loop: eager with grad
    tape recording, eager under no_grad, and the same loop jitted. The
    eager path must not linearize (lazy-vjp dispatch), so tape-on overhead
    is bookkeeping only. Reference bar: generated C++ ad_func pipeline is
    µs-level (eager_gen.py:301)."""
    import paddle_tpu as paddle

    n = 50 if small else 300
    x = paddle.to_tensor(np.random.randn(128, 128).astype(np.float32))
    w = paddle.to_tensor(np.random.randn(128, 128).astype(np.float32))
    w.stop_gradient = False

    def loop_eager():
        y = x
        for _ in range(n):
            y = paddle.ops.matmul(y, w)
        return y

    def timed(f):
        out = f()
        jax.block_until_ready(out._data if hasattr(out, "_data") else out)
        t0 = time.perf_counter()
        out = f()
        jax.block_until_ready(out._data if hasattr(out, "_data") else out)
        return (time.perf_counter() - t0) / n * 1e6  # µs/op

    with_tape = timed(loop_eager)
    with paddle.no_grad():
        no_tape = timed(loop_eager)

    def jit_loop(xa, wa):
        def body(y, _):
            return y @ wa, None
        y, _ = jax.lax.scan(body, xa, None, length=n)
        return y

    jitted = jax.jit(jit_loop)
    # warm up (compile) on another input of the same shape
    x2 = jnp.asarray(np.random.randn(128, 128).astype(np.float32))
    jax.block_until_ready(jitted(x2, w._data))
    t0 = time.perf_counter()
    jax.block_until_ready(jitted(x._data, w._data))
    jit_us = (time.perf_counter() - t0) / n * 1e6

    return {
        "metric": "eager_dispatch_overhead_us_per_op",
        "value": round(with_tape, 2),
        "unit": "us/op",
        "vs_baseline": round(jit_us / max(with_tape, 1e-9), 4),
        "extra": {"eager_tape_us": round(with_tape, 2),
                  "eager_no_grad_us": round(no_tape, 2),
                  "jit_us": round(jit_us, 2),
                  "matmul": "128x128", "iters": n},
    }


def _async_gpt_parts(small):
    """Shared GPT harness of the async-runtime rungs: model + a
    functional AdamW step buildable donated or undonated (SAME math —
    donation is pure buffer aliasing, so loss parity is exact)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if small:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128,
                        use_flash_attention=False)
        batch, seq, iters = 4, 128, 6
    else:
        cfg = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                        max_seq_len=1024)
        batch, seq, iters = _env_int("BENCH_BATCH", 8), 1024, 8
    paddle.seed(7)
    model = GPTForCausalLM(cfg)
    params = [p for p in model.parameters() if not p.stop_gradient]
    b1, b2, eps, lr = 0.9, 0.95, 1e-8, 2.5e-4

    def loss_fn(pa, ids):
        originals = [p._data for p in params]
        for p, a in zip(params, pa):
            p._data = a
        try:
            t = paddle.Tensor(ids)
            _, loss = model(t, labels=t)
            return loss._data.astype(jnp.float32)
        finally:
            for p, o in zip(params, originals):
                p._data = o

    def make_step(donate):
        def step(state, ids):
            pa, m_st, v_st, t = state
            loss, grads = jax.value_and_grad(loss_fn)(pa, ids)
            t = t + 1
            tf = t.astype(jnp.float32)
            new_pa, new_m, new_v = [], [], []
            for w, m, v, g in zip(pa, m_st, v_st, grads):
                g = g.astype(jnp.float32)
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                m_hat = m / (1 - b1 ** tf)
                v_hat = v / (1 - b2 ** tf)
                w = w - lr * m_hat / (jnp.sqrt(v_hat) + eps)
                new_pa.append(w)
                new_m.append(m)
                new_v.append(v)
            return loss, (new_pa, new_m, new_v, t)

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    def init_state():
        pa = [jnp.array(p._data, copy=True) for p in params]
        return (pa, [jnp.zeros_like(a) for a in pa],
                [jnp.zeros_like(a) for a in pa],
                jnp.asarray(0, jnp.int32))

    return cfg, model, params, make_step, init_state, batch, seq, iters


def _bench_async_overlap(small):
    """Async-runtime rung (BENCH_MODEL=async_overlap; io/prefetch.py +
    donated steps + sharding/decomposed.py).

    The SAME GPT AdamW step runs two ways on the same batches:

    * ``off`` — the synchronous baseline: batch transferred inline on
      the consumer, undonated step, per-step host sync on the loss (the
      pre-round-17 ``Engine.fit`` shape).
    * ``on`` — the async runtime: DevicePrefetcher transfers batch k+1
      while step k computes, the step donates its param/optimizer-state
      buffers, and the loss is read once at the end.

    Loss parity between the legs gates the score (donation and
    prefetch change scheduling, never math). extra records the
    round-12 attribution of both legs — the acceptance bar is
    idle+host share strictly lower with overlap on — plus the
    perf.memory high-water census of each leg (donated buffers count 0
    the moment the step consumes them) and, when >= 2 devices are
    visible, the decomposed vs serial stage-2 parameter re-gather."""
    import paddle_tpu as paddle
    from paddle_tpu.io.prefetch import DevicePrefetcher
    from paddle_tpu.observability import perf as _perf, trace as _tr
    from paddle_tpu.observability.perf import memory as _mem
    from paddle_tpu.observability.perf.device import DEVICE_CAT

    cfg, model, params, make_step, init_state, batch, seq, iters = \
        _async_gpt_parts(small)
    rng = np.random.RandomState(0)
    # the loader hands out device Tensors (DataLoader._to_output);
    # the pre-round-17 Engine.fit pulled them back to host and re-put
    # them per step — that round trip is part of the off leg
    loader_batches = [
        paddle.Tensor(jnp.asarray(rng.randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int64)))
        for _ in range(iters)]
    step_off = make_step(False)
    step_on = make_step(True)

    def place(t):
        """The Engine's batch placement: Tensor → host → device."""
        return jnp.asarray(t.numpy())

    def run_off(state, census=False):
        lf = None
        for i, t in enumerate(loader_batches):
            with _tr.span("io.transfer", "io"):
                x = place(t)               # inline, on the critical path
            loss, new_state = step_off(state, x)
            if census and i == 1:
                # old state still referenced here — the undonated
                # execution window really holds both copies
                _mem.update_high_water("async_overlap_off")
            state = new_state
            lf = float(loss)               # per-step host sync
        return lf, state

    def run_on(state, census=False):
        pf = DevicePrefetcher(iter(loader_batches), depth=2,
                              place_fn=place)
        loss = None
        try:
            for i, x in enumerate(pf):
                prev = state
                loss, state = step_on(prev, x)
                if census and i == 1:
                    # prev was just donated: its buffers census as 0 —
                    # the high-water drop donation buys
                    _mem.update_high_water("async_overlap_on")
        finally:
            pf.close()
        return float(loss), state

    # warmup (compiles both programs) + parity + census
    state_off = init_state()
    state_on = init_state()
    loss_off, state_off = run_off(state_off, census=True)
    loss_on, state_on = run_on(state_on, census=True)
    parity = abs(loss_on - loss_off) <= 1e-3 * max(abs(loss_off), 1.0)

    # interleaved timed chunks, min per leg, alternating order per
    # round so host drift hits both legs equally
    best_off = best_on = float("inf")
    for r in range(5):
        legs = ("off", "on") if r % 2 == 0 else ("on", "off")
        for leg in legs:
            t0 = time.perf_counter()
            if leg == "off":
                _, state_off = run_off(state_off)
                best_off = min(best_off,
                               (time.perf_counter() - t0) / iters)
            else:
                _, state_on = run_on(state_on)
                best_on = min(best_on,
                              (time.perf_counter() - t0) / iters)

    # round-12 attribution of one step per leg. The jit call is
    # bracketed as a device span: on an async-dispatch backend it is a
    # ~ms enqueue (the block in timed_section covers the real execution
    # window); on a backend that serializes donated dispatch (CPU) the
    # call IS the execution — either way the device share lands where
    # the device actually worked, and the off leg's inline transfer +
    # per-step sync stay host/idle.
    attr_off = attr_on = None
    pf_attr = None
    try:
        import itertools

        st = {"s": state_off, "i": 0}

        def off_step():
            t = loader_batches[st["i"] % iters]
            st["i"] += 1
            with _tr.span("io.transfer", "io"):
                x = place(t)
            with _tr.span("bench.step", DEVICE_CAT):
                loss, st["s"] = step_off(st["s"], x)
            float(loss)                     # the sync the off leg pays
            return loss

        att = _perf.step_attribution(off_step, iters=2, warmup=1,
                                     name="async_off")["total"]
        attr_off = {k: round(att[k], 4) for k in
                    ("compute_frac", "collective_frac", "host_frac",
                     "idle_frac")}

        pf_attr = DevicePrefetcher(
            iter(itertools.cycle(loader_batches)), depth=2,
            place_fn=place)
        st2 = {"s": state_on}

        def on_step():
            x = next(pf_attr)
            with _tr.span("bench.step", DEVICE_CAT):
                loss, st2["s"] = step_on(st2["s"], x)
            return loss

        att = _perf.step_attribution(on_step, iters=2, warmup=1,
                                     name="async_on")["total"]
        attr_on = {k: round(att[k], 4) for k in
                   ("compute_frac", "collective_frac", "host_frac",
                    "idle_frac")}
    except Exception:
        pass
    finally:
        if pf_attr is not None:
            pf_attr.close()

    # decomposed vs serial stage-2 parameter re-gather (the old serial
    # front) — needs a multi-device sharding mesh
    gather = None
    if jax.device_count() >= 2:
        try:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed import mesh as mesh_mod
            from paddle_tpu.distributed.fleet.meta_optimizers. \
                dygraph_sharding_optimizer import shard_spec_for
            from paddle_tpu.distributed.sharding import (gather_grouped,
                                                         plan_groups)
            prev_mesh = mesh_mod._global_mesh
            try:
                mesh_mod._global_mesh = None
                deg = jax.device_count()
                mesh = mesh_mod.build_mesh({"sharding": deg})
                mesh_mod.set_mesh(mesh)
                shardable = [
                    (p, NamedSharding(
                        mesh, shard_spec_for(p.shape, deg, "sharding")))
                    for p in params
                    if shard_spec_for(p.shape, deg, "sharding")]
                rep = NamedSharding(mesh, P())

                def to_sharded():
                    for p, sh in shardable:
                        p._data = jax.device_put(p._data, sh)
                    jax.block_until_ready([p._data for p, _ in shardable])

                def timed_gather(fn):
                    best = float("inf")
                    for _ in range(3):
                        to_sharded()
                        t0 = time.perf_counter()
                        fn()
                        jax.block_until_ready(
                            [p._data for p, _ in shardable])
                        best = min(best, time.perf_counter() - t0)
                    return best

                def serial():
                    for p, _sh in shardable:
                        p._data = jax.device_put(p._data, rep)

                def decomposed():
                    gather_grouped([(p, rep) for p, _ in shardable],
                                   site="bench")

                gather = {
                    "serial_s": round(timed_gather(serial), 5),
                    "decomposed_s": round(timed_gather(decomposed), 5),
                    "groups": len(plan_groups(
                        [p for p, _ in shardable])),
                    "params": len(shardable)}
            finally:
                mesh_mod._global_mesh = prev_mesh
        except Exception:
            gather = None

    hbm_off = _mem.high_water("async_overlap_off")
    hbm_on = _mem.high_water("async_overlap_on")
    ratio = best_off / max(best_on, 1e-9)
    overlap_win = None
    if attr_off and attr_on:
        overlap_win = bool(
            attr_on["host_frac"] + attr_on["idle_frac"]
            < attr_off["host_frac"] + attr_off["idle_frac"])
    return {
        "metric": "async_overlap_step_ratio",
        "value": round(ratio, 4),
        "unit": "x_sync",
        # parity is the gate: a fast-but-wrong async pipeline scores 0
        "vs_baseline": round(ratio, 4) if parity else 0.0,
        "extra": {"sync_step_s": round(best_off, 4),
                  "async_step_s": round(best_on, 4),
                  "loss_sync": round(loss_off, 5),
                  "loss_async": round(loss_on, 5),
                  "loss_parity": bool(parity),
                  "attribution_off": attr_off,
                  "attribution_on": attr_on,
                  "idle_host_shrinks": overlap_win,
                  "hbm_high_water_off": hbm_off.get("total"),
                  "hbm_high_water_on": hbm_on.get("total"),
                  "gather_decomposition": gather,
                  "batch": batch, "seq": seq},
    }


def _bench_async_batch_sweep(small):
    """steps/sec-vs-batch sweep (BENCH_MODEL=async_batch_sweep): the
    SAME GPT step donated vs undonated across a batch ladder. Donation
    halves the params+optimizer-state working set of the step (inputs
    alias outputs), which is headroom for bigger batches — the sweep
    records tokens/s AND the alias-aware compiled peak bytes
    (memory_analysis) at every batch so the headroom is visible even on
    hosts where nothing OOMs. value = donated/undonated tokens/s at the
    largest batch, parity-gated."""
    cfg, model, params, make_step, init_state, _batch, seq, _iters = \
        _async_gpt_parts(small)
    batches = (2, 4, 8) if small else (4, 8, _env_int("BENCH_BATCH", 16))
    iters = 3 if small else 5
    step_off = make_step(False)
    step_on = make_step(True)
    rng = np.random.RandomState(0)

    def peak_bytes(compiled):
        from paddle_tpu.observability.perf.device import memory_breakdown
        mb = memory_breakdown(compiled)
        return mb["peak_bytes"] if mb else None

    def leg(step, state, ids):
        loss, state = step(state, ids)      # compile + warm
        first = float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, state = step(state, ids)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        return first, dt, state

    curve = []
    ratio_at_max = 0.0
    parity_all = True
    for b in batches:
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                      (b, seq)).astype(np.int64))
        first_off, dt_off, _ = leg(step_off, init_state(), ids)
        first_on, dt_on, _ = leg(step_on, init_state(), ids)
        parity = abs(first_on - first_off) <= 1e-3 * max(
            abs(first_off), 1.0)
        parity_all = parity_all and parity
        pk_off = peak_bytes(step_off.lower(init_state(), ids).compile())
        pk_on = peak_bytes(step_on.lower(init_state(), ids).compile())
        tok_off = b * seq / dt_off
        tok_on = b * seq / dt_on
        ratio_at_max = tok_on / max(tok_off, 1e-9)
        curve.append({"batch": b,
                      "tokens_per_s_undonated": round(tok_off, 1),
                      "tokens_per_s_donated": round(tok_on, 1),
                      "peak_bytes_undonated": pk_off,
                      "peak_bytes_donated": pk_on,
                      "loss_parity": bool(parity)})
    comparable = [c for c in curve
                  if c["peak_bytes_donated"] and c["peak_bytes_undonated"]]
    # None (not a vacuous True) when the backend measured nothing — the
    # acceptance signal must never read as satisfied without evidence
    donated_smaller = (
        all(c["peak_bytes_donated"] < c["peak_bytes_undonated"]
            for c in comparable)
        if comparable else None)
    return {
        "metric": "async_batch_sweep_tokens_ratio",
        "value": round(ratio_at_max, 4),
        "unit": "x_undonated",
        "vs_baseline": round(ratio_at_max, 4) if parity_all else 0.0,
        "extra": {"sweep": curve, "seq": seq,
                  "donated_peak_below_undonated": donated_smaller,
                  "loss_parity": bool(parity_all)},
    }


def _bench_pipeline(small):
    """Wall-clock pipeline-schedule comparison (VERDICT r3 #4): step time
    of FThenB vs 1F1B vs VPP(K=2,4) vs ZBH1 at fixed (m, total blocks)
    on a pp=4 mesh. Single-chip hosts re-exec onto a 4-device virtual CPU
    mesh (the schedules are SPMD programs; the RELATIVE tick economics —
    VPP's smaller bubble, ZBH1's dW filler — are schedule properties, and
    the measurement reports its host so the caller can weigh it)."""
    import subprocess
    import sys

    if os.environ.get("BENCH_PIPE_CHILD") == "1":
        # the child runs on a virtual CPU mesh, which would flip main()'s
        # small-detection — honor the parent's choice instead
        small = os.environ.get("BENCH_PIPE_SMALL") == "1"
    if jax.device_count() < 4 and os.environ.get("BENCH_PIPE_CHILD") != "1":
        env = dict(os.environ)
        # the child gets a 4-device VIRTUAL CPU mesh, never the chip:
        # this parent holds it, and a chip belongs to one process
        env.update(BENCH_PIPE_CHILD="1", BENCH_MODEL="pipeline",
                   BENCH_PIPE_SMALL="1" if small else "0",
                   JAX_PLATFORMS="cpu")
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform")]
        env["XLA_FLAGS"] = " ".join(
            flags + ["--xla_force_host_platform_device_count=4"])
        proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                              env=env, capture_output=True, text=True,
                              timeout=1800)
        for line in proc.stdout.splitlines():
            if line.startswith("{"):
                return json.loads(line)
        raise RuntimeError(f"pipeline child failed: {proc.stderr[-500:]}")

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              LayerDesc, PipelineLayer,
                                              PipelineParallel)

    mesh_mod.set_mesh(mesh_mod.build_mesh({"pp": 4},
                                          devices=jax.devices()[:4]))
    d = _env_int("BENCH_PIPE_HIDDEN", 192)
    mb_rows = _env_int("BENCH_PIPE_BATCH", 4 if small else 32)
    m = 8                      # micro-batches

    class _Blk(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(d, d)

        def forward(self, x):
            return paddle.ops.tanh(self.fc(x))

    x = paddle.to_tensor(
        np.random.randn(m * mb_rows, d).astype(np.float32))
    y = paddle.to_tensor(
        np.random.randn(m * mb_rows, d).astype(np.float32))

    def run_one(sched, L):
        paddle.seed(99)
        strategy = DistributedStrategy()
        strategy.pipeline_configs = {"accumulate_steps": m,
                                     "schedule_mode": sched}
        pl = PipelineLayer(
            layers=[LayerDesc(_Blk) for _ in range(L)],
            loss_fn=lambda o, t: paddle.ops.mean((o - t) ** 2))
        runtime = PipelineParallel(pl, None, strategy)
        runtime.forward_backward_pipeline((x, y))   # compile
        iters = 2 if small else 6
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = runtime.forward_backward_pipeline((x, y))
        jax.block_until_ready(loss._data)
        return (time.perf_counter() - t0) / iters * 1e3  # ms

    # K = blocks/stage: VPP interleaves K chunks per rank, so K is set by
    # the model depth at fixed S=4. Compare each schedule at the SAME L.
    times = {}
    for L, ktag in ((8, "K2"), (16, "K4")):
        for sched in ("FThenB", "1F1B", "VPP", "ZBH1"):
            times[f"{sched}-L{L}"] = run_one(sched, L)
    speedups = {ktag: times[f"1F1B-L{L}"] / times[f"VPP-L{L}"]
                for L, ktag in ((8, "K2"), (16, "K4"))}
    best = max(speedups.values())
    return {
        "metric": "pipeline_vpp_speedup_vs_1f1b",
        "value": round(best, 4),
        "unit": "x",
        "vs_baseline": round(best, 4),
        "extra": {"step_ms": {k: round(v, 2) for k, v in times.items()},
                  "vpp_speedup": {k: round(v, 4)
                                  for k, v in speedups.items()},
                  "m": m, "stages": 4, "hidden": d, "micro_rows": mb_rows,
                  "host": jax.default_backend()},
    }


def _bench_pipeline_bubble(small):
    """Pipeline-bubble rung (BENCH_MODEL=pipeline_bubble;
    distributed/pipeline/). Partitions a stacked-MLP program into S=4
    cost-balanced stages, runs 1F1B train steps with per-step timing,
    and replays the measured durations through the schedule event
    simulation (``schedules.simulate``) — the measured bubble fraction
    must land within tolerance of the closed form ``(S-1)/(m+S-1)``.
    With balanced stages the closed form is independent of the F:B
    cost ratio, so the bar holds on any host; the value is the boolean
    gate (1.0 = in tolerance AND gradient parity vs the unpipelined
    reference), raw fractions in extra."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static
    from paddle_tpu.distributed.pipeline import (PipelinedProgram,
                                                 partition_program)

    S, m = 4, 8
    d = _env_int("BENCH_PIPE_HIDDEN", 192 if small else 512)
    rows = 4                     # per-microbatch batch rows
    paddle.seed(23)
    blocks = []
    for _ in range(2 * S):
        blocks += [nn.Linear(d, d), nn.GELU()]
    model = nn.Sequential(*blocks)
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [rows, d], "float32")
        y = static.data("y", [rows, d], "float32")
        loss = ((model(x) - y) ** 2).mean()
    part = partition_program(prog, S, fetch_ids=[id(loss)])
    pp = PipelinedProgram(part, schedule="1f1b", loss_id=id(loss),
                          check=False)
    rng = np.random.RandomState(3)
    feed = {"x": rng.randn(m * rows, d).astype(np.float32),
            "y": rng.randn(m * rows, d).astype(np.float32)}
    pp.train_step(feed, m)       # compile
    best = None
    for _ in range(2 if small else 5):
        _l, grads, stats = pp.train_step(feed, m, collect_timing=True)
        err = abs(stats["measured_bubble"]
                  - stats["analytical_bubble"])
        if best is None or err < best[0]:
            best = (err, stats, grads)
    err, stats, grads = best
    _lr, grads_ref = pp.run_unpipelined(feed, m)
    parity = all(np.allclose(np.asarray(grads[k]),
                             np.asarray(grads_ref[k]))
                 for k in grads_ref)
    # CPU smoke carries per-step host-dispatch overhead the closed form
    # does not model; 0.15 absolute holds with ~2x margin there while
    # still catching a broken schedule (fthenb at S=4/m=8 would read
    # ~0.45 off a 0.27 bar)
    tol = float(os.environ.get("BENCH_PIPE_TOL", "0.15"))
    ok = bool(parity and err <= tol)
    return {
        "metric": "pipeline_bubble_measured_vs_analytical",
        "value": 1.0 if ok else 0.0,
        "unit": "bool",
        "vs_baseline": 1.0 if ok else 0.0,
        "extra": {
            "measured_bubble": round(stats["measured_bubble"], 4),
            "analytical_bubble": round(stats["analytical_bubble"], 4),
            "abs_err": round(err, 4), "tolerance": tol,
            "grad_parity": bool(parity), "stages": S,
            "microbatches": m, "hidden": d, "schedule": "1f1b",
            "host": jax.default_backend()},
    }


#: rungs that ride along in every default run, each with its own metric
#: class (none joins the train-ladder geomean): (bench key, metric name)
_RIDERS = (
    ("compile_cache", "compile_cache_warm_speedup"),
    ("spmd_auto", "spmd_auto_vs_fleet_tp_step_ratio"),
    ("embedding", "embedding_sharded_vs_replicated_step_ratio"),
    ("planner_vs_manual", "planner_vs_manual_step_ratio"),
    ("fusion", "fusion_fused_vs_unfused_step_ratio"),
    ("fleet_observability", "fleet_observability_overhead_ratio"),
    ("goodput_overhead", "goodput_overhead_ratio"),
    ("fault_recovery", "fault_recovery_overhead_ratio"),
    ("async_overlap", "async_overlap_step_ratio"),
    ("async_batch_sweep", "async_batch_sweep_tokens_ratio"),
    ("serving_resilience", "serving_resilience_goodput_tokens_per_sec"),
    ("serving_router", "serving_router_goodput_scaling"),
    ("serving_reqtrace", "serving_reqtrace_overhead_ratio"),
    ("verifier_overhead", "verifier_overhead_ratio"),
    ("pipeline_bubble", "pipeline_bubble_measured_vs_analytical"),
)


def _run_rung(fn, metric, small):
    """One rung, isolated: a rung that raises is recorded as
    ``"unit": "error"`` so the rest of the run still reports — and
    main() exits non-zero for it."""
    import gc
    import traceback
    try:
        r = fn(small)
    except Exception as e:
        traceback.print_exc()
        r = {"metric": metric, "value": 0.0, "unit": "error",
             "vs_baseline": 0.0, "extra": {"error": repr(e)[:300]}}
    print(json.dumps(r))
    sys.stdout.flush()
    # the 345M and 770M rungs each approach the 16 GB HBM ceiling; drop
    # cached executables/constants between rungs so one rung's residue
    # can't OOM the next
    gc.collect()
    jax.clear_caches()
    return r


def main():
    small = os.environ.get("BENCH_SMALL") == "1"
    if small or os.environ.get("BENCH_PIPE_CHILD") == "1":
        # CPU correctness sizes, or the pipeline rung's child: it runs on
        # a VIRTUAL CPU mesh because a chip belongs to one process and
        # the parent bench already holds it. Pin the platform before any
        # backend initializes.
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        # a measurement path with no chip fails; it never shrinks to toy
        # sizes on the CPU under the chip metrics' names
        sys.exit(f"bench.py measures on the chip, and the default backend "
                 f"is {jax.default_backend()!r}. BENCH_SMALL=1 runs the "
                 f"CPU correctness sizes (none of its numbers is a device "
                 f"metric).")
    else:
        from paddle_tpu.compile.cache import enable_jax_cache
        enable_jax_cache()      # before the first compile

    benches = {"gpt2": _bench_gpt, "resnet50": _bench_resnet50,
               "bert": _bench_bert, "llama": _bench_llama,
               "llama14": _bench_llama14,
               "dispatch": _bench_dispatch, "pipeline": _bench_pipeline,
               "pipeline_bubble": _bench_pipeline_bubble,
               "serving": _bench_serving,
               "serving_resilience": _bench_serving_resilience,
               "serving_router": _bench_serving_router,
               "serving_reqtrace": _bench_serving_reqtrace,
               "verifier_overhead": _bench_verifier_overhead,
               "static_analysis": _bench_static_analysis,
               "compile_cache": _bench_compile_cache,
               "spmd_auto": _bench_spmd_auto,
               "embedding": _bench_embedding,
               "planner_vs_manual": _bench_planner_vs_manual,
               "fusion": _bench_fusion,
               "fleet_observability": _bench_fleet_observability,
               "goodput_overhead": _bench_goodput_overhead,
               "fault_recovery": _bench_fault_recovery,
               "async_overlap": _bench_async_overlap,
               "async_batch_sweep": _bench_async_batch_sweep}
    if _env_bool("BENCH_FUSION", False):
        # opt the LADDER rungs into the fusion pass (they record the
        # flag state in extra either way); the fusion rung itself
        # measures both states regardless
        import paddle_tpu as _p
        _p.set_flags({"FLAGS_enable_fusion": True})
    which = os.environ.get("BENCH_MODEL", "all")
    if which != "all":
        print(json.dumps(benches[which](small)))
        return

    # Default run: every ladder rung (BASELINE.md configs 1-4), then the
    # riders: one JSON line per rung as it lands, then a combined summary
    # as the FINAL line so a driver that keeps only the last line still
    # records the ladder.
    rungs = {name: _run_rung(benches[name], name, small)
             for name in ("gpt2", "resnet50", "bert", "llama", "llama14")}
    riders = {name: _run_rung(benches[name], metric, small)
              for name, metric in _RIDERS}
    cc, sa, pv, fu = (riders[k] for k in (
        "compile_cache", "spmd_auto", "planner_vs_manual", "fusion"))
    fo, go, fr = (riders[k] for k in (
        "fleet_observability", "goodput_overhead", "fault_recovery"))
    ao, ab = riders["async_overlap"], riders["async_batch_sweep"]
    sr, srr, rt, vo = (riders[k] for k in (
        "serving_resilience", "serving_router", "serving_reqtrace",
        "verifier_overhead"))

    errors = [name for name, r in {**rungs, **riders}.items()
              if r["unit"] == "error"]
    ratios = [r["vs_baseline"] for name, r in rungs.items()
              if r["unit"] != "error"]
    geomean = (float(np.exp(np.mean(np.log(np.maximum(ratios, 1e-9)))))
               if ratios and not any(e in rungs for e in errors) else 0.0)
    print(json.dumps({
        # a failed rung zeroes the headline so the driver can't record a
        # full-ladder score from a partial run
        "metric": "train_ladder_vs_baseline_geomean",
        "value": round(geomean, 4),
        "unit": "x_baseline_geomean",
        "vs_baseline": round(geomean, 4),
        "errors": errors,
        "extra": {**{name: {"value": r["value"], "unit": r["unit"],
                            "vs_baseline": r["vs_baseline"],
                            "mfu": r.get("extra", {}).get("mfu"),
                            "attribution": r.get("extra", {}).get(
                                "attribution")}
                     for name, r in rungs.items()},
                  "compile_cache": {
                      "value": cc["value"], "unit": cc["unit"],
                      "cold_start_s": cc.get("extra", {}).get(
                          "cold_start_s"),
                      "warm_start_s": cc.get("extra", {}).get(
                          "warm_start_s")},
                  "serving_resilience": {
                      "value": sr["value"], "unit": sr["unit"],
                      "overload_retention": sr["vs_baseline"],
                      "curve": sr.get("extra", {}).get(
                          "goodput_vs_offered_load")},
                  "serving_router": {
                      "value": srr["value"], "unit": srr["unit"],
                      "overload_retention": srr["vs_baseline"],
                      "shed_at_router": srr.get("extra", {}).get(
                          "shed_at_router_total"),
                      "replica_side_shed": srr.get("extra", {}).get(
                          "replica_side_shed_total"),
                      "int8_kv_parity": srr.get("extra", {}).get(
                          "int8_kv_parity"),
                      "speculative_parity": srr.get("extra", {}).get(
                          "speculative_parity"),
                      "spec_acceptance_rate": srr.get("extra", {}).get(
                          "spec_acceptance_rate"),
                      "resident_batch_multiplier": srr.get(
                          "extra", {}).get("resident_batch_multiplier")},
                  "spmd_auto": {
                      "value": sa["value"], "unit": sa["unit"],
                      "loss_parity": sa.get("extra", {}).get(
                          "loss_parity"),
                      "auto_step_s": sa.get("extra", {}).get(
                          "auto_step_s"),
                      "fleet_tp_step_s": sa.get("extra", {}).get(
                          "fleet_tp_step_s"),
                      "attribution": sa.get("extra", {}).get(
                          "attribution")},
                  "planner_vs_manual": {
                      "value": pv["value"], "unit": pv["unit"],
                      "loss_parity": pv.get("extra", {}).get(
                          "loss_parity"),
                      "planner_winner": pv.get("extra", {}).get(
                          "planner_winner"),
                      "planner_step_s": pv.get("extra", {}).get(
                          "planner_step_s"),
                      "planner_fallbacks": pv.get("extra", {}).get(
                          "planner_fallbacks")},
                  "fusion": {
                      "value": fu["value"], "unit": fu["unit"],
                      "vs_baseline": fu["vs_baseline"],
                      "patterns": fu.get("extra", {}).get("patterns"),
                      "train_ratio": fu.get("extra", {}).get(
                          "train_ratio"),
                      "eager_ratio": fu.get("extra", {}).get(
                          "eager_ratio")},
                  "fleet_observability": {
                      "value": fo["value"], "unit": fo["unit"],
                      "overhead_pct": fo.get("extra", {}).get(
                          "overhead_pct"),
                      "within_budget": fo.get("extra", {}).get(
                          "within_budget")},
                  "goodput_overhead": {
                      "value": go["value"], "unit": go["unit"],
                      "overhead_pct": go.get("extra", {}).get(
                          "overhead_pct"),
                      "within_budget": go.get("extra", {}).get(
                          "within_budget")},
                  "fault_recovery": {
                      "value": fr["value"], "unit": fr["unit"],
                      "overhead_pct": fr.get("extra", {}).get(
                          "overhead_pct"),
                      "within_budget": fr.get("extra", {}).get(
                          "within_budget"),
                      "mttr_s": fr.get("extra", {}).get("mttr_s"),
                      "mttr_recovered": fr.get("extra", {}).get(
                          "mttr_recovered")},
                  "serving_reqtrace": {
                      "value": rt["value"], "unit": rt["unit"],
                      "overhead_pct": rt.get("extra", {}).get(
                          "overhead_pct"),
                      "within_budget": rt.get("extra", {}).get(
                          "within_budget")},
                  "verifier_overhead": {
                      "value": vo["value"], "unit": vo["unit"],
                      "overhead_pct": vo.get("extra", {}).get(
                          "overhead_pct"),
                      "within_budget": vo.get("extra", {}).get(
                          "within_budget")},
                  "async_overlap": {
                      "value": ao["value"], "unit": ao["unit"],
                      "loss_parity": ao.get("extra", {}).get(
                          "loss_parity"),
                      "idle_host_shrinks": ao.get("extra", {}).get(
                          "idle_host_shrinks"),
                      "attribution_off": ao.get("extra", {}).get(
                          "attribution_off"),
                      "attribution_on": ao.get("extra", {}).get(
                          "attribution_on")},
                  "async_batch_sweep": {
                      "value": ab["value"], "unit": ab["unit"],
                      "donated_peak_below_undonated": ab.get(
                          "extra", {}).get(
                              "donated_peak_below_undonated"),
                      "sweep": ab.get("extra", {}).get("sweep")}},
    }))
    if errors:
        sys.exit(f"bench.py: rungs failed: {', '.join(errors)}")


if __name__ == "__main__":
    main()
