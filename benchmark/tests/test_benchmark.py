"""CPU tests of the benchmark's own code at tiny presets. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the repo's tier-1 ``tests/``.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import flops, peaks, stats, trace_reduce  # noqa: E402
from benchmark.lib import traffic as traffic_lib  # noqa: E402
from benchmark.tests import tiny  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIGS = {c["name"]: json.load(open(os.path.join(ROOT, c["file"])))
           for c in BENCH["configs"]}


# ------------------------------------------------------------- generator
@pytest.mark.parametrize("mix", [tiny.OPEN, tiny.CLOSED],
                         ids=["open", "closed"])
def test_generator_is_deterministic_in_seed(mix):
    a = traffic_lib.requests(mix, 2**31 + 5, 4.0, 509)
    b = traffic_lib.requests(mix, 2**31 + 5, 4.0, 509)
    c = traffic_lib.requests(mix, 6, 4.0, 509)
    assert a == b
    assert a != c
    lens = lambda p: sorted((len(r["prompt"]), r["max_new_tokens"])  # noqa
                            for r in p["requests"])
    # another seed: the same multiset of sizes in another order
    assert lens(a) == lens(c)
    assert [r["prompt"] for r in a["requests"]] != \
        [r["prompt"] for r in c["requests"]]


def test_open_loop_offers_the_rate_and_clips_lengths():
    plan = traffic_lib.requests(tiny.OPEN, 1, 10.0, 509)
    reqs = plan["requests"]
    assert len(reqs) == 200
    due = [r["due"] for r in reqs]
    assert due == sorted(due) and 9.0 < due[-1] < 11.0
    assert all(4 <= len(r["prompt"]) <= 96 for r in reqs)
    assert all(2 <= r["max_new_tokens"] <= 24 for r in reqs)
    gaps = sorted(np.diff(due))
    assert gaps[-1] > 5 * np.median(gaps)      # exponential, not uniform


def test_ttft_runs_from_the_due_time_and_failures_are_worst():
    from benchmark.drivers import serve

    def rec(due, sent, stamps, status="FINISHED"):
        r = serve.Live({"prompt": [1]}, 0, due, sent, [])
        r.stamps, r.status = stamps, status
        return r

    records = [rec(1.0, 1.5, [2.0, 2.1, 2.3])] * 19 + \
        [rec(1.0, 1.0, [], "SHED")]
    red = serve.reduce_window({"records": records, "seconds": 10.0}, True)
    assert red["failed"] == 1 and red["attempted"] == 20
    assert math.isinf(red["ttft_p90_ms"]) is False
    assert red["ttft_p90_ms"] >= 1000.0         # from due (1.0), not sent
    assert red["gen_late_p95_ms"] == pytest.approx(500.0)
    assert red["itl_p95_ms"] == pytest.approx(195.0, rel=0.05)
    assert red["serve_tokens_per_s"] == pytest.approx(5.7)
    worse = serve.reduce_window(
        {"records": records[:17] + [rec(1.0, 1.0, [], "FAILED")] * 3,
         "seconds": 10.0}, True)
    assert math.isinf(worse["ttft_p90_ms"])


# ------------------------------------------------------------ arithmetic
def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile(xs, 90) == pytest.approx(90.1)
    assert stats.percentile([3.0], 95) == 3.0
    assert math.isinf(stats.percentile([1, 2, math.inf], 95))
    assert stats.percentile([1, 2, 3, math.inf], 50) == pytest.approx(2.5)
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_share([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)
    assert stats.latencies_with_failures([1.0], 2) == [1.0, math.inf,
                                                       math.inf]


def test_flops_against_hand_counts():
    gpt = CONFIGS["gpt2-medium"]
    # per layer 12 h^2 matmul weights; forward 2 ops a weight; causal
    # attention 2 matmuls x 2 ops x h x (S+1)/2 keys; tied head 2 h V
    layer = 2 * 12 * 1024**2 + 2 * 2 * 1024 * 1025 / 2
    fwd = 24 * layer + 2 * 1024 * 50257
    assert flops.gpt2_fwd_flops_per_token(gpt, 1024) == pytest.approx(fwd)
    assert flops.gpt2_train_flops_per_token(gpt, 1024) == \
        pytest.approx(3 * fwd)
    assert 2.26e9 < 3 * fwd < 2.28e9
    assert flops.gpt2_param_count(gpt) == 354_823_168
    # flash forward over 128 (batch x head) sequences of 1024 x 64
    one = 2.0 * 128 * 1024 * 1025 / 2 * 64
    assert flops.flash_call_flops("fwd", 128, 1024, 64) == 2 * one
    assert flops.flash_call_flops("dq", 128, 1024, 64) \
        + flops.flash_call_flops("dkv", 128, 1024, 64) == 5 * one
    assert flops.flash_call_bytes("fwd", 128, 1024, 64) == \
        4 * 128 * 1024 * 64 * 2 + 128 * 1024 * 4
    if "mistral-7b-v0.3-L16" in CONFIGS:
        mis = CONFIGS["mistral-7b-v0.3-L16"]
        per_layer = (4096 * 4096 * 2 + 2 * 4096 * 1024
                     + 3 * 4096 * 14336 + 2 * 4096)
        assert flops.llama_like_layer_params(mis) == per_layer == 218_112_000
        assert flops.llama_like_param_count(mis) == \
            16 * per_layer + 2 * 32768 * 4096 + 4096
        assert flops.kv_bytes_per_token(mis) == 64 * 1024
        weights = (16 * per_layer + 4096 + 32768 * 4096) * 2
        assert flops.decode_step_bytes(mis, 1000) == \
            weights + 1000 * 64 * 1024
    pk = peaks.peaks_for("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 0, pk) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, pk) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# ------------------------------------------------------- trace reduction
def test_union_and_gaps():
    assert trace_reduce.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert trace_reduce.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3),
                                                        (4, 5)]


def test_kernels_are_told_apart_by_signature():
    from benchmark.layer_metrics.flash_attn_roofline_pct import kernel_kind
    t = "bf16[128,1024,128]{2,1,0:T(8,128)(2,1)}"
    lse = "f32[128,1024,1]{2,1,0:T(8,128)}"
    tail = '), custom_call_target="tpu_custom_call", operand_layout_' \
        'constraints={bf16[128,1024,128]{2,1,0}}'
    fwd = f"%jvp__.26 = ({t}, {lse}) custom-call({t} %pad.10, {t} %pad.11, " \
        f"{t} %pad.12{tail}"
    six = f"{t} %a, {t} %b, {t} %c, {t} %d, {lse} %e, {lse} %f{tail}"
    dkv = f"%checkpoint.57 = ({t}, {t}) custom-call({six}"
    dq = f"%checkpoint.56 = {t} custom-call({six}"
    names = [trace_reduce.short_name(x) for x in (fwd, dkv, dq)]
    assert names[0] == ("%jvp__.26 tpu_custom_call/3 "
                        "(bf16[128,1024,128],f32[128,1024,1])")
    assert [kernel_kind(n) for n in names] == ["fwd", "dkv", "dq"]
    plain = "%fusion.942 = (f32[8]{0}, f32[8,4]{1,0}) fusion(f32[8]{0} %x), " \
        "kind=kOutput, calls=%fused_computation.1"
    assert trace_reduce.short_name(plain) == "%fusion.942 fusion"
    assert kernel_kind("%fusion.942 fusion") is None
    assert trace_reduce.short_name("jit_step(123)") == "jit_step(123)"


def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(ROOT, "benchmark", "lib", "testdata",
                        "small_trace.xplane.pb")
    s = trace_reduce.reduce(path)
    assert s["n_devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    # three steps of one small jitted program, each under a bench.step span
    spans = [h for h in s["host_spans"] if h[0].startswith("bench.step")]
    assert len(spans) == 3
    assert sum(c for c, _s in s["modules"].values()) == pytest.approx(3)
    bd = trace_reduce.breakdown(s)
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] >= bd["device_ops"][-1][1]
    assert sum(sec for _n, sec in bd["idle_gaps"]) <= s["window_s"]


# ------------------------------------------- references against the model
def test_gpt2_reference_against_the_model_file():
    import jax
    import paddle_tpu as paddle
    from benchmark.drivers import fit
    from benchmark.reference import gpt2 as ref

    cfg = tiny.GPT2
    engine, _opt, _names = fit.build(cfg, 3, jax.devices()[:1])
    ids = np.random.default_rng(0).integers(0, 97, (2, 32))
    lm = engine._model.lm
    with paddle.no_grad():
        _logits, loss = lm(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
    with jax.default_matmul_precision("highest"):
        want = ref.loss_sum(ref.init_params(cfg, 3), ids, ref._Frozen(cfg))
    assert float(loss) == pytest.approx(float(want) / (2 * 31), rel=2e-5)


def test_llama_reference_against_the_model_file():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from benchmark.drivers import serve
    from benchmark.reference import llama_like as ref

    cfg = tiny.LLAMA
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=509, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=128, rope_theta=1e6,
        rms_eps=1e-5, use_flash_attention=False))
    serve.load_weights(model, cfg, 4)
    ids = np.random.default_rng(1).integers(1, 509, (2, 24))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids)).numpy(), np.float32)
    want = np.asarray(ref.logits(cfg, 4, ids))
    # the model computes in the weights' bf16; the reference in float32
    assert np.max(np.abs(got - want)) < 0.03 * np.max(np.abs(want))
    assert np.mean(np.argmax(got, -1) == np.argmax(want, -1)) > 0.9


# ------------------------------------------------------ the harness's line
def test_run_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("trace", [False, True])
def test_last_line_has_exactly_the_contracts_keys(trace):
    from benchmark import run as run_mod
    from benchmark.lib import harness

    cell = harness.load_cell(BENCH["workloads"][0]["name"])
    summary = {"busy_s": 1.5, "window_s": 2.0, "ops": {"fusion.1": [3, 1.0]},
               "idle_gaps": [["bench.step", 0.25]]}
    ctx = {"kind": "fit", "config": cell["config"], "chips": 1,
           "device_kind": "TPU v5 lite", "window_s": 10.0,
           "tokens": 8 * 1024 * 40, "epochs": 2, "steps_per_epoch": 20,
           "batch": 8, "seq_len": 1024, "fit_call_s": 10.5,
           "epoch_starts": [0.4, 5.4], "t_end": 10.4,
           "setup_compile_s": 3.0, "stall_s": 0.1, "trace": summary,
           "traced_steps": 20}
    out = {"correct": True, "attempted": 40, "failed": 0, "ctx": ctx,
           "metrics": {"train_tokens_per_s": 32768.0, "setup_s": 20.0},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 12 * 2**30}}
    line = json.loads(run_mod.finish(cell, out, trace))
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == keys | ({"breakdown"} if trace else set())
    names = {m["name"] for m in cell["per_layer" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names
    if trace:
        assert set(line["device"]) == {"platform", "kind", "count",
                                       "memory_peak_bytes", "busy_s",
                                       "window_s"}
        assert line["metrics"]["train_step_ms"]["value"] == 250.0
        assert 0 < line["metrics"]["train_mfu_pct"]["value"] < 100
        # no trace rows for the flash kernels: the reader has nothing to
        # read and the metric is left out, never a host-clock number
        assert "flash_attn_roofline_pct" not in line["metrics"]
    else:
        assert set(line["metrics"]) == names
        assert set(line["device"]) == {"platform", "kind", "count",
                                       "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_benchmark_json_finds_every_file_by_name():
    from benchmark.lib import harness

    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        harness.load_driver(cell["traffic"]["kind"])
        assert cell["config"]["name"] == w["config"]
    for m in BENCH["per_layer"]:
        assert callable(__import__(
            "benchmark.layer_metrics." + m["name"], fromlist=["read"]).read)
