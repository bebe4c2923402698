"""The DeepSeek-V3 (``deepseek_v3``) serving kind at a size a test run
holds: a sound run is ``correct``, a served token altered is not, the int8
control fails the limit, the configuration's file keeps the published keys,
and the operation and byte counts against hand counts. CPU, the tiny preset
of ``tiny_deepseek_v3.py``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import flops_deepseek_v3 as fl  # noqa: E402
from benchmark.tests import tiny, tiny_deepseek_v3  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "kanana-longdoc-saturated"
NAME = "kanana-2-30b-a3b-L8-ep8"
REAL = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", NAME + ".json")))
MIX = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "longdoc-closed-128.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OPEN = dict(tiny.OPEN, kind="serve_deepseek_v3")
CLOSED = dict(tiny.CLOSED, kind="serve_deepseek_v3")


def _devices():
    import jax
    return jax.devices()[:1]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_sound_run_is_correct(mix):
    from benchmark.drivers import serve_deepseek_v3
    from benchmark.lib import harness
    out = serve_deepseek_v3.run(
        tiny.cell(tiny_deepseek_v3.DEEPSEEK, mix), 2, 1.5, False,
        _devices(), time.perf_counter())
    assert out["correct"], out["numbers"]
    assert out["ctx"]["kind"] == "serve"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_tokens_per_s"] > 0
    load = out["ctx"]["window"]["expert_load"]
    assert len(load) == 2 and all(sum(layer) > 0 for layer in load)
    assert harness.read_layer_metric("moe_expert_load_max_over_mean",
                                     out["ctx"]) >= 1.0
    # no trace, nothing for the trace's readers
    for name in ("mla_attn_share_pct", "mla_decode_roofline_pct",
                 "mla_decode_attn_roofline_pct"):
        assert harness.read_layer_metric(name, out["ctx"]) is None


def test_altered_token_is_not_correct():
    from benchmark.drivers import serve_deepseek_v3

    def alter(rec, position, token):
        return (token + 1) % 251 if position == 1 else token

    out = serve_deepseek_v3.run(
        tiny.cell(tiny_deepseek_v3.DEEPSEEK, OPEN), 22, 1.5, False,
        _devices(), time.perf_counter(), alter_token=alter)
    assert not out["correct"]
    assert out["numbers"]["logit_gap_max"] > \
        tiny_deepseek_v3.DEEPSEEK["check"]["logit_gap_max"]


def test_control_fails_the_limit_at_test_size():
    """int8 weights in the reference's place, at a size where the rounding
    shows (hidden 1024, 8 heads of 64 + 32 over a latent row of 256 + 32,
    three layers, 64 experts top-6 of which 16 are held, vocab 16384; at
    hidden 64 int8 weights are as close to float32 as bf16 arithmetic is):
    the mean gap of its first choices fails the limit of that size, which
    the program's served tokens pass."""
    from benchmark.drivers import serve_deepseek_v3
    cfg = dict(tiny_deepseek_v3.DEEPSEEK, hidden_size=1024, vocab_size=16384,
               num_attention_heads=8, kv_lora_rank=256, qk_nope_head_dim=64,
               qk_rope_head_dim=32, qk_head_dim=96, v_head_dim=64,
               intermediate_size=2048, moe_intermediate_size=512,
               n_routed_experts=16, router_width=64, experts_held=[0, 16],
               num_experts_per_tok=6, initializer_range=0.02)
    cfg["check"] = dict(cfg["check"], logit_gap_mean=1e-3)
    mix = dict(OPEN, check_requests=24, rate_rps=4.0)
    got = serve_deepseek_v3.control(tiny.cell(cfg, mix), 2, _devices(), 8.0)
    assert got["program"]["logit_gap_mean"] < cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > \
        3 * got["program"]["logit_gap_mean"]


def test_the_other_kinds_functions_are_put_back():
    from benchmark.drivers import serve, serve_deepseek_v3
    mine = (serve.build, serve.drive, serve.compare_with_reference)
    with serve_deepseek_v3._in_serves_place():
        assert serve.build is serve_deepseek_v3.build
    assert (serve.build, serve.drive, serve.compare_with_reference) == mine
    assert serve.build.__module__ == "benchmark.drivers.serve"


# ------------------------------------------------------ the configuration
def test_cell_and_its_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-closed-128"
    assert cell["config"] == NAME and len(cell["why"]) <= 200
    assert BENCH["workloads"][-1] is cell       # appended, nothing moved
    reports = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {
        "setup_s", "serve_tokens_per_s", "itl_p95_ms", "compile_s",
        "batch_occupancy_pct", "idle_attributed_pct_serve",
        "prefill_chunk_device_ms", "decode_step_device_ms",
        "paged_attn_share_pct", "moe_share_pct",
        "moe_expert_load_max_over_mean", "mla_attn_share_pct",
        "mla_decode_roofline_pct", "mla_decode_attn_roofline_pct"}
    new = BENCH["per_layer"][-3:]
    assert [m["name"] for m in new] == [
        "mla_attn_share_pct", "mla_decode_roofline_pct",
        "mla_decode_attn_roofline_pct"]
    assert all(m["workloads"] == [CELL] and m["unit"] == "%" for m in new)
    assert [m["moves"] for m in new] == ["itl_p95_ms", "serve_tokens_per_s",
                                         "itl_p95_ms"]
    # metrics of decode-only ticks: this traffic leaves none in a window
    # (every tick that decodes also runs two prefill chunks)
    assert not {"decode_tick_ms", "tick_host_exposed_ms"} & reports
    assert all("workloads" in m for m in BENCH["per_layer"])
    # one cell in four may take four chips
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_traffic_is_the_issues():
    assert (MIX["kind"], MIX["loop"], MIX["clients"],
            MIX["requests_per_cycle"], MIX["cycles"]) == (
        "serve_deepseek_v3", "closed", 128, 256, 2)
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 1.0, "min": 512, "max": 28672}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 64, "max": 2048}
    assert (MIX["pairing_seed"], MIX["order_seed"],
            MIX["check_requests"]) == (7, 11, 8)
    from benchmark.lib import traffic
    pairs = traffic.length_pairs(MIX, 256)
    assert round(float(pairs[:, 0].mean())) == 6335
    assert round(float(pairs[:, 1].mean())) == 608
    # the longest request fits the published context
    assert pairs.sum(axis=1).max() <= REAL["engine"]["context"] == \
        REAL["max_position_embeddings"] == 32768
    # every id of the slice of the vocabulary this chip holds
    plan = traffic.requests(dict(MIX, cycles=1), 2**31 + 5, 50.0,
                            REAL["vocab_size"])
    ids = [t for r in plan["requests"][:8] for t in r["prompt"]]
    assert 1 <= min(ids) and max(ids) < REAL["vocab_size"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_keeps_the_published_keys():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert REAL["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if REAL.get(k, "") != v}
    assert differs == set(REAL["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    # every published width unchanged
    for key in ("hidden_size", "num_attention_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim",
                "v_head_dim", "head_dim", "moe_intermediate_size",
                "intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "q_lora_rank"):
        assert REAL[key] == row["config"][key], key
    assert REAL["reduced_from"] == {
        k: row["config"][k] for k in REAL["reduced"]}
    assert REAL["router_width"] == 128
    lo, hi = REAL["experts_held"]
    assert hi - lo == REAL["n_routed_experts"] == 16
    assert REAL["vocab_size"] * 8 == row["config"]["vocab_size"]
    # the floors: a whole period and four layers after the dense one,
    # eight experts, an eighth of the vocabulary
    assert REAL["num_hidden_layers"] - REAL["first_k_dense_replace"] >= 4
    eng = REAL["engine"]
    assert eng["context"] % eng["block_size"] == 0
    assert eng["num_blocks"] * eng["block_size"] == 786432
    for key in ("deployment", "depth", "batch", "weights",
                "initializer_range", "e_score_correction_bias",
                "latent_row_padding"):
        assert REAL["assumed"][key]


def test_flops_against_hand_counts():
    c = REAL
    assert fl.counts(c) == {"layers": 8, "dense": 1, "sparse": 7}
    assert fl.dims(c) == {"q": 6144, "row": 576, "kv_b": 8192, "o": 4096,
                          "shared": 1536}
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert fl.attention_matmul_params(c) == attn == 26_345_472
    assert fl.dense_mlp_params(c) == 3 * 2048 * 6144 == 37_748_736
    assert fl.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    assert fl.moe_fixed_matmul_params(c) == 2048 * 128 + 3 * 2048 * 1536
    # the ISSUE's count: 910.6 M parameters when every held expert is read
    assert round((8 * attn + 37_748_736
                  + 7 * (2048 * 128 + 3 * 2048 * 1536 + 16 * 4_718_592)
                  + 2 * 2048 * 16032) / 1e5) == 9106
    # 64 tokens that pick 6 of 128 touch nearly all 16 held experts
    touched = 16 * (1 - (1 - 6 / 128) ** 64)
    assert fl.experts_touched(c, 64) == pytest.approx(touched)
    assert 15.2 < touched < 16
    assert fl.pairs_landed(c, 64) == 64 * 6 * 16 / 128 == 48.0
    # what a token caches in a layer, whatever the program pads it to
    assert fl.row_bytes(c) == 1152
    # a (query, row) pair, absorbed: 32 heads, a 576-wide score and a
    # 512-wide weighted sum; 17.8 MFLOP a cached token a layer a chunk
    assert fl.pair_flops(c) == 2 * 32 * (576 + 512) == 69_632
    assert round(256 * fl.pair_flops(c) / 1e5) == 178
    matrices = (8 * attn + 37_748_736
                + 7 * (2048 * 128 + 3 * 2048 * 1536 + touched * 4_718_592)
                + 2048 * 16032)
    scales = 8 * (2 * 2048 + 512) + 2048 + 7 * 128
    assert fl.weight_bytes(c, 64) == pytest.approx(2 * matrices + 4 * scales)
    # a decode step of 64 lanes over 400 000 cached tokens: every layer
    # reads them all and writes a row a lane
    cached = 400_000
    assert fl.decode_step_bytes(c, 64, cached) == pytest.approx(
        fl.weight_bytes(c, 64) + 8 * (cached + 64) * 1152)
    per_token = 2 * (8 * attn + 37_748_736
                     + 7 * (2048 * 128 + 3 * 2048 * 1536))
    assert fl.matmul_flops_per_token(c) == per_token
    assert fl.decode_step_flops(c, 64, cached) == pytest.approx(
        64 * (per_token + 2 * 2048 * 16032) + 2 * 7 * 48 * 4_718_592
        + 69_632 * 8 * cached)
    # the kernel: every row once a layer, an absorbed query row in and a
    # latent output row out a head a lane
    assert fl.latent_attn_decode_bytes(c, 64, cached) == 8 * (
        cached * 1152 + 64 * 32 * (576 + 512) * 2)
    assert fl.latent_attn_decode_flops(c, cached) == 69_632 * 8 * cached
    # bound by HBM at the published peaks: 60 operations a byte
    assert fl.latent_attn_decode_flops(c, cached) / 197e12 < \
        fl.latent_attn_decode_bytes(c, 64, cached) / 819e9
    # a 256-token chunk at 4096 cached tokens
    assert fl.prefill_chunk_bytes(c, 256, 4096) == pytest.approx(
        fl.weight_bytes(c, 256) + 8 * (4096 + 256) * 1152)
    keys = 3840 + 128.5
    assert fl.prefill_chunk_flops(c, 256, 4096) == pytest.approx(
        256 * per_token + 2 * 7 * fl.pairs_landed(c, 256) * 4_718_592
        + 69_632 * 8 * 256 * keys + 2 * 2048 * 16032)
