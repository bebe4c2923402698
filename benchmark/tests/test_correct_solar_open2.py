"""The Solar Open 2 (``solar_open2``) serving kind at a size a test run
holds: a sound run is ``correct``, a served token altered is not, the int8
control fails the limit at test size, the configuration's file keeps the
published keys, and the operation and byte counts against hand counts. CPU,
the tiny preset of ``tiny_solar_open2.py``.

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

from benchmark.lib import flops_solar_open2 as fl  # noqa: E402
from benchmark.tests import tiny, tiny_solar_open2  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "solaropen2-rag-saturated"
NAME = "solar-open2-250b-L4-ep8"
REAL = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", NAME + ".json")))
MIX = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "rag-closed-512.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = tiny_solar_open2.CFG


def _devices():
    import jax
    return jax.devices()[:1]


def _mix(base):
    return dict(base, kind="serve_solar_open2")


@pytest.mark.parametrize("mix", [tiny.OPEN, tiny.CLOSED],
                         ids=["open", "closed"])
def test_sound_solar_open2_run_is_correct(mix):
    from benchmark.drivers import serve_solar_open2
    out = serve_solar_open2.run(tiny.cell(TINY, _mix(mix)), 21, 1.5, False,
                                _devices(), time.perf_counter())
    assert out["correct"], out["numbers"]
    assert out["ctx"]["kind"] == "serve"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_tokens_per_s"] > 0
    assert out["ctx"]["max_batch"] == 4
    # the expert counters were read around the window: four layers of four
    load = out["ctx"]["window"]["expert_load"]
    assert len(load) == 4 and all(len(row) == 4 for row in load)
    assert sum(map(sum, load)) > 0


def test_altered_solar_open2_token_is_not_correct():
    from benchmark.drivers import serve_solar_open2

    def alter(rec, position, token):
        return (token + 1) % 251 if position == 1 else token

    out = serve_solar_open2.run(
        tiny.cell(TINY, _mix(tiny.OPEN)), 22, 1.5, False, _devices(),
        time.perf_counter(), alter_token=alter)
    assert not out["correct"]
    assert out["numbers"]["logit_gap_max"] > TINY["check"]["logit_gap_max"]


def test_solar_open2_control_fails_the_limit_at_test_size():
    """int8 weights in the reference's place, at a size where the rounding
    shows (hidden 1024, 8 linear heads of 64 x 64, 8 query heads over 2 K/V
    heads of 128, experts of 256, vocab 8192, N(0, 0.02); at hidden 64 int8
    weights are as close to float32 as bfloat16 arithmetic is): the mean gap
    of its first choices fails the limit of that size, which the program's
    served tokens pass (program 0.0050-0.0086, control 0.0097-0.0172 over
    seeds 1-3 on the CPU; seed 2 reads 0.0050 and 0.0147)."""
    from benchmark.drivers import serve_solar_open2
    cfg = dict(TINY, hidden_size=1024, vocab_size=8192,
               num_attention_heads=8, num_key_value_heads=2, head_dim=128,
               linear_attn_config={"short_conv_kernel_size": 4,
                                   "head_dim": 64, "num_heads": 8,
                                   "num_kv_heads": None},
               moe_intermediate_size=256, initializer_range=0.02,
               chunk_size=32)
    cfg["check"] = dict(cfg["check"], logit_gap_mean=9e-3)
    mix = dict(_mix(tiny.OPEN), check_requests=24, rate_rps=4.0)
    got = serve_solar_open2.control(tiny.cell(cfg, mix), 2, _devices(), 6.0)
    assert got["program"]["logit_gap_mean"] < cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > \
        1.5 * got["program"]["logit_gap_mean"]


def test_the_other_kinds_functions_are_put_back():
    from benchmark.drivers import serve, serve_hybrid, serve_solar_open2
    mine = (serve.build, serve.drive, serve.compare_with_reference)
    with serve_solar_open2._in_serves_place():
        assert serve.build is serve_solar_open2.build
        assert serve.drive is serve_hybrid.drive    # the counters' reads
    assert (serve.build, serve.drive, serve.compare_with_reference) == mine
    assert serve.build.__module__ == "benchmark.drivers.serve"


def test_another_arch_is_refused_by_name():
    from benchmark.drivers import serve_solar_open2
    with pytest.raises(SystemExit, match="serve_solar_open2 driver has no "
                                         "model for arch 'nemotron_h'"):
        serve_solar_open2.model_config(dict(TINY, arch="nemotron_h"))
    with pytest.raises(SystemExit, match="disagree"):
        serve_solar_open2.model_config(dict(TINY, n_routed_experts=5))


def test_a_program_without_the_model_fails_at_once(monkeypatch):
    """The parent of the PR that brought the model is tried on the cell with
    these files laid over it: it must fail cleanly, not hang."""
    import paddle_tpu.models as zoo
    from benchmark.drivers import serve_solar_open2
    monkeypatch.delattr(zoo, "SolarOpen2Config")
    with pytest.raises(SystemExit, match="no solar_open2 model"):
        serve_solar_open2.model_config(TINY)


# ------------------------------------------------------ the configuration
def test_cell_and_its_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME,
                    "traffic": "rag-closed-512", "chips": 1,
                    "why": cell["why"]}
    assert BENCH["workloads"][-1] is cell and len(cell["why"]) <= 200
    reports = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"setup_s", "serve_tokens_per_s", "itl_p95_ms", "compile_s",
            "kda_step_roofline_pct", "kda_share_pct",
            "solar_tick_roofline_pct", "prefill_chunk_device_ms",
            "mixed_step_device_ms", "batch_occupancy_pct", "decode_tick_ms",
            "idle_attributed_pct_serve", "host_late_share_pct",
            "mixed_share_pct", "engine_warmup_s",
            "moe_expert_load_max_over_mean"} <= reports
    # left to a benchmark PR (the ledger's notes) or another model's bytes
    assert not reports & {"tick_host_exposed_ms", "ttft_p90_ms",
                          "full_attn_decode_roofline_pct",
                          "delta_rule_decode_roofline_pct",
                          "mha_decode_attn_roofline_pct",
                          "olmo_hybrid_decode_roofline_pct",
                          "exaone_decode_roofline_pct",
                          "decode_step_roofline_pct", "mamba_share_pct"}
    assert all("workloads" in m for m in BENCH["per_layer"])
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == [
        "kda_step_roofline_pct", "kda_share_pct", "solar_tick_roofline_pct"]
    assert all(m["workloads"] == [CELL] for m in BENCH["per_layer"][-3:])
    config = BENCH["configs"][-1]
    assert config["name"] == NAME
    assert config["reduced"] == REAL["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    assert config["file"] == f"benchmark/configs/{NAME}.json"
    assert config["source"] == REAL["source"]


def test_traffic_is_the_issues_letter_for_letter():
    assert MIX["kind"] == "serve_solar_open2" and MIX["loop"] == "closed"
    assert MIX["clients"] == 512 == 2 * REAL["engine"]["max_batch"]
    assert MIX["requests_per_cycle"] == 1024
    assert (MIX["pairing_seed"], MIX["check_requests"]) == (7, 8)
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 800,
                                 "sigma": 0.9, "min": 128, "max": 6144}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 64, "max": 2048}
    assert MIX["prompt_len"]["max"] + MIX["output_len"]["max"] \
        <= REAL["engine"]["context"]
    # no client ever runs out: more requests than 60 s can finish
    assert MIX["requests_per_cycle"] * MIX["cycles"] >= 4 * MIX["clients"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_keeps_the_published_keys():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Solar-Open2-250B")
    assert REAL["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if REAL.get(k) != v}
    assert differs == set(REAL["reduced"])
    assert REAL["reduced_from"] == {k: row["config"][k] for k in differs}
    # layers 0-3: one whole period of the published pattern, G K K K
    assert REAL["gqa_layers"] == [0] and REAL["num_hidden_layers"] == 4
    assert row["config"]["gqa_layers"][:2] == [0, 4]
    # no width is cut
    for key in ("hidden_size", "num_attention_heads", "head_dim",
                "num_key_value_heads", "moe_intermediate_size",
                "linear_attn_config", "num_experts_per_tok",
                "n_shared_experts", "intermediate_size"):
        assert REAL[key] == row["config"][key], key
    assert REAL["router_width"] == row["config"]["n_routed_experts"] == 320
    assert REAL["experts_held"] == [0, 40] and REAL["n_routed_experts"] == 40
    assert "96 chips" in REAL["assumed"]["deployment"]
    assert "8 chips sharing each layer" in REAL["assumed"]["deployment"]
    for key in ("deployment", "depth", "batch", "norm_placement",
                "linear_attention", "low_rank_projections", "time_constants",
                "conv", "gqa_layers", "router", "intermediate_size", "state",
                "weights", "chunk_size"):
        assert REAL["assumed"][key], key
    assert REAL["engine"] == dict(
        REAL["engine"], max_batch=256, context=8192, block_size=16,
        num_blocks=40960, prefill_token_budget=1024, max_queue=512)
    assert REAL["engine"]["max_queue"] >= MIX["clients"]
    assert REAL["check"]["control_precision"] == "int8"
    assert 0 < REAL["check"]["logit_gap_mean"] < REAL["check"]["logit_gap_max"]


def test_the_program_builds_the_configuration_as_published():
    from benchmark.drivers import serve_solar_open2
    cfg = serve_solar_open2.model_config(REAL)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (4096, 64, 8, 128)
    assert (cfg.linear_heads, cfg.linear_head_dim, cfg.linear_dim,
            cfg.conv_taps) == (64, 128, 8192, 4)
    assert (cfg.n_routed_experts, cfg.experts_held,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size) == (
        320, (0, 40), 8, 1280)
    assert cfg.vocab_size == 24576 and cfg.num_hidden_layers == 4
    assert cfg.gqa_layers == (0,) and cfg.max_seq_len == 8192


def test_flops_against_hand_counts():
    c = REAL
    kda = (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64)
    assert fl.kda_matmul_params(c) == kda == 137_625_600
    assert fl.full_matmul_params(c) == 3 * 4096 * 8192 + 2 * 4096 * 1024 \
        == 109_051_904
    assert fl.expert_params(c) == 3 * 4096 * 1280 == 15_728_640
    assert fl.moe_fixed_matmul_params(c) == 4096 * 320 + 15_728_640
    # the issue's arithmetic: 3308 M parameters, 6.62 GB in bfloat16
    assert fl.param_count(c) == pytest.approx(3308e6, rel=1e-3)
    assert fl.rule_state_bytes(c) == 64 * 128 * 128 * 4 == 4_194_304
    assert fl.state_bytes_per_slot_layer(c) == 4_194_304 + 3 * 3 * 8192 * 2 \
        == 4_341_760
    assert fl.kv_bytes_per_token(c) == 4096
    assert fl.counts(c) == {"full": 1, "kda": 3}
    # 256 rows that each pick 8 of 320 touch every held expert
    assert fl.experts_touched(c, 256) == pytest.approx(40, abs=0.1)
    assert fl.experts_touched(c, 1) == pytest.approx(1.0)
    assert fl.pairs_landed(c, 256) == 256
    lanes, cached = 256, 256 * 1500
    weights = fl.weight_bytes(c, lanes)
    assert weights == pytest.approx(6.42e9, rel=1e-2)   # no embedding rows
    assert fl.decode_step_bytes(c, lanes, cached) == pytest.approx(
        weights + 2 * lanes * 3 * 4_341_760 + (lanes + cached) * 4096)
    # the issue's ~14.4 GB a step: bytes bound it, not operations
    assert 14.2e9 < fl.decode_step_bytes(c, lanes, cached) < 14.9e9
    assert fl.decode_step_flops(c, lanes, cached) / 197e12 \
        < fl.decode_step_bytes(c, lanes, cached) / 819e9 / 4
    # the rule alone, one layer: each state in and out once, five rows
    assert fl.rule_decode_bytes(c, lanes) == lanes * (
        2 * 4_194_304 + 5 * 8192 * 4)
    assert fl.rule_flops_per_token_layer(c) == 7 * 64 * 128 * 128
    # a chunk with the step aboard streams the weights ONCE
    mixed = fl.mixed_step_bytes(c, 256, 256, lanes, cached)
    apart = (fl.prefill_chunk_bytes(c, 256, 256)
             + fl.decode_step_bytes(c, lanes, cached))
    assert mixed == pytest.approx(apart - weights, rel=1e-2)
    assert fl.mixed_step_flops(c, 256, 256, lanes, cached) == \
        fl.prefill_chunk_flops(c, 256, 256) \
        + fl.decode_step_flops(c, lanes, cached)
