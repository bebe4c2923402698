"""The hybrid (``nemotron_h``) serving kind at a size a test run holds: a
sound run is ``correct``, a served token altered is not, the configuration's
file keeps the published keys, and the operation and byte counts against
hand counts. CPU, the tiny preset of ``tiny_hybrid.py``.

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

from benchmark.lib import flops_nemotron_h as fl  # noqa: E402
from benchmark.tests import tiny, tiny_hybrid  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "nemotron3s-reason-saturated"
REAL = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "nemotron-3-super-L11-ep4.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _devices():
    import jax
    return jax.devices()[:1]


@pytest.mark.parametrize("mix", [tiny.OPEN, tiny.CLOSED],
                         ids=["open", "closed"])
def test_sound_hybrid_run_is_correct(mix):
    from benchmark.drivers import serve_hybrid
    out = serve_hybrid.run(tiny.cell(tiny_hybrid.NEMOTRON, mix), 21, 1.5,
                           False, _devices(), time.perf_counter())
    assert out["correct"], out["numbers"]
    assert out["ctx"]["kind"] == "serve"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_tokens_per_s"] > 0
    load = out["ctx"]["window"]["expert_load"]
    assert len(load) == 2 and all(sum(layer) > 0 for layer in load)
    from benchmark.lib import harness
    assert harness.read_layer_metric("moe_expert_load_max_over_mean",
                                     out["ctx"]) >= 1.0


def test_altered_hybrid_token_is_not_correct():
    from benchmark.drivers import serve_hybrid

    def alter(rec, position, token):
        return (token + 1) % 251 if position == 1 else token

    out = serve_hybrid.run(
        tiny.cell(tiny_hybrid.NEMOTRON, tiny.OPEN), 22, 1.5, False,
        _devices(), time.perf_counter(), alter_token=alter)
    assert not out["correct"]
    assert out["numbers"]["logit_gap_max"] > \
        tiny_hybrid.NEMOTRON["check"]["logit_gap_max"]


def test_hybrid_control_fails_the_limit_at_test_size():
    """int8 weights in the reference's place, at a size where the rounding
    shows (hidden 1024, two periods ``MEM*E``, 64 experts top-8 of which 16
    are held, vocab 16384; at hidden 64 int8 weights are as close to
    float32 as bf16 arithmetic is): the mean gap of its first choices fails
    the limit of that size, which the program's served tokens pass (program
    0.00011-0.00033, control 0.00070-0.00125 over seeds 1-3 on CPU)."""
    from benchmark.drivers import serve_hybrid
    pattern = "MEM*EMEM*E"
    cfg = dict(tiny_hybrid.NEMOTRON, hidden_size=1024, vocab_size=16384,
               hybrid_override_pattern=pattern,
               num_hidden_layers=len(pattern), num_attention_heads=8,
               num_key_value_heads=2, head_dim=128, mamba_num_heads=32,
               mamba_head_dim=64, n_groups=2, ssm_state_size=64,
               chunk_size=32, n_routed_experts=16, router_width=64,
               experts_held=[0, 16], num_experts_per_tok=8,
               moe_latent_size=256, moe_intermediate_size=512,
               moe_shared_expert_intermediate_size=1024,
               initializer_range=0.02)
    cfg["check"] = dict(cfg["check"], logit_gap_mean=5e-4)
    mix = dict(tiny.OPEN, check_requests=24, rate_rps=4.0)
    got = serve_hybrid.control(tiny.cell(cfg, mix), 2, _devices(), 8.0)
    assert got["program"]["logit_gap_mean"] < cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > \
        3 * got["program"]["logit_gap_mean"]


def test_the_other_kinds_functions_are_put_back():
    """``serve_hybrid.run`` borrows ``drivers/serve.py``'s ``run`` with its
    own build / drive / comparison in place; afterwards the dense kind's
    are its own again."""
    from benchmark.drivers import serve, serve_hybrid
    mine = (serve.build, serve.drive, serve.compare_with_reference)
    with serve_hybrid._in_serves_place():
        assert serve.build is serve_hybrid.build
    assert (serve.build, serve.drive, serve.compare_with_reference) == mine
    assert serve.build.__module__ == "benchmark.drivers.serve"


# ------------------------------------------------------ the configuration
def test_cell_and_its_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-closed-128"
    reports = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"setup_s", "serve_tokens_per_s", "itl_p95_ms", "compile_s",
            "hybrid_decode_roofline_pct", "moe_share_pct", "mamba_share_pct",
            "moe_expert_load_max_over_mean", "decode_step_device_ms",
            "prefill_chunk_device_ms", "tick_host_exposed_ms",
            "batch_occupancy_pct", "decode_tick_ms",
            "idle_attributed_pct_serve"} <= reports
    assert "decode_step_roofline_pct" not in reports   # its bytes are Llama's
    # no metric is left to every cell by default
    assert all("workloads" in m for m in BENCH["per_layer"])


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_keeps_the_published_keys():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert REAL["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if REAL.get(k) != v}
    assert differs == set(REAL["reduced"]) | {"hybrid_override_pattern"}
    pattern = row["config"]["hybrid_override_pattern"]
    off = REAL["pattern_offset"]
    assert pattern[off:off + 11] == REAL["hybrid_override_pattern"]
    assert REAL["reduced_from"]["n_routed_experts"] == REAL["router_width"]
    lo, hi = REAL["experts_held"]
    assert hi - lo == REAL["n_routed_experts"] >= 8
    assert REAL["vocab_size"] * 8 >= row["config"]["vocab_size"]


def test_flops_against_hand_counts():
    c = REAL
    mamba = 4096 * 18560 + 8192 * 4096
    assert fl.mamba_matmul_params(c) == mamba
    assert fl.mamba_layer_params(c) == mamba + 10240 * 5 + 3 * 128 \
        + 8192 + 4096 == 109_640_064
    assert fl.attention_matmul_params(c) == 2 * 4096 * 4096 \
        + 2 * 4096 * 256 == 35_651_584
    assert fl.moe_fixed_matmul_params(c) == 4096 * 512 + 2 * 4096 * 1024 \
        + 2 * 4096 * 5376 == 54_525_952
    assert fl.expert_params(c) == 2 * 1024 * 2688 == 5_505_024
    assert fl.kv_bytes_per_token(c) == 1024
    assert fl.state_bytes_per_slot_layer(c) == 3 * 10240 * 2 \
        + 128 * 64 * 128 * 4 == 4_255_744
    # 64 lanes picking 22 of 512 touch ~120 of the 128 held; one lane ~5.4
    assert 120 < fl.experts_touched(c, 64) < 121
    assert 5.3 < fl.experts_touched(c, 1) < 5.6
    assert fl.pairs_landed(c, 64) == 352
    held = (5 * 109_640_064 + 35_651_584 + 4096
            + 5 * (54_525_952 + 4096 + 512) + 4096 + 4096 * 32768)
    all_experts = 5 * 128 * 5_505_024
    touched = 5 * fl.experts_touched(c, 64) * 5_505_024
    assert fl.weight_bytes(c, 64) == pytest.approx(2 * (held + touched))
    assert 9.2e9 < 2 * (held + all_experts + 4096 * 32768) < 9.4e9
    state = 2 * 64 * 5 * 4_255_744
    assert fl.decode_step_bytes(c, 64, 50_000) == pytest.approx(
        2 * (held + touched) + state + 50_000 * 1024)
    # a chunk of one slot reads and writes one slot's state
    assert fl.prefill_chunk_bytes(c, 256, 256) == pytest.approx(
        fl.weight_bytes(c, 256) + 2 * 5 * 4_255_744 + 256 * 1024)
    per_lane = (2 * (5 * mamba + 35_651_584 + 5 * 54_525_952)
                + 5 * (5 * 128 * 64 * 128 + 2 * 4 * 10240)
                + 2 * 4096 * 32768)
    assert fl.decode_step_flops(c, 64, 0) == pytest.approx(
        64 * per_lane + 2 * 352 * 5_505_024 * 5)
