"""The six readers the ``lfm2_moe`` training cell added, each on a synthetic
``ctx`` (and, for the trace-fed ones, a synthetic recording in the place of
``program_spans.recording``): what each finds, and that each returns None and
does not raise where there is nothing to read (the parent's program, an
untraced run, another architecture's cell).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lfm2_readers.py -q
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.layer_metrics import (fit_startup_s,  # noqa: E402
                                     lfm2_flash_attn_roofline_pct,
                                     lfm2_train_mfu_pct,
                                     moe_grouped_roofline_pct,
                                     moe_train_load_max_over_mean,
                                     moe_train_share_pct,
                                     short_conv_share_pct)
from benchmark.lib import program_spans, train_scopes  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2-8b-a1b-L6-ep4.json")) as f:
    CONFIG = json.load(f)


def ctx(**over):
    base = {"kind": "fit", "config": CONFIG, "chips": 1,
            "device_kind": "TPU v5 lite", "window_s": 40.0,
            "tokens": 100 * 16384, "epochs": 10, "steps_per_epoch": 10,
            "batch": 2, "seq_len": 8192, "trace": {"devices": {"d": {}}},
            "traced_steps": 10, "expert_load": None,
            "expert_load_traced": None}
    base.update(over)
    return base


def recording(monkeypatch, ops):
    """One train step of 1 s holding ``ops``: (instruction, seconds,
    op_name path)."""
    events, scopes, t = [], {}, 10.0
    for name, secs, path in ops:
        events.append((name, t, t + secs))
        scopes[name] = path + ":fusion"
        t += secs
    rec = {"spans": {"fit.dispatch": [(10.0, 10.1)]}, "ops": events,
           "modules": [("jit_engine_train_step(1)", 10.0, 11.0),
                       ("jit_other(2)", 12.0, 13.0)],
           "scopes": scopes}
    monkeypatch.setattr(program_spans, "recording", lambda _ctx: rec)
    return rec


STEP = "jit(engine_train_step)/jit(main)/"
OPS = [
    ("%fusion.1 = f32[8] fusion()", 0.10, STEP + "jvp(moe)/moe.router/dot"),
    ("%fusion.2 = f32[8] fusion()", 0.05,
     STEP + "transpose(jvp(jvp()))/checkpoint/moe/moe.experts/moe.group/x"),
    ("%ragged-dot-metadata.1 = (s32[9]) custom-call()", 0.01,
     "ragged-dot-metadata"),
    ("%ragged-dot-none.3 = f32[65536,1792]{1,0:T(8,128)} custom-call()",
     0.10, "ragged-dot-none"),
    ("%ragged-dot-none.4 = f32[65536,2048]{1,0:T(8,128)} custom-call()",
     0.10, "ragged-dot-none"),
    ("%ragged-dot-none.9 = f32[8,2048,1792]{2,1,0:T(8,128)} custom-call()",
     0.04, "ragged-dot-none"),
    ("%fusion.7 = bf16[8] fusion()", 0.20, STEP + "jvp(short_conv)/mul"),
    ("%fusion.8 = bf16[8] fusion()", 0.10,
     STEP + "transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "short_conv/dot_general"),
    ("%fusion.9 = f32[8] fusion()", 0.30, STEP + "optimizer/mul"),
]


def even_load(steps, pairs=2048):
    """``steps`` steps of 4 layers in which every held expert got ``pairs``
    (the tokens an expert counted stand in for its pairs)."""
    return [[steps * pairs] * 8 + [steps * 8 * pairs, steps * 65536]
            for _ in range(4)]


def test_mfu_is_tokens_times_operations_over_the_peak():
    got = lfm2_train_mfu_pct.read(ctx(expert_load=even_load(100)))
    per_token = 3 * 554.258432e6
    assert got == pytest.approx(
        100 * per_token * 100 * 16384 / 40.0 / 197e12, rel=1e-6)
    assert 0 < got < 100
    # the counter says twice the even split landed in each of 4 layers
    more = lfm2_train_mfu_pct.read(ctx(expert_load=even_load(100, 4096)))
    extra = 3 * 4 * 100 * 16384 * 2.0 * 3 * 2048 * 1792
    assert more == pytest.approx(got + 100 * extra / 40.0 / 197e12, rel=1e-6)
    # no counter, no reading: the even split would under-bill a window in
    # which the router drifts towards the held experts
    assert lfm2_train_mfu_pct.read(ctx()) is None
    assert lfm2_train_mfu_pct.read(ctx(kind="serve")) is None
    assert lfm2_train_mfu_pct.read(
        ctx(config={"arch": "gpt2", "n_embd": 8})) is None


def test_moe_share_counts_the_scope_and_the_scopeless_kernels(monkeypatch):
    recording(monkeypatch, OPS)
    assert moe_train_share_pct.read(ctx()) == pytest.approx(
        100 * (0.10 + 0.05 + 0.01 + 0.10 + 0.10 + 0.04) / 1.0)
    assert short_conv_share_pct.read(ctx()) == pytest.approx(30.0)


def test_shares_find_nothing_without_a_trace_or_the_scopes(monkeypatch):
    assert moe_train_share_pct.read(ctx(trace=None)) is None
    assert short_conv_share_pct.read(ctx(kind="serve")) is None
    recording(monkeypatch, [OPS[-1]])          # a step with neither layer
    assert moe_train_share_pct.read(ctx()) is None
    assert short_conv_share_pct.read(ctx()) is None
    assert moe_grouped_roofline_pct.read(ctx()) is None


def test_grouped_calls_are_told_by_name_and_sized_by_result(monkeypatch):
    recording(monkeypatch, OPS)
    calls = train_scopes.grouped_calls(ctx())
    assert [shape for shape, _s in calls] == [
        (65536, 1792), (65536, 2048), (8, 2048, 1792)]
    assert sum(s for _shape, s in calls) == pytest.approx(0.24)


def test_grouped_roofline_bills_the_landed_pairs(monkeypatch):
    """Three calls of 2 x 16384 x 2048 x 1792 operations each, whichever way
    they are turned: 0.6104 ms a call at the published peak."""
    recording(monkeypatch, OPS)
    even = moe_grouped_roofline_pct.read(
        ctx(expert_load_traced=even_load(10)))
    one = 2.0 * 16384 * 2048 * 1792 / 197e12
    assert even == pytest.approx(100 * 3 * one / 0.24, rel=1e-6)
    # 10 traced steps x 4 layers of 2000 pairs an expert
    load = even_load(10, 2000)
    counted = moe_grouped_roofline_pct.read(ctx(expert_load_traced=load))
    assert counted == pytest.approx(even * 2000 / 2048, rel=1e-6)
    assert moe_grouped_roofline_pct.group_sizes(
        ctx(expert_load_traced=load)) == [2000.0] * 8
    # without the traced steps' counter there is nothing to divide
    assert moe_grouped_roofline_pct.read(
        ctx(expert_load=even_load(100))) is None


def test_grouped_roofline_reads_the_load_of_the_traced_steps(monkeypatch):
    """A load that drifts: 2048 pairs an expert a layer in the window's
    first epochs, 3600 in the traced last one. The calls the trace timed
    multiplied the last epoch's pairs, so those are what is billed, not the
    window's mean."""
    recording(monkeypatch, OPS)
    window = [[9 * 10 * 2048 + 10 * 3600] * 8
              + [8 * (9 * 10 * 2048 + 10 * 3600), 100 * 65536]
              for _ in range(4)]
    drifting = ctx(expert_load=window,
                   expert_load_traced=even_load(10, 3600))
    assert moe_grouped_roofline_pct.group_sizes(drifting) == [3600.0] * 8
    steady = ctx(expert_load=even_load(100),
                 expert_load_traced=even_load(10))
    assert moe_grouped_roofline_pct.read(drifting) == pytest.approx(
        moe_grouped_roofline_pct.read(steady) * 3600 / 2048, rel=1e-6)


FLASH = {   # trace["ops"] as trace_reduce keeps it: name -> (calls, seconds)
    "%flash_fwd.2 tpu_custom_call/3 (bf16[64,8192,128],f32[64,8192,1])":
        (20, 0.18),
    "%flash_dq.1 tpu_custom_call/6 bf16[64,8192,128]": (10, 0.10),
    "%flash_dkv.1 tpu_custom_call/6 (bf16[64,8192,128],bf16[64,8192,128])":
        (10, 0.14),
    "%ragged-dot-none.3 tpu_custom_call/3 f32[65536,1792]": (120, 0.9),
    "%fusion.7 fusion": (10, 0.2),
}


def test_flash_roofline_takes_heads_and_width_from_the_published_keys():
    """2 x 32 sequences of 8192 at head width 64: one causal matmul is
    2 x 64 x 8192 x 8193 / 2 x 64 operations; forward 2, dQ 3, dK/dV 2 of
    them; all three are bound by operations at this length."""
    one = 2.0 * 64 * 8192 * 8193 / 2 * 64 / 197e12
    got = lfm2_flash_attn_roofline_pct.read(ctx(trace={"ops": FLASH}))
    assert got == pytest.approx(
        100 * (20 * 2 + 10 * 3 + 10 * 2) * one / 0.42, rel=1e-6)
    assert 0 < got < 100
    # one kernel missing, no trace, another architecture: nothing
    two = {k: v for k, v in FLASH.items() if "flash_dq" not in k}
    assert lfm2_flash_attn_roofline_pct.read(ctx(trace={"ops": two})) is None
    assert lfm2_flash_attn_roofline_pct.read(ctx(trace=None)) is None
    assert lfm2_flash_attn_roofline_pct.read(
        ctx(config={"arch": "gpt2", "n_embd": 8, "n_head": 2},
            trace={"ops": FLASH})) is None


def test_fit_startup_reads_what_the_driver_hands_every_fit_cell():
    """``fit_startup_s`` needs ``fit_call_s``, ``epoch_starts`` and
    ``t_end``: 0.5 s before the first of 20 steps of 0.1 s."""
    got = fit_startup_s.read(ctx(
        epochs=2, fit_call_s=2.5, epoch_starts=[10.5, 11.5], t_end=12.5))
    assert got == pytest.approx(0.5)


def test_load_reader_takes_the_worst_layer():
    load = [[10, 10, 10, 10, 40, 160], [5, 5, 5, 25, 40, 160]]
    assert moe_train_load_max_over_mean.read(ctx(expert_load=load)) == 2.5
    assert moe_train_load_max_over_mean.read(ctx()) is None
    assert moe_train_load_max_over_mean.read({"kind": "serve"}) is None
