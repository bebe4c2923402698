"""The five readers the ``smallthinker`` training cell added, each on a
synthetic ``ctx`` with a synthetic recording in the place of
``program_spans.recording`` (what each finds, and that each returns None and
does not raise where there is nothing to read: the parent's program, an
untraced run, another architecture's cell), and on a small trace recorded on
the chip (``lib/testdata/program_fit_smallthinker.xplane.pb``: two steps of
the tiny ``KERNEL`` preset through the cell's own driver, heads of 128, 2048
tokens a row against a window of 512, with the run's ``ctx`` beside it).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_smallthinker_readers.py -q
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.layer_metrics import (full_attn_train_share_pct,  # noqa: E402
                                     full_flash_roofline_pct,
                                     moe_train_share_pct,
                                     smallthinker_train_mfu_pct,
                                     window_attn_train_share_pct,
                                     window_flash_roofline_pct)
from benchmark.lib import (flash_scopes, flops_smallthinker,  # noqa: E402
                           harness, program_spans, trace_reduce)

DATA = os.path.join(ROOT, "benchmark", "lib", "testdata")
NEW = ["smallthinker_train_mfu_pct", "window_flash_roofline_pct",
       "full_flash_roofline_pct", "window_attn_train_share_pct",
       "full_attn_train_share_pct"]

with open(os.path.join(ROOT, "benchmark", "configs",
                       "smallthinker-21b-a3b-L4-ep8.json")) as f:
    CONFIG = json.load(f)


def ctx(**over):
    base = {"kind": "fit", "config": CONFIG, "chips": 1,
            "device_kind": "TPU v5 lite", "window_s": 50.0,
            "tokens": 80 * 16384, "epochs": 8, "steps_per_epoch": 10,
            "batch": 1, "seq_len": 16384, "trace": {"devices": {"d": {}}},
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
        scopes[name] = path + ":custom-call"
        t += secs
    rec = {"spans": {"fit.dispatch": [(10.0, 10.1)]}, "ops": events,
           "modules": [("jit_engine_train_step(1)", 10.0, 11.0)],
           "scopes": scopes}
    monkeypatch.setattr(program_spans, "recording", lambda _ctx: rec)
    return rec


def kernel(name, n_in, results, layout="{2,1,0:T(8,128)(2,1)}"):
    shapes = ", ".join(r + layout for r in results)
    shape = f"({shapes})" if len(results) > 1 else shapes
    operands = ", ".join(f"%p.{i}" for i in range(n_in))
    return (f"%{name} = {shape} custom-call({operands}), "
            'custom_call_target="tpu_custom_call"')


STEP = "jit(engine_train_step)/jit(main)/"
QKV, LSE = "bf16[1,16384,3584]", "f32[1,28,1,16384]"


def flash_ops(scope, n, fwd, dq, dkv):
    """``n`` layers' three kernels under ``scope`` as the step names them:
    forward under ``jvp``, backward under the rematerialised transpose."""
    back = (STEP + "transpose(jvp(checkpoint))/rematted_computation/"
            + scope + "/")
    out = []
    for i in range(n):
        out += [(kernel(f"flash_fwd.{scope}{i}", 3, [QKV, LSE]), fwd,
                 STEP + f"jvp(checkpoint)/{scope}/flash_fwd"),
                (kernel(f"flash_dq.{scope}{i}", 6, [QKV]), dq,
                 back + "flash_dq"),
                (kernel(f"flash_dkv.{scope}{i}", 6, [QKV, QKV]), dkv,
                 back + "flash_dkv")]
    return out


OPS = (flash_ops("attn.full", 1, 0.030, 0.050, 0.040)
       + flash_ops("attn.window", 3, 0.014, 0.022, 0.018)
       + [("%fusion.1 = bf16[8] fusion()", 0.05,
           STEP + "jvp(checkpoint)/attn.window/dot_general"),
          ("%fusion.2 = f32[8] fusion()", 0.04,
           STEP + "jvp(checkpoint)/moe/moe.router/dot_general"),
          (kernel("ragged-dot-none.3", 3, ["f32[98304,768]"],
                  "{1,0:T(8,128)}"), 0.10, "ragged-dot-none"),
          ("%fusion.9 = f32[8] fusion()", 0.20, STEP + "optimizer/mul")])


def test_flash_calls_are_told_by_scope_and_signature(monkeypatch):
    recording(monkeypatch, OPS)
    full = flash_scopes.calls_under(ctx(), "attn.full")
    assert {k: len(v) for k, v in full.items()} == {"fwd": 1, "dq": 1,
                                                    "dkv": 1}
    window = flash_scopes.calls_under(ctx(), "attn.window")
    assert {k: len(v) for k, v in window.items()} == {"fwd": 3, "dq": 3,
                                                      "dkv": 3}
    assert sum(map(sum, window.values())) == pytest.approx(3 * 0.054)
    # the grouped matmul is a Mosaic call of three operands too: no scope
    assert flash_scopes.calls_under(ctx(), "moe") == {}


def test_rooflines_bill_the_pairs_inside_each_layers_mask(monkeypatch):
    """28 sequences of 16 384 at head width 128: one matmul over the causal
    triangle is 2 x 28 x 134 225 920 x 128 operations, over the window's
    band 2 x 28 x 58 722 304 x 128; forward 2, dQ 3, dK/dV 2 of them; all
    bound by operations at this length."""
    recording(monkeypatch, OPS)
    one = 2.0 * 28 * 134_225_920 * 128 / 197e12
    assert full_flash_roofline_pct.read(ctx()) == pytest.approx(
        100 * 7 * one / 0.120, rel=1e-6)
    band = 2.0 * 28 * 58_722_304 * 128 / 197e12
    got = window_flash_roofline_pct.read(ctx())
    assert got == pytest.approx(100 * 3 * 7 * band / (3 * 0.054), rel=1e-6)
    assert 0 < got < 100
    assert flops_smallthinker.flash_call_flops("fwd", 28, 16384, 128, 4096) \
        / flops_smallthinker.flash_call_flops("fwd", 28, 16384, 128) \
        == pytest.approx(0.4375, abs=1e-4)


def test_shares_read_the_scopes(monkeypatch):
    recording(monkeypatch, OPS)
    assert full_attn_train_share_pct.read(ctx()) == pytest.approx(12.0)
    assert window_attn_train_share_pct.read(ctx()) == pytest.approx(
        100 * (3 * 0.054 + 0.05))
    # the router's operations, entered before the attention's scope, and
    # the scopeless grouped kernel are the expert layer's
    assert moe_train_share_pct.read(ctx()) == pytest.approx(14.0)


def test_mfu_is_tokens_times_operations_over_the_peak():
    even = [[80 * 1536] * 8 + [80 * 8 * 1536, 80 * 98304] for _ in range(4)]
    got = smallthinker_train_mfu_pct.read(ctx(expert_load=even))
    per_token = flops_smallthinker.train_flops_per_token(CONFIG, 16384)
    assert got == pytest.approx(
        100 * per_token * 80 * 16384 / 50.0 / 197e12, rel=1e-6)
    assert 0 < got < 100
    # attention over the pairs inside each layer's mask: 134.2 M + 3 x 58.7 M
    pairs = 134_225_920 + 3 * 58_722_304
    attn = 3 * 4 * 3584 * pairs / 16384
    rest = per_token - attn
    assert attn / per_token == pytest.approx(0.49, abs=0.03)
    assert rest > 0
    # the counter says twice the even split landed in each of 4 layers
    twice = [[80 * 3072] * 8 + [80 * 8 * 3072, 80 * 98304] for _ in range(4)]
    more = smallthinker_train_mfu_pct.read(ctx(expert_load=twice))
    extra = 3 * 4 * 80 * 16384 * 0.75 * 2.0 * 3 * 2560 * 768
    assert more == pytest.approx(got + 100 * extra / 50.0 / 197e12, rel=1e-6)


def test_nothing_to_read_gives_none(monkeypatch):
    """No trace, no counter, a serving cell, another architecture's
    configuration, a step without the scopes or with a kernel missing: None,
    never an exception."""
    for c in (ctx(trace=None), ctx(kind="serve"),
              ctx(config={"arch": "lfm2_moe", "num_attention_heads": 32})):
        assert {n: harness.read_layer_metric(n, c) for n in NEW} \
            == dict.fromkeys(NEW)
    recording(monkeypatch, [OPS[-1]])           # a step with no attention
    assert {n: harness.read_layer_metric(n, ctx()) for n in NEW} \
        == dict.fromkeys(NEW)
    recording(monkeypatch, [op for op in OPS if "flash_dq" not in op[0]])
    assert window_flash_roofline_pct.read(ctx()) is None
    assert full_flash_roofline_pct.read(ctx()) is None
    assert window_attn_train_share_pct.read(ctx()) is not None
    # another architecture's traced step under this reader: nothing
    recording(monkeypatch, OPS)
    other = ctx(config={"arch": "lfm2_moe", "num_attention_heads": 32})
    assert window_flash_roofline_pct.read(other) is None
    assert smallthinker_train_mfu_pct.read(other) is None


# ------------------------------------------------- the recorded training
def recorded_ctx(monkeypatch):
    with open(os.path.join(DATA, "program_fit_smallthinker.ctx.json")) as f:
        ctx = json.load(f)
    path = os.path.join(DATA, "program_fit_smallthinker.xplane.pb")
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    return dict(ctx, trace=trace_reduce.reduce(path))


def test_readers_on_the_recorded_smallthinker_trace(monkeypatch):
    """Two traced steps of the tiny ``KERNEL`` preset on a v5e through the
    cell's own driver (one full + NoPE layer, three of window 512 over 2048
    tokens, heads of 128, grouped ReGLU experts): the scopes are in the
    step's ``op_name``s as the readers look for them, each layer kind runs
    the three flash kernels under its own scope, and every reader of the
    cell finds a number (the chip's own readings of that run: window 47.4,
    full 55.8, shares 9.9 / 3.4 / 77.4, load 1.67)."""
    ctx = recorded_ctx(monkeypatch)
    rec = program_spans.recording(ctx)
    scopes = " ".join(rec["scopes"].values())
    for scope in ("embed", "attn.full", "attn.window", "moe/moe.router",
                  "moe/moe.experts", "loss", "optimizer"):
        assert f"{scope}/" in scopes or f"{scope})" in scopes, scope
    assert "attn.window/moe.router" not in scopes
    assert "attn.full/moe.router" not in scopes
    steps = ctx["traced_steps"]
    full = flash_scopes.calls_under(ctx, "attn.full")
    window = flash_scopes.calls_under(ctx, "attn.window")
    assert {k: len(v) for k, v in full.items()} == dict.fromkeys(
        ("fwd", "dq", "dkv"), steps)
    assert {k: len(v) for k, v in window.items()} == dict.fromkeys(
        ("fwd", "dq", "dkv"), 3 * steps)
    # a window of 512 in 2048 tokens: 0.4375 of the triangle's pairs, and
    # the window layer's kernels take less time than the full layer's
    assert sum(map(sum, window.values())) / 3 < sum(map(sum, full.values()))
    got = {name: harness.read_layer_metric(name, ctx)
           for name in NEW + ["moe_train_share_pct",
                              "moe_train_load_max_over_mean",
                              "lm_head_loss_ms_per_step",
                              "optimizer_ms_per_step"]}
    assert got["window_flash_roofline_pct"] == pytest.approx(47.43, abs=0.05)
    assert got["full_flash_roofline_pct"] == pytest.approx(55.79, abs=0.05)
    assert 0 < got["window_flash_roofline_pct"] < 100
    assert got["window_attn_train_share_pct"] == pytest.approx(9.86, abs=0.05)
    assert got["full_attn_train_share_pct"] == pytest.approx(3.45, abs=0.05)
    assert got["moe_train_share_pct"] == pytest.approx(77.36, abs=0.05)
    assert got["moe_train_load_max_over_mean"] == pytest.approx(1.674,
                                                                abs=0.01)
    assert 0 < got["smallthinker_train_mfu_pct"] < 5      # a tiny model
    assert got["lm_head_loss_ms_per_step"] > 0
    assert got["optimizer_ms_per_step"] > 0


@pytest.mark.parametrize("file,kind", [("program_fit.xplane.pb", "fit"),
                                       ("program_serve.xplane.pb", "serve"),
                                       ("small_trace.xplane.pb", "fit")])
def test_other_programs_traces_give_none(monkeypatch, file, kind):
    """GPT-2's recorded training step (flash kernels under ``attn``, no
    ``attn.window`` / ``attn.full``), a served model's, a trace without the
    program's spans, each under THIS configuration and under its own: None,
    never an exception (the parent is measured with these readers laid over
    it)."""
    path = os.path.join(DATA, file)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: path)
    for config in (CONFIG, {"arch": "gpt2", "n_head": 4, "n_embd": 64}):
        c = ctx(kind=kind, config=config, trace=trace_reduce.reduce(path))
        assert {n: harness.read_layer_metric(n, c) for n in NEW} \
            == dict.fromkeys(NEW)
