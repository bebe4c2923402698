"""That the ``smallthinker`` training cell's ``correct`` can come out false:
the driver at tiny size, sound and with the timed path broken underneath (a
step that changes nothing, half the batch left out, and the cell's own fault:
the window argument dropped from one layer, causal where the configuration
says window), and the control (the reference with float8 weight matmuls)
failing a limit at a size where the rounding shows. CPU, tiny presets; the
limits here are the tiny presets' own.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct_smallthinker.py -q
"""
from __future__ import annotations

import copy
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import tiny, tiny_smallthinker  # noqa: E402
from benchmark.tests.test_correct import _HalfBatch  # noqa: E402
from benchmark.tests.test_correct_lfm2_moe import _Unchanged  # noqa: E402


def _devices():
    import jax
    return jax.devices()[:1]


def _cell(cfg=None, mix=None):
    return tiny.cell(cfg or tiny_smallthinker.SMALLTHINKER,
                     mix or tiny_smallthinker.FIT)


def drop_the_window(lm):
    """The cell's fault: layer 1 attends to every key."""
    lm.model.layers[1].self_attn.window = None


def test_sound_run_is_correct_and_carries_no_counter():
    from benchmark.drivers import fit_smallthinker
    out = fit_smallthinker.run(_cell(), 11, 0.5, False, _devices(),
                               time.perf_counter())
    assert out["correct"], out["numbers"]
    assert out["ctx"]["kind"] == "fit"
    assert out["ctx"]["expert_load"] is None
    assert out["ctx"]["expert_load_traced"] is None
    assert out["metrics"]["train_tokens_per_s"] > 0
    assert out["metrics"]["setup_s"] > 0


def test_traced_run_reads_the_steps_own_counter():
    """``--trace 1`` switches metrics on before the step is built: the one
    step program carries the counter, every selected pair of all four
    layers is in it, and the readers that need it have something to read."""
    import paddle_tpu as paddle
    from benchmark.drivers import fit_smallthinker
    from benchmark.layer_metrics import (moe_train_load_max_over_mean,
                                         smallthinker_train_mfu_pct)
    try:
        out = fit_smallthinker.run(_cell(), 13, 0.3, True, _devices(),
                                   time.perf_counter())
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})
    assert out["correct"], out["numbers"]
    assert out["numbers"]["no_pair_dropped"] == 0.0
    load = out["ctx"]["expert_load"]
    assert len(load) == 4 and len(load[0]) == 4 + 2
    traced = out["ctx"]["expert_load_traced"]
    steps, per_step = out["ctx"]["traced_steps"], load[0][-1] // (
        out["ctx"]["epochs"] * out["ctx"]["steps_per_epoch"])
    assert [row[-1] for row in traced] == [steps * per_step] * 4
    assert 1.0 <= moe_train_load_max_over_mean.read(out["ctx"]) < 2.0
    ctx = dict(out["ctx"], device_kind="TPU v5 lite")
    assert smallthinker_train_mfu_pct.read(ctx) > 0


@pytest.mark.parametrize("fault,caught_by", [
    (dict(break_step=_Unchanged), "delta_norm_gap"),
    (dict(break_step=_HalfBatch), "loss_gap_step1"),
    (dict(break_model=drop_the_window), "delta_norm_gap")],
    ids=["unchanged", "half-batch", "window-dropped"])
def test_broken_run_is_not_correct(fault, caught_by):
    from benchmark.drivers import fit_smallthinker
    cell = _cell()
    out = fit_smallthinker.run(cell, 12, 0.3, False, _devices(),
                               time.perf_counter(), **fault)
    assert not out["correct"]
    assert out["numbers"][caught_by] > cell["config"]["check"][caught_by]


def test_control_fails_a_limit_at_test_size():
    """float8 weight matmuls in the reference, at a width where the rounding
    shows (hidden 256, 128 tokens a row): the control fails the
    gradient-norm limit of that size, which the program's own reading
    passes."""
    from benchmark.drivers import fit_smallthinker
    cfg = copy.deepcopy(tiny_smallthinker.SMALLTHINKER)
    cfg.update(hidden_size=256, head_dim=64, moe_ffn_hidden_size=128,
               vocab_size=1024, sliding_window_size=48,
               max_position_embeddings=128)
    cfg["check"]["grad_norm_gap"] = 0.009
    cell = _cell(cfg, dict(tiny_smallthinker.FIT, seq_len=128))
    sound = fit_smallthinker.run(cell, 1, 0.3, False, _devices(),
                                 time.perf_counter())
    assert sound["numbers"]["grad_norm_gap"] < cfg["check"]["grad_norm_gap"]
    low = fit_smallthinker.control(cell, 1, _devices())
    assert low["grad_norm_gap"] > cfg["check"]["grad_norm_gap"]
    assert low["grad_norm_gap"] > 2 * sound["numbers"]["grad_norm_gap"]


def test_a_program_without_the_model_fails_before_the_reference(monkeypatch):
    """Over a ``paddle_tpu`` that lacks ``models.smallthinker`` (the parent
    of the PR that added it) the run raises at once: no reference, no
    engine."""
    import builtins

    from benchmark.drivers import fit_smallthinker
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name == "paddle_tpu.models.smallthinker":
            raise ImportError(f"No module named {name!r}")
        return real(name, *a, **k)

    monkeypatch.delitem(sys.modules, "paddle_tpu.models.smallthinker",
                        raising=False)
    monkeypatch.setattr(builtins, "__import__", no_model)
    monkeypatch.setattr(fit_smallthinker, "reference_numbers",
                        lambda *a, **k: pytest.fail("the reference ran"))
    with pytest.raises(ImportError):
        fit_smallthinker.run(_cell(), 1, 0.1, False, _devices(),
                             time.perf_counter())
