"""That the ``lfm2_moe`` training cell's ``correct`` can come out false: the
driver at tiny size, sound and with the timed step broken underneath, and the
control (the reference with float8 weight matmuls) failing a limit at a size
where the rounding shows. CPU, tiny presets; the limits here are the tiny
presets' own.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct_lfm2_moe.py -q
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

from benchmark.tests import tiny, tiny_lfm2_moe  # noqa: E402
from benchmark.tests.test_correct import _HalfBatch  # noqa: E402


class _Unchanged(_HalfBatch):
    """A step that returns its state unchanged (the loss is still real). The
    Engine's step donates what it is given, so what goes back is a copy
    taken before the call."""

    def __call__(self, params, opt_state, lr, x, y):
        import jax
        import jax.numpy as jnp
        kept = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        loss, _p, _s = self.step(params, opt_state, lr, x, y)
        return (loss, *kept)


def _devices():
    import jax
    return jax.devices()[:1]


def _cell(cfg=None, mix=None):
    return tiny.cell(cfg or tiny_lfm2_moe.LFM2, mix or tiny_lfm2_moe.FIT)


def test_sound_run_is_correct_and_carries_no_counter():
    from benchmark.drivers import fit_lfm2_moe
    out = fit_lfm2_moe.run(_cell(), 11, 0.5, False, _devices(),
                           time.perf_counter())
    assert out["correct"], out["numbers"]
    assert out["ctx"]["kind"] == "fit"
    assert out["ctx"]["expert_load"] is None
    assert out["ctx"]["expert_load_traced"] is None
    assert out["metrics"]["train_tokens_per_s"] > 0
    assert out["metrics"]["setup_s"] > 0


def test_traced_run_reads_the_steps_own_counter():
    """``--trace 1`` switches metrics on before the step is built: the one
    step program carries the counter, every selected pair is in it, and the
    load reader has something to read."""
    import paddle_tpu as paddle
    from benchmark.drivers import fit_lfm2_moe
    from benchmark.layer_metrics import moe_train_load_max_over_mean
    try:
        out = fit_lfm2_moe.run(_cell(), 13, 0.3, True, _devices(),
                               time.perf_counter())
    finally:
        paddle.set_flags({"FLAGS_enable_metrics": False})
    assert out["correct"], out["numbers"]
    assert out["numbers"]["no_pair_dropped"] == 0.0
    load = out["ctx"]["expert_load"]
    assert len(load) == 4 and len(load[0]) == 4 + 2
    # the traced (last) epoch's share of it, over ``traced_steps`` steps
    traced = out["ctx"]["expert_load_traced"]
    steps, per_step = out["ctx"]["traced_steps"], load[0][-1] // (
        out["ctx"]["epochs"] * out["ctx"]["steps_per_epoch"])
    assert [row[-1] for row in traced] == [steps * per_step] * 4
    assert all(0 <= t <= w for rt, rw in zip(traced, load)
               for t, w in zip(rt, rw))
    worst = moe_train_load_max_over_mean.read(out["ctx"])
    assert 1.0 <= worst < 2.0


@pytest.mark.parametrize("broken,caught_by", [
    (_Unchanged, "delta_norm_gap"), (_HalfBatch, "loss_gap_step1")])
def test_broken_step_is_not_correct(broken, caught_by):
    from benchmark.drivers import fit_lfm2_moe
    cell = _cell()
    out = fit_lfm2_moe.run(cell, 12, 0.3, False, _devices(),
                           time.perf_counter(), break_step=broken)
    assert not out["correct"]
    assert out["numbers"][caught_by] > cell["config"]["check"][caught_by]


def test_control_fails_a_limit_at_test_size():
    """float8 weight matmuls in the reference, at a width where the rounding
    shows (hidden 256, 128 tokens a row): the control fails the
    gradient-norm limit of that size, which the program's own readings pass
    (program 0.0057-0.0058, control 0.0137-0.0173 over seeds 1-2 on CPU)."""
    from benchmark.drivers import fit_lfm2_moe
    cfg = copy.deepcopy(tiny_lfm2_moe.LFM2)
    cfg.update(hidden_size=256, intermediate_size=512,
               moe_intermediate_size=128, vocab_size=1024)
    cfg["check"]["grad_norm_gap"] = 0.009
    cell = _cell(cfg, dict(tiny_lfm2_moe.FIT, seq_len=128))
    sound = fit_lfm2_moe.run(cell, 1, 0.3, False, _devices(),
                             time.perf_counter())
    assert sound["numbers"]["grad_norm_gap"] < cfg["check"]["grad_norm_gap"]
    low = fit_lfm2_moe.control(cell, 1, _devices())
    assert low["grad_norm_gap"] > cfg["check"]["grad_norm_gap"]
    assert low["grad_norm_gap"] > 2 * sound["numbers"]["grad_norm_gap"]


def test_a_program_without_the_model_fails_before_the_reference(monkeypatch):
    """Over a ``paddle_tpu`` that lacks ``models.lfm2_moe`` (the parent of
    the PR that added it) the run raises at once: no reference, no engine."""
    import builtins

    from benchmark.drivers import fit_lfm2_moe
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name == "paddle_tpu.models.lfm2_moe":
            raise ImportError(f"No module named {name!r}")
        return real(name, *a, **k)

    monkeypatch.delitem(sys.modules, "paddle_tpu.models.lfm2_moe",
                        raising=False)
    monkeypatch.setattr(builtins, "__import__", no_model)
    monkeypatch.setattr(fit_lfm2_moe, "reference_numbers",
                        lambda *a, **k: pytest.fail("the reference ran"))
    with pytest.raises(ImportError):
        fit_lfm2_moe.run(_cell(), 1, 0.1, False, _devices(),
                         time.perf_counter())
