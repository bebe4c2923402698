"""That ``correct`` can come out false: the control (the reference in the
next lower precision, in the program's place) at a size a test run holds, and
a run driven past the harness's look for a chip with the timed path broken
underneath. CPU, tiny presets; the limits here are the tiny presets' own.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import tiny  # noqa: E402


def _devices():
    import jax
    return jax.devices()[:1]


# ---------------------------------------------------------------- training
def test_sound_training_run_is_correct():
    from benchmark.drivers import fit
    out = fit.run(tiny.cell(tiny.GPT2, tiny.FIT), 11, 0.5, False, _devices(),
                  time.perf_counter())
    assert out["correct"], out["numbers"]
    assert out["metrics"]["train_tokens_per_s"] > 0
    assert out["metrics"]["setup_s"] > 0


class _Unchanged:
    """A step that returns its state unchanged (the loss is still real)."""

    def __init__(self, step):
        self.step = step

    def __call__(self, params, opt_state, lr, x, y):
        loss, _p, _s = self.step(params, opt_state, lr, x, y)
        return loss, params, opt_state

    def _cache_size(self):
        return self.step._cache_size()


class _HalfBatch(_Unchanged):
    """A step that leaves out half of the batch (feeds the first half twice)."""

    def __call__(self, params, opt_state, lr, x, y):
        import jax.numpy as jnp
        half = x.shape[0] // 2
        x = jnp.concatenate([x[:half], x[:half]])
        return self.step(params, opt_state, lr, x, x)


@pytest.mark.parametrize("broken,caught_by", [
    (_Unchanged, "delta_norm_gap"), (_HalfBatch, "loss_gap_step1")])
def test_broken_training_step_is_not_correct(broken, caught_by):
    from benchmark.drivers import fit
    cell = tiny.cell(tiny.GPT2, tiny.FIT)
    out = fit.run(cell, 12, 0.5, False, _devices(), time.perf_counter(),
                  break_step=broken)
    assert not out["correct"]
    assert out["numbers"][caught_by] > cell["config"]["check"][caught_by]


def test_training_control_fails_a_limit_at_test_size():
    """float8 matmuls in the reference, at a width where the rounding shows
    (n_embd 256, 4 layers, 128 tokens): the control fails the gradient-norm
    limit of that size, which the program's own readings pass (program
    0.0029-0.0038, control 0.012-0.031 over seeds 1-3 on CPU)."""
    from benchmark.drivers import fit
    cfg = dict(tiny.GPT2, n_embd=256, n_head=4, n_layer=4, n_positions=128,
               vocab_size=1024)
    cfg["check"] = dict(cfg["check"], grad_norm_gap=0.008)
    cell = tiny.cell(cfg, dict(tiny.FIT, seq_len=128, steps_per_epoch=2))
    sound = fit.run(cell, 1, 0.3, False, _devices(), time.perf_counter())
    assert sound["numbers"]["grad_norm_gap"] < cfg["check"]["grad_norm_gap"]
    low = fit.control(cell, 1, _devices())
    assert low["grad_norm_gap"] > cfg["check"]["grad_norm_gap"]
    assert low["grad_norm_gap"] > 3 * sound["numbers"]["grad_norm_gap"]


# ----------------------------------------------------------------- serving
@pytest.mark.parametrize("mix", [tiny.OPEN, tiny.CLOSED],
                         ids=["open", "closed"])
def test_sound_serving_run_is_correct(mix):
    from benchmark.drivers import serve
    out = serve.run(tiny.cell(tiny.LLAMA, mix), 21, 1.5, False, _devices(),
                    time.perf_counter())
    assert out["correct"], out["numbers"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_tokens_per_s"] > 0
    assert out["metrics"]["itl_p95_ms"] > 0
    if mix["loop"] == "open":
        assert out["metrics"]["ttft_p90_ms"] > 0


def test_altered_served_token_is_not_correct():
    """One served token of every request changed where it is produced (the
    generator's read of the stream): the reference must disown it."""
    from benchmark.drivers import serve

    def alter(rec, position, token):
        return (token + 1) % 509 if position == 1 else token

    out = serve.run(tiny.cell(tiny.LLAMA, tiny.OPEN), 22, 1.5, False,
                    _devices(), time.perf_counter(), alter_token=alter)
    assert not out["correct"]
    assert out["numbers"]["logit_gap_max"] > \
        tiny.LLAMA["check"]["logit_gap_max"]


def test_serving_control_fails_the_limit_at_test_size():
    """int8 weights in the reference's place, at a size where the rounding
    shows (hidden 1024, 8 layers, vocab 16384; at hidden 256 int8 weights are
    as close to float32 as bf16 arithmetic is): the mean gap of its first
    choices fails the limit of that size, which the program's served tokens
    pass (program 0.00005-0.00013, control 0.0023-0.0025 on CPU)."""
    from benchmark.drivers import serve
    cfg = dict(tiny.LLAMA, hidden_size=1024, intermediate_size=2816,
               num_attention_heads=8, num_key_value_heads=2, head_dim=128,
               vocab_size=16384, num_hidden_layers=8)
    cfg["check"] = dict(cfg["check"], logit_gap_mean=6e-4)
    mix = dict(tiny.OPEN, check_requests=16, rate_rps=4.0)
    got = serve.control(tiny.cell(cfg, mix), 1, _devices(), 6.0)
    assert got["program"]["logit_gap_mean"] < cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > cfg["check"]["logit_gap_mean"]
    assert got["control"]["logit_gap_mean"] > \
        3 * got["program"]["logit_gap_mean"]
