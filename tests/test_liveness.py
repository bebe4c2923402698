"""Tier-1 contract for the static memory analyzer (static.liveness).

Three claims, each load-bearing for the TPU9xx verifier pass and the
planner's liveness-at-peak HBM term:

* **intervals** — def/last-use residency with the documented edge
  rules: entries caller-held to program end, donation shortening,
  fetch pinning, in-place/write-family alias extension;
* **prediction vs measurement** — the static peak is within 10% of an
  eager replay's measured high-water AND of the perf census high-water
  gauge on the REAL ladder programs (the tiny GPT-with-loss and llama
  forward that ``tools.tpulint --programs`` verifies), so the size
  model is anchored to actual buffer sizes, not to itself;
* **enforcement** — ``FLAGS_verify_programs=strict`` +
  ``FLAGS_verifier_hbm_capacity`` raises TPU901 from ``Program.run``
  BEFORE ``jax.jit`` ever sees the program (the jit cache stays empty).
"""
import gc

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.ops as ops
from paddle_tpu import static
from paddle_tpu.static import liveness, verifier

R = verifier.Record
F32 = "float32"


def _r(name, in_ids, out_ids, shape=(8, 8), **kw):
    n_in, n_out = len(in_ids), len(out_ids)
    return R(name, in_ids=in_ids, out_ids=out_ids,
             in_shapes=[shape] * n_in, out_shapes=[shape] * n_out,
             in_dtypes=[F32] * n_in, out_dtypes=[F32] * n_out, **kw)


NB = 8 * 8 * 4                       # bytes of one (8, 8) float32


# ==========================================================================
# intervals
# ==========================================================================
class TestIntervals:
    def test_chain_def_to_last_use(self):
        recs = [_r("matmul", [1, 2], [3]), _r("relu", [3], [4]),
                _r("sum", [4], [5], shape=())]
        res = liveness.analyze(recs, fetch_ids=[5])
        iv = res.intervals
        # entries are caller-held buffers: resident through program end
        assert iv[1].start == -1 and iv[1].end == 3
        assert iv[1].origin == "param"
        # interior value: def at its op, dead after its last use
        assert (iv[3].start, iv[3].end) == (0, 1)
        assert (iv[4].start, iv[4].end) == (1, 2)
        # fetched value: pinned through program end
        assert iv[5].end == 3
        assert res.n_ops == 3 and len(res.curve) == 3

    def test_donation_frees_entry_after_last_use(self):
        recs = [_r("relu", [1], [2]), _r("relu", [2], [3])]
        kept = liveness.analyze(recs, fetch_ids=[3])
        donated = liveness.analyze(recs, fetch_ids=[3],
                                   donated_ids=[1])
        assert kept.intervals[1].end == 2      # held to program end
        assert donated.intervals[1].end == 0   # freed after op#0
        # the donated buffer is gone at op#1, so the curve is lower
        assert donated.curve[1] == kept.curve[1] - NB

    def test_write_family_alias_extends_result(self):
        # t[0:2] = v then t read much later: the setitem RESULT buffer
        # stays reachable through the target's identity (eager payload
        # swap), so its interval extends to the target's last use
        recs = [
            _r("setitem", [1, 9], [2],
               attrs={"write_region": ((0, 2), (0, 8))}),
            _r("relu", [8], [3]),
            _r("relu", [3], [4]),
            _r("add", [2, 1], [5]),
        ]
        res = liveness.analyze(recs, fetch_ids=[5])
        assert res.intervals[2].end >= res.intervals[1].end

    def test_elementwise_chain_peak_is_three_buffers(self):
        # entry + previous output + current output at every interior op
        recs = [_r("relu", [i], [i + 1]) for i in range(1, 7)]
        res = liveness.analyze(recs, fetch_ids=[7])
        assert res.peak_bytes == pytest.approx(3 * NB)
        # NOT the all-resident estimate (entry + 6 outputs)
        assert res.peak_bytes < 7 * NB

    def test_peak_report_attribution(self):
        recs = [_r("matmul", [1, 2], [3]), _r("relu", [3], [4])]
        rep = liveness.peak_report(recs, fetch_ids=[4],
                                   capacity_bytes=10 * NB)
        assert rep["peak_op"]["name"] in ("matmul", "relu")
        assert rep["peak_bytes"] == pytest.approx(rep["curve"][
            rep["peak_index"]])
        assert rep["utilization"] == pytest.approx(
            rep["peak_bytes"] / (10 * NB))
        sizes = [tv["nbytes"] for tv in rep["top_values"]]
        assert sizes == sorted(sizes, reverse=True)
        assert "static peak HBM" in liveness.render_peak_report(rep)


# ==========================================================================
# static prediction vs measured replay + perf census (10% tolerance)
# ==========================================================================
def _ladder_gpt():
    from tools.tpulint.program_check import _gpt_loss_program
    prog, fetch, model = _gpt_loss_program()
    return prog, fetch, model


def _ladder_llama():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    paddle.seed(7)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, max_seq_len=32,
        use_flash_attention=False))
    prog = static.Program()
    with static.program_guard(prog):
        ids = static.data("ids", [2, 8], "int64")
        logits = model(ids)
        if isinstance(logits, (tuple, list)):
            logits = logits[0]
    return prog, [id(logits)], model


class TestStaticPeakVsCensus:
    @pytest.mark.parametrize("build,phase", [
        (_ladder_gpt, "liveness_gpt"),
        (_ladder_llama, "liveness_llama"),
    ])
    def test_prediction_within_10pct_of_census(self, build, phase):
        prog, fetch, _model = build()
        gc.collect()                 # stabilize the process-wide census
        res = liveness.measure_peak(prog, fetch_ids=fetch, phase=phase)
        static_peak = res["static_peak_bytes"]
        assert static_peak > 0

        # claim 1: replay under the same deletion schedule
        measured = res["peak_bytes"]
        assert abs(static_peak - measured) <= 0.10 * measured, res

        # claim 2: the perf census gauge saw the same high-water —
        # census counts every live buffer in the process, so compare
        # the replay's contribution (delta over its floor + entries)
        census = (res["entry_bytes"]
                  + res["census_high_water"] - res["census_floor"])
        assert census > 0
        assert abs(static_peak - census) <= 0.10 * census, res

    def test_peak_report_on_ladder_program(self):
        prog, fetch, _model = _ladder_gpt()
        rep = liveness.peak_report(prog, fetch_ids=fetch)
        assert rep["n_ops"] == len(prog.global_block().ops)
        assert 0 <= rep["peak_index"] < rep["n_ops"]
        assert rep["peak_bytes"] >= rep["entry_bytes"] > 0
        assert len(rep["top_values"]) == 5


# ==========================================================================
# TPU901 enforcement: strict mode raises BEFORE compile
# ==========================================================================
@pytest.fixture
def _flags_guard():
    prev = paddle.get_flags(
        ["FLAGS_verify_programs", "FLAGS_verifier_hbm_capacity"])
    yield
    paddle.set_flags(prev)


class TestStrictEnforcement:
    def _program(self):
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [64, 64], "float32")
            y = ops.matmul(x, x)
            z = ops.tanh(y)
        return prog, z

    def test_tpu901_raises_before_compile(self, _flags_guard):
        prog, z = self._program()
        paddle.set_flags({"FLAGS_verify_programs": "strict",
                          "FLAGS_verifier_hbm_capacity": 1024})
        with pytest.raises(verifier.ProgramVerifierError) as ei:
            prog.run({"x": np.zeros((64, 64), np.float32)}, [id(z)])
        assert "TPU901" in str(ei.value)
        # the whole point: the diagnostic fired before jax.jit was
        # ever built for this program
        assert not prog._jit_cache

    def test_fitting_program_runs_clean_in_strict(self, _flags_guard):
        prog, z = self._program()
        paddle.set_flags({"FLAGS_verify_programs": "strict",
                          "FLAGS_verifier_hbm_capacity": 10 ** 9})
        out = prog.run({"x": np.ones((64, 64), np.float32)}, [id(z)])
        assert np.asarray(out[0]).shape == (64, 64)
        assert prog._jit_cache       # compiled this time
