"""IR-level pipeline parallelism (distributed/pipeline/): the stage
partitioner over the static Program op list, the micro-batch schedule
tables, the pipelined runtime's EXACT gradient parity against the
unpipelined step, (data, pp) mesh placement, planner integration
(PP as a placement dimension under hard-HBM rejection), and the
TPU8xx cross-stage verifier family.

Parity model: pipelining reorders WHEN each microbatch's forward and
backward run, never WHAT they compute — per-microbatch contributions
are reduced in a fixed order, so every schedule must be bitwise
identical to the sequential microbatched step, and both must match an
independent jax.grad over the raw op-list replay.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, static
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.pipeline import (
    SCHEDULES, PipelinedProgram, analytical_bubble, build_schedule,
    partition_program, peak_inflight, simulate)
from paddle_tpu.static import verifier


def _mlp_program(n_blocks=4, d=8, rows=4, seed=7):
    """Stacked Linear+GELU chain traced at MICROBATCH shape [rows, d]."""
    paddle.seed(seed)
    blocks = []
    for _ in range(n_blocks):
        blocks += [nn.Linear(d, d), nn.GELU()]
    model = nn.Sequential(*blocks)
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [rows, d], "float32")
        y = static.data("y", [rows, d], "float32")
        loss = ((model(x) - y) ** 2).mean()
    return prog, loss


def _feed(prog, m, seed=3):
    """Random feed at m x the traced microbatch leading dim."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, vid in prog.feed_vars.items():
        shape = list(prog._feed_shapes[name])
        shape[0] *= m
        dt = str(prog._feed_dtypes[name])
        if dt.startswith("int"):
            out[name] = rng.randint(0, 8, size=shape).astype(dt)
        else:
            out[name] = rng.randn(*shape).astype(dt)
    return out


def _ref_loss_grads(prog, loss_id, feed, m, params=None):
    """Independent reference: jax.grad over the raw op-list replay,
    microbatch-mean — no pipeline machinery involved."""
    names = sorted(prog.feed_vars)
    feed_ids = [prog.feed_vars[n] for n in names]
    cap_ids = list(prog._captured.keys())
    base = {pid: t._data for pid, t in prog._captured.items()}
    if params:
        base.update(params)
    diff_ids = [pid for pid in cap_ids
                if jnp.issubdtype(jnp.asarray(base[pid]).dtype,
                                  jnp.inexact)]
    rest = {pid: base[pid] for pid in cap_ids if pid not in diff_ids}

    def total(diff_list):
        caps = dict(zip(diff_ids, diff_list))
        caps.update(rest)
        tot = 0.0
        for j in range(m):
            mb = [jnp.split(jnp.asarray(feed[n]), m)[j] for n in names]
            env = prog._replay_by_ids(feed_ids, mb, cap_ids,
                                      [caps[pid] for pid in cap_ids])
            tot = tot + env[loss_id]
        return tot / m

    loss, grads = jax.value_and_grad(total)(
        [base[pid] for pid in diff_ids])
    return loss, dict(zip(diff_ids, grads))


# ==========================================================================
# stage partitioner
# ==========================================================================
class TestPartitioner:
    def test_uniform_contiguous_cover(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 4, strategy="uniform",
                                 fetch_ids=[id(loss)])
        ops = prog.global_block().ops
        assert len(part.stages) == 4
        covered = []
        for k, st in enumerate(part.stages):
            assert st.index == k
            assert st.op_stop > st.op_start
            covered.extend(range(st.op_start, st.op_stop))
        assert covered == list(range(len(ops)))

    def test_cost_strategy_balances_seconds(self):
        prog, loss = _mlp_program(n_blocks=8)
        part = partition_program(prog, 4, strategy="cost",
                                 fetch_ids=[id(loss)])
        secs = part.stage_seconds()
        assert len(secs) == 4 and all(s > 0 for s in secs)
        # identical blocks: the greedy prefix cut keeps stages within
        # a small factor of each other
        assert max(secs) <= 4.0 * min(s for s in secs if s > 0)

    def test_custom_split_points(self):
        prog, loss = _mlp_program()
        n = len(prog.global_block().ops)
        cut = n // 2
        part = partition_program(prog, strategy="custom",
                                 split_points=[cut],
                                 fetch_ids=[id(loss)])
        assert part.boundaries == (cut,)
        assert part.stages[0].op_stop == cut
        assert part.stages[1].op_start == cut

    def test_cut_values_pair_across_boundary(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        s0, s1 = part.stages
        assert s0.send and s0.send == s1.recv
        # cuts are real intermediate values: not feeds, not params
        feeds = set(prog.feed_vars.values())
        for vid in s0.send:
            assert vid not in feeds
            assert vid not in s0.param_ids
        # params partition disjointly
        assert not (set(s0.param_ids) & set(s1.param_ids))

    def test_stage_records_carry_transfer_contract(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        recs0, recs1 = part.stage_records()
        sends = [r for r in recs0 if r.name == "send"]
        recvs = [r for r in recs1 if r.name == "recv"]
        assert sends and len(sends) == len(recvs)
        for k, (snd, rcv) in enumerate(zip(sends, recvs)):
            assert snd.attrs["peer"] == 1 and rcv.attrs["peer"] == 0
            assert snd.attrs["seq"] == rcv.attrs["seq"] == k
            assert snd.in_shapes[0] == rcv.out_shapes[0]
            assert snd.in_dtypes[0] == rcv.out_dtypes[0]


# ==========================================================================
# schedule tables
# ==========================================================================
class TestSchedules:
    @pytest.mark.parametrize("name", ["fthenb", "1f1b"])
    @pytest.mark.parametrize("S,m", [(2, 4), (4, 8), (4, 16)])
    def test_uniform_bubble_matches_closed_form(self, name, S, m):
        table = build_schedule(name, S, m)
        sim = simulate(table)
        want = (S - 1) / (m + S - 1)
        assert sim["bubble"] == pytest.approx(want, abs=1e-9)
        assert analytical_bubble(name, S, m) == pytest.approx(want)

    def test_every_unit_runs_once(self):
        for name in SCHEDULES:
            S, m = 4, 6
            table = build_schedule(name, S, m)
            assert len(table) == S
            for s in range(S):
                for kind in ("F", "B"):
                    units = [st for st in table[s] if st.kind == kind]
                    assert all(st.stage == s for st in units)
                    assert sorted(st.mb for st in units) == list(range(m))

    def test_1f1b_memory_win_over_fthenb(self):
        S, m = 4, 16
        depth_ft = peak_inflight(build_schedule("fthenb", S, m))
        depth_11 = peak_inflight(build_schedule("1f1b", S, m))
        assert depth_ft[0] == m
        assert depth_11[0] == min(m, S)

    def test_zb_no_worse_than_1f1b(self):
        S, m = 4, 8
        zb = simulate(build_schedule("zb", S, m))
        f11 = simulate(build_schedule("1f1b", S, m))
        assert zb["makespan"] <= f11["makespan"] + 1e-9
        assert analytical_bubble("zb", S, m) == pytest.approx(
            zb["bubble"])


# ==========================================================================
# runtime: exact parity
# ==========================================================================
class TestRuntimeParity:
    @pytest.mark.parametrize("timed", [False, True],
                             ids=["untimed", "timed"])
    @pytest.mark.parametrize("schedule", SCHEDULES)
    def test_bitwise_vs_unpipelined(self, schedule, timed):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        pp = PipelinedProgram(part, schedule=schedule,
                              loss_id=id(loss))
        feed = _feed(prog, m=4)
        l_pp, g_pp, stats = pp.train_step(feed, 4, collect_timing=timed)
        if timed:
            # every step of the table was timed, and the replay of those
            # durations gives a bubble fraction (how close it lies to the
            # analytical one is a property of the host, not asserted)
            assert len(stats["timings"]) == stats["steps"]
            assert 0.0 <= stats["measured_bubble"] < 1.0
        l_ref, g_ref = pp.run_unpipelined(feed, 4)
        # bitwise: pipelining reorders execution, not arithmetic
        assert np.asarray(l_pp).tobytes() == np.asarray(l_ref).tobytes()
        assert set(g_pp) == set(g_ref)
        for pid in g_ref:
            assert np.asarray(g_pp[pid]).tobytes() == \
                np.asarray(g_ref[pid]).tobytes()
        assert stats["schedule"] == schedule
        assert stats["num_stages"] == 2

    def test_matches_independent_jax_grad(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        pp = PipelinedProgram(part, schedule="1f1b", loss_id=id(loss))
        feed = _feed(prog, m=4)
        l_pp, g_pp, _ = pp.train_step(feed, 4)
        l_ref, g_ref = _ref_loss_grads(prog, id(loss), feed, 4)
        np.testing.assert_allclose(np.asarray(l_pp),
                                   np.asarray(l_ref), rtol=1e-6)
        assert set(g_pp) == set(g_ref)
        for pid in g_ref:
            np.testing.assert_allclose(np.asarray(g_pp[pid]),
                                       np.asarray(g_ref[pid]),
                                       rtol=1e-5, atol=1e-6)

    def test_forward_only(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        pp = PipelinedProgram(part, schedule="fthenb", loss_id=id(loss))
        feed = _feed(prog, m=2)
        fetched = pp.forward(feed, 2)
        assert id(loss) in fetched and len(fetched[id(loss)]) == 2

    def test_loss_must_live_on_last_stage(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        with pytest.raises(ValueError):
            PipelinedProgram(part, loss_id=123456789)


# ==========================================================================
# (data, pp) mesh placement + 4-stage GPT training parity
# ==========================================================================
@pytest.fixture
def dp_pp_mesh():
    old = mesh_mod._global_mesh
    mesh = mesh_mod.build_mesh({"data": 2, "pp": 4})
    mesh_mod.set_mesh(mesh)
    yield mesh
    mesh_mod.set_mesh(old)


def _gpt_program(batch=2, seq=8):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import functional as F
    import paddle_tpu.ops as ops
    paddle.seed(11)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=32, hidden_size=16, num_layers=4, num_heads=2,
        max_seq_len=16, use_flash_attention=False))
    prog = static.Program()
    with static.program_guard(prog):
        ids = static.data("ids", [batch, seq], "int64")
        logits = model(ids)
        if isinstance(logits, (tuple, list)):
            logits = logits[0]
        v = logits.shape[-1]
        loss = F.cross_entropy(
            ops.reshape(logits[:, :-1, :], [-1, v]),
            ops.reshape(ids[:, 1:], [-1])).mean()
    return prog, loss


class TestGPTMeshTraining:
    def test_4stage_gpt_trains_with_loss_parity(self, dp_pp_mesh):
        """The acceptance bar: a 4-layer GPT trained for 3 SGD steps on
        the (data=2, pp=4) mesh tracks the single-device unpipelined
        reference loss step for step."""
        prog, loss = _gpt_program()
        part = partition_program(prog, 4, fetch_ids=[id(loss)])
        pp = PipelinedProgram(part, schedule="1f1b", loss_id=id(loss),
                              mesh=dp_pp_mesh, pp_axis="pp",
                              data_axis="data")
        m, lr = 4, 0.1
        feed = _feed(prog, m=m, seed=5)
        ref_params = None
        losses, ref_losses = [], []
        for _ in range(3):
            l_pp, g_pp, _ = pp.train_step(feed, m)
            l_ref, g_ref = _ref_loss_grads(prog, id(loss), feed, m,
                                           params=ref_params)
            losses.append(float(np.asarray(l_pp)))
            ref_losses.append(float(np.asarray(l_ref)))
            # SGD on both sides: the pipelined program's captured
            # params, and the reference's private copies
            if ref_params is None:
                ref_params = {pid: prog._captured[pid]._data
                              for pid in prog._captured}
            for pid, g in g_pp.items():
                t = prog._captured[pid]
                t._swap_payload(t._data - lr * jnp.asarray(g))
            ref_params = {
                pid: (ref_params[pid] - lr * jnp.asarray(g_ref[pid])
                      if pid in g_ref else ref_params[pid])
                for pid in ref_params}
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
        assert losses[-1] < losses[0]  # it actually trains

    def test_pipeline_only_mesh_matches_unmeshed(self, dp_pp_mesh):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        feed = _feed(prog, m=2)
        # a (2, 2) sub-mesh over 4 of the 8 virtual devices
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("data", "pp"))
        on_mesh = PipelinedProgram(part, schedule="1f1b",
                                   loss_id=id(loss), mesh=mesh,
                                   pp_axis="pp", data_axis="data")
        plain = PipelinedProgram(part, schedule="1f1b",
                                 loss_id=id(loss))
        l_m, g_m, _ = on_mesh.train_step(feed, 2)
        l_p, g_p, _ = plain.train_step(feed, 2)
        np.testing.assert_allclose(np.asarray(l_m), np.asarray(l_p),
                                   rtol=1e-6)
        for pid in g_p:
            np.testing.assert_allclose(np.asarray(g_m[pid]),
                                       np.asarray(g_p[pid]),
                                       rtol=1e-5, atol=1e-7)


# ==========================================================================
# planner integration: PP under hard-HBM rejection
# ==========================================================================
class TestPlannerIntegration:
    def test_pp_wins_when_hbm_rejects_tp_fsdp(self, dp_pp_mesh):
        from paddle_tpu.distributed.planner import plan
        prog, loss = _mlp_program(n_blocks=8, d=32)
        # capacity below what any whole-model-per-device candidate
        # needs, but 1/4 of the params per stage fits
        param_bytes = sum(
            float(np.prod(t._data.shape)) * 4
            for t in prog._captured.values())
        capacity = param_bytes * 4.0 * 0.6   # (2 + opt) * 0.6 < full
        result = plan(prog, dp_pp_mesh, capacity_bytes=capacity)
        win = result.winner
        assert win.candidate.origin == "pipeline", \
            [(c.candidate.name, c.score.rejected) for c in result.ranked]
        assert result.pipeline is not None
        assert result.pipeline.num_stages == 4
        assert result.pipeline.schedule in SCHEDULES
        assert 0.0 < result.pipeline.bubble_fraction < 1.0
        assert "pipeline" in result.summary()

    def test_pp_not_offered_without_pipeline_axis(self):
        from paddle_tpu.distributed.pipeline.planning import \
            pipeline_candidates
        prog, loss = _mlp_program()
        mesh = mesh_mod.build_mesh({"data": 8})
        assert pipeline_candidates(prog, mesh) == []

    def test_roomy_capacity_prefers_pure_dp(self, dp_pp_mesh):
        from paddle_tpu.distributed.planner import plan
        prog, loss = _mlp_program()
        result = plan(prog, dp_pp_mesh, capacity_bytes=1e12)
        assert result.winner.candidate.origin != "pipeline"
        assert result.pipeline is None


# ==========================================================================
# verifier: TPU8xx cross-stage desync
# ==========================================================================
class TestStageVerifier:
    def _records(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        return [list(r) for r in part.stage_records()]

    def test_clean_partition_verifies(self):
        report = verifier.check_stages(self._records())
        assert report.ok, report.render()

    def test_shape_desync_flagged_and_strict_raises(self):
        recs = self._records()
        for r in recs[1]:
            if r.name == "recv":
                r.out_shapes = ((9, 9),)
                break
        report = verifier.check_stages(recs)
        assert "TPU802" in report.codes()
        with pytest.raises(verifier.ProgramVerifierError):
            verifier.enforce(report, "strict")

    def test_dropped_recv_flagged(self):
        recs = self._records()
        recs[1] = [r for r in recs[1] if r.name != "recv"]
        report = verifier.check_stages(recs)
        assert "TPU801" in report.codes()

    def test_runtime_strict_check_rejects_tampered_partition(self):
        prog, loss = _mlp_program()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        # tamper the partition's own contract: claim a different dtype
        # on the boundary recv
        recs = [list(r) for r in part.stage_records()]
        for r in recs[1]:
            if r.name == "recv":
                r.out_dtypes = ("int32",)
                break
        report = verifier.check_stages(recs)
        assert "TPU802" in report.codes()
