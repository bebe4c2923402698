"""Auto-parallel placement planner — search, scoring, emission.

Contracts under test (ISSUE 11 / ROADMAP "Auto-parallel planner"):

* the spec algebra surfaces Partial (reduce-pending) placements with
  the documented meet rule, and the general einsum rule resolves
  arbitrary equations (MoE dispatch/combine included) from the
  recorded ``equation`` attr;
* candidate enumeration is deterministic (same params + mesh -> same
  population, same order);
* the cost model ranks sanely: DP beats TP on a small model; when the
  replicated parameters exceed one chip's HBM the DP candidate is
  REJECTED (hard, with the reason naming the capacity) and a
  sharded-parameter candidate wins;
* ``plan()`` on the GPT emits a placement with ZERO replicate-fallback
  ops, and the emitted (param_specs, in_specs) round-trip through
  ``Engine(mesh=, placement="auto")`` / ``to_static(param_specs=
  "auto")`` with loss parity vs the unsharded path on a virtual
  (data, tp) mesh;
* every op the GPT/llama/MoE workloads emit is scored — named rule,
  category fallback, or an explicit PENALTY_OPS entry
  (tools/planner_audit.py, wired here like fusion_audit).
"""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import mesh as mesh_mod, planner, spmd
from paddle_tpu.distributed.planner import cost as pcost
from paddle_tpu.distributed.spmd import rules as R
from paddle_tpu.models import GPTConfig, GPTForCausalLM


def _mesh(**shape):
    return mesh_mod.build_mesh(dict(shape))


# ==========================================================================
# spec algebra: Partial + meet rule
# ==========================================================================
class TestPartialAlgebra:
    def test_meet_partial_documented_semantics(self):
        # equal keeps
        assert R.meet_partial(("tp",), ("tp",)) == ("tp",)
        # subset -> intersection survives
        assert R.meet_partial(("ep", "tp"), ("tp",)) == ("tp",)
        # disagreement -> only commonly-pending axes survive (an axis
        # one side already reduced cannot be un-reduced)
        assert R.meet_partial(("tp",), ("ep",)) == ()
        assert R.meet_partial(("tp",), ()) == ()
        # normalization: Partial / str / unsorted
        assert R.meet_partial(R.Partial(("b", "a")), ("a", "b")) \
            == ("a", "b")
        assert R.normalize_partial("tp") == ("tp",)
        assert R.normalize_partial(None) == ()

    def test_matmul_contraction_surfaces_partial(self):
        # row-parallel: x(.., H-tp) @ W(H/tp, N) -> out Partial over tp
        res = R.matmul_rule([("data", None, "tp"), ("tp", None)],
                            [(4, 16, 32), (32, 96)], {}, [(4, 16, 96)])
        assert res.out_partial[0] == ("tp",)
        # column-parallel: no pending reduce
        res = R.matmul_rule([("data", None, None), (None, "tp")],
                            [(4, 16, 32), (32, 96)], {}, [(4, 16, 96)])
        assert res.out_partial[0] == ()

    def test_embedding_vocab_shard_is_partial(self):
        res = R.embedding_rule([("data", None), ("tp", None)],
                               [(4, 16), (64, 32)], {}, [(4, 16, 32)])
        assert res.out_partial[0] == ("tp",)


# ==========================================================================
# general einsum rule (equation attr)
# ==========================================================================
class TestEinsumRule:
    def test_moe_dispatch_and_combine(self):
        # dispatch: nec,nh->ech — e sharded over ep propagates; n
        # contracted (unsharded) -> no partial
        res = R.einsum_rule([(None, "ep", None), (None, None)],
                            [(64, 8, 4), (64, 32)],
                            {"equation": "nec,nh->ech"}, [(8, 4, 32)])
        assert res.out_specs[0] == ("ep", None, None)
        assert res.out_partial[0] == ()
        # combine: nec,ech->nh — e contracted AND sharded -> Partial
        res = R.einsum_rule([(None, "ep", None), ("ep", None, None)],
                            [(64, 8, 4), (8, 4, 32)],
                            {"equation": "nec,ech->nh"}, [(64, 32)])
        assert res.out_partial[0] == ("ep",)

    def test_contracted_sharded_dim_partial(self):
        res = R.einsum_rule([("data", "tp"), ("tp", None)],
                            [(8, 32), (32, 16)],
                            {"equation": "bh,hd->bd"}, [(8, 16)])
        assert res.out_specs[0] == ("data", None)
        assert res.out_partial[0] == ("tp",)

    def test_input_constraints_follow_label_map(self):
        res = R.einsum_rule([("data", None), (None, "tp")],
                            [(8, 32), (32, 16)],
                            {"equation": "bh,hd->bd"}, [(8, 16)])
        # h merged replicated, d keeps tp
        assert res.in_specs[1] == (None, "tp")
        assert res.out_specs[0] == ("data", "tp")

    def test_implicit_output_and_fallbacks(self):
        terms = R.parse_einsum_equation("ij,jk", 2)
        assert terms == ([["i", "j"], ["j", "k"]], ["i", "k"])
        assert R.parse_einsum_equation("...ij,jk->...ik", 2) is None
        assert R.parse_einsum_equation("ij,jk->ik", 3) is None
        # no equation -> legacy heuristic, never a crash
        res = R.einsum_rule([("data", None)], [(8, 32)], {}, [(8, 32)])
        assert len(res.out_specs) == 1

    def test_einsum_dispatch_records_equation(self):
        from paddle_tpu import static
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [4, 8], "float32")
            y = paddle.to_tensor(np.ones((8, 2), np.float32))
            paddle.einsum("ij,jk->ik", x, y)
        rec = prog.global_block().ops[-1]
        assert rec.name == "einsum"
        assert rec.attrs.get("equation") == "ij,jk->ik"

    def test_einsum_cost_from_equation(self):
        from paddle_tpu.observability.perf.costmodel import einsum_cost
        c = einsum_cost([(8, 32), (32, 16)], [],
                        {"equation": "bh,hd->bd"}, [(8, 16)])
        assert c.flops == 2.0 * 8 * 32 * 16


# ==========================================================================
# candidate enumeration
# ==========================================================================
PARAMS = [
    ("net.0.fc1.weight", (32, 128)), ("net.0.fc1.bias", (128,)),
    ("net.0.fc2.weight", (128, 32)), ("net.0.fc2.bias", (32,)),
    ("net.ln.weight", (32,)), ("net.wte.weight", (512, 32)),
]


class TestCandidates:
    def test_roles(self):
        assert planner.classify_param("a.qkv_proj.weight", (4, 12)) \
            == "column"
        assert planner.classify_param("a.out_proj.weight", (4, 4)) \
            == "row"
        assert planner.classify_param("gpt.wte.weight", (64, 4)) \
            == "embedding"
        assert planner.classify_param("gpt.wpe.weight", (16, 4)) \
            == "position"
        assert planner.classify_param("blk.ln1.weight", (4,)) == "norm"
        assert planner.classify_param("x.fc1.bias", (8,)) == "bias"

    def test_families_present(self):
        mesh = _mesh(data=2, tp=4)
        cands = planner.enumerate_candidates(PARAMS, mesh)
        names = [c.name for c in cands]
        assert "dp" in names and "tp(tp)" in names \
            and "fsdp(tp)" in names
        dp = next(c for c in cands if c.name == "dp")
        assert all(all(e is None for e in s)
                   for _, s in dp.param_specs)
        tp = next(c for c in cands if c.name == "tp(tp)")
        assert tp.spec_of("net.0.fc1.weight") == (None, "tp")
        assert tp.spec_of("net.0.fc2.weight") == ("tp", None)

    def test_enumeration_deterministic(self):
        mesh = _mesh(data=2, tp=4)
        a = planner.enumerate_candidates(PARAMS, mesh)
        b = planner.enumerate_candidates(PARAMS, mesh)
        assert [c.name for c in a] == [c.name for c in b]
        assert [c.param_specs for c in a] == [c.param_specs for c in b]

    def test_hybrid_family_on_3d_mesh(self):
        mesh = _mesh(data=2, fsdp=2, tp=2)
        names = [c.name for c in
                 planner.enumerate_candidates(PARAMS, mesh)]
        assert any("xfsdp" in n for n in names)


# ==========================================================================
# cost model sanity
# ==========================================================================
class _MLP(nn.Layer):
    """Named fc1/fc2 so the planner's role heuristics see them."""

    def __init__(self, hidden=256):
        super().__init__()
        self.fc1 = nn.Linear(32, hidden)
        self.fc2 = nn.Linear(hidden, 8)

    def forward(self, x):
        from paddle_tpu.nn import functional as F
        return self.fc2(F.gelu(self.fc1(x)))


def _mlp_plan(mesh, capacity_bytes=None, hidden=256, batch=1024):
    # small PARAMS, big batch — the data-parallel sweet spot (grad
    # sync is param-sized, activation work batch-sized)
    paddle.seed(7)
    model = _MLP(hidden)
    x = np.random.RandomState(0).randn(batch, 32).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 8, (batch,)) \
        .astype(np.int64)
    loss = nn.CrossEntropyLoss()

    def loss_fn(xt, yt):
        return loss(model(xt), yt)

    return planner.plan(loss_fn, mesh, example_inputs=(x, y),
                        model=model, capacity_bytes=capacity_bytes)


class TestCostSanity:
    def test_dp_beats_tp_on_small_model(self):
        res = _mlp_plan(_mesh(data=2, tp=4))
        by_name = {s.candidate.name: s.score for s in res.ranked}
        assert by_name["dp"].total_s < by_name["tp(tp)"].total_s
        assert not by_name["dp"].rejected

    def test_over_capacity_rejects_dp_and_shards_params(self):
        # param-heavy regime (big weights, small batch): capacity below
        # the replicated footprint must REJECT dp (hard), and the
        # winner must actually shard parameters
        mesh = _mesh(data=2, tp=4)
        probe = _mlp_plan(mesh, hidden=512, batch=64)
        dp = next(s for s in probe.ranked if s.candidate.name == "dp")
        tight = dp.score.hbm_bytes * 0.6
        res = _mlp_plan(mesh, capacity_bytes=tight, hidden=512,
                        batch=64)
        dp2 = next(s for s in res.ranked if s.candidate.name == "dp")
        assert dp2.score.rejected and "HBM" in dp2.score.rejected
        win = res.winner
        assert not win.score.rejected
        assert win.score.hbm_bytes <= tight
        assert any(any(e is not None for e in s)
                   for s in res.param_spec_table.values())

    def test_all_rejected_raises(self):
        with pytest.raises(RuntimeError, match="every candidate"):
            _mlp_plan(_mesh(data=2, tp=4), capacity_bytes=1.0)

    def test_partial_and_grad_sync_are_priced(self):
        res = _mlp_plan(_mesh(data=2, tp=4))
        by_name = {s.candidate.name: s.score for s in res.ranked}
        # DP pays gradient sync; megatron-TP pays pending reduces
        assert by_name["dp"].collective_breakdown["grad_sync"] > 0
        tp = by_name["tp(tp)"]
        assert tp.collective_breakdown["partial"] > 0 \
            or tp.collective_breakdown["backward"] > 0

    def test_penalty_ops_documented(self):
        for op, why in pcost.PENALTY_OPS.items():
            assert isinstance(why, str) and len(why) > 10


# ==========================================================================
# GPT plan: zero fallbacks + deterministic emission
# ==========================================================================
GPT_CFG = dict(vocab_size=128, hidden_size=64, num_layers=1,
               num_heads=4, max_seq_len=32, use_flash_attention=False)


def _gpt_plan(mesh):
    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig(**GPT_CFG))
    ids = np.random.RandomState(0).randint(
        0, GPT_CFG["vocab_size"], (4, 32)).astype(np.int64)

    def loss_fn(x):
        _, loss = model(x, labels=x)
        return loss

    return model, ids, planner.plan(loss_fn, mesh,
                                    example_inputs=(ids,), model=model)


@pytest.fixture(scope="module")
def gpt_plan():
    """One shared plan for the read-only GPT assertions."""
    return _gpt_plan(_mesh(data=2, tp=4))


class TestGptPlan:
    def test_winner_has_zero_fallbacks(self, gpt_plan):
        _, _, res = gpt_plan
        assert res.winner.fallbacks == {}
        assert res.winner.score.fallback_ops == {}
        assert res.winner.score.unscored_ops == {}

    def test_plan_deterministic(self, gpt_plan):
        _, _, a = gpt_plan
        _, _, b = _gpt_plan(_mesh(data=2, tp=4))
        assert [s.candidate.name for s in a.ranked] \
            == [s.candidate.name for s in b.ranked]
        assert a.param_spec_table == b.param_spec_table

    def test_report_renders(self, gpt_plan):
        _, _, res = gpt_plan
        text = res.report()
        assert "Candidate table" in text
        assert res.winner.candidate.name in text
        assert "Emitted placement" in text
        s = res.summary()
        assert s["winner"] == res.winner.candidate.name


# ==========================================================================
# emission round-trips (Engine / to_static)
# ==========================================================================
class TestRoundTrip:
    def test_engine_auto_matches_unsharded(self):
        from paddle_tpu.distributed.auto_parallel.engine import Engine
        from paddle_tpu.io import TensorDataset

        def build():
            paddle.seed(11)
            model = nn.Sequential(nn.Linear(32, 64), nn.GELU(),
                                  nn.Linear(64, 8))
            opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=model.parameters())
            return model, opt

        rng = np.random.RandomState(0)
        xs = rng.randn(32, 32).astype(np.float32)
        ys = rng.randint(0, 8, (32,)).astype(np.int64)
        ds = TensorDataset([paddle.to_tensor(xs), paddle.to_tensor(ys)])

        prev = mesh_mod._global_mesh
        try:
            mesh_mod._global_mesh = None
            # single-device reference (no mesh -> plain jit)
            model, opt = build()
            ref = Engine(model, nn.CrossEntropyLoss(), opt)
            ref_hist = ref.fit(ds, epochs=1, batch_size=32)

            mesh_mod._global_mesh = None
            mesh = _mesh(data=2, tp=4)
            model2, opt2 = build()
            eng = Engine(model2, nn.CrossEntropyLoss(), opt2,
                         mesh=mesh, placement="auto")
            hist = eng.fit(ds, epochs=1, batch_size=32)
        finally:
            mesh_mod._global_mesh = prev

        assert eng.placement_plan is not None
        assert eng.spmd_stats["fallback"] == {}
        np.testing.assert_allclose(hist, ref_hist, rtol=1e-4,
                                   atol=1e-5)

    def test_to_static_auto_matches_eager(self):
        from paddle_tpu.jit import to_static

        paddle.seed(5)
        model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(),
                              nn.Linear(32, 4))
        mesh = _mesh(data=2, tp=4)

        def fwd(x):
            return (model(x) ** 2).mean()

        x = paddle.to_tensor(
            np.random.RandomState(2).randn(8, 16).astype(np.float32))
        eager = float(fwd(x).numpy())
        f = to_static(fwd, full_graph=True, mesh=mesh,
                      param_specs="auto")
        got = float(f(x).numpy())
        assert f.placement_plan is not None
        assert f.spmd_stats["fallback"] == {}
        np.testing.assert_allclose(got, eager, rtol=1e-5)

    def test_apply_stamps_and_places(self):
        # constrain capacity so the winner MUST shard parameters, then
        # apply() must place them for real
        mesh = _mesh(data=2, tp=4)
        paddle.seed(3)
        model = GPTForCausalLM(GPTConfig(**GPT_CFG))
        ids = np.random.RandomState(0).randint(
            0, GPT_CFG["vocab_size"], (4, 32)).astype(np.int64)

        def loss_fn(x):
            _, loss = model(x, labels=x)
            return loss

        probe = planner.plan(loss_fn, mesh, example_inputs=(ids,),
                             model=model)
        dp = next(s for s in probe.ranked if s.candidate.name == "dp")
        res = planner.plan(loss_fn, mesh, example_inputs=(ids,),
                           model=model,
                           capacity_bytes=dp.score.hbm_bytes * 0.7)
        placed = res.apply(model)
        assert placed  # the winner shards something
        for name, spec in placed.items():
            p = dict(model.named_parameters())[name]
            assert tuple(p._spmd_spec) == tuple(spec)

    def test_planned_gpt_step_matches_unsharded(self):
        """The planner's own placement, applied to the GPT and traced
        through the propagation scope: the unsharded model's loss
        (rtol 1e-3) and no replicate-fallback op in the traced step.
        Capacity is cut so that the winner has to shard parameters."""
        mesh = _mesh(data=2, tp=4)
        ids = np.random.RandomState(0).randint(
            0, GPT_CFG["vocab_size"], (4, 32)).astype(np.int64)

        def build():
            paddle.seed(3)
            return GPTForCausalLM(GPTConfig(**GPT_CFG))

        def loss_of(model, scope=None, in_spec=None):
            params = [p for p in model.parameters()
                      if not p.stop_gradient]

            def f(arrays, ids_a):
                originals = [p._data for p in params]
                for p, a in zip(params, arrays):
                    p._data = a
                try:
                    x = Tensor(ids_a)
                    if scope is not None:
                        for p in params:
                            spec = spmd.param_spec_of(p)
                            if spec is not None:
                                scope.seed(p, spec)
                        scope.seed(x, in_spec)
                    _, loss = model(x, labels=x)
                    return loss._data
                finally:
                    for p, o in zip(params, originals):
                        p._data = o

            return float(jax.jit(f)([p._data for p in params], ids))

        ref_loss = loss_of(build())

        model = build()

        def plan_loss(x):
            _, loss = model(x, labels=x)
            return loss

        probe = planner.plan(plan_loss, mesh, example_inputs=(ids,),
                             model=model)
        dp = next(s for s in probe.ranked if s.candidate.name == "dp")
        res = planner.plan(plan_loss, mesh, example_inputs=(ids,),
                           model=model,
                           capacity_bytes=dp.score.hbm_bytes * 0.7)
        assert res.apply(model)         # the winner shards something
        scope = spmd.trace_scope(mesh)
        with scope:
            loss = loss_of(model, scope, res.in_specs)
        assert scope.stats["annotated"] > 0      # the scope saw the step
        assert not scope.stats["fallback"], scope.stats
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-3)

    def test_in_specs_shape(self, gpt_plan):
        _, _, res = gpt_plan
        spec = res.in_specs
        assert isinstance(spec, P)


# ==========================================================================
# audit: no silently-unscored ops (tier-1, like fusion_audit)
# ==========================================================================
def test_planner_audit_clean():
    from tools.planner_audit import audit
    rep = audit()
    assert rep["ok"], rep["uncovered"]
    assert set(rep["workloads"]) == {"gpt", "llama", "moe", "dlrm"}
    # the MoE workload's opaque ops go through the penalty table, not
    # silence
    assert rep["workloads"]["moe"].get("moe_layer") == "penalty"


# ==========================================================================
# liveness-at-peak activation pricing (static.liveness -> cost.score_plan)
# ==========================================================================
class TestLivenessActivations:
    """The HBM term prices the liveness PEAK, not the sum of every
    activation: a long elementwise chain holds ~2 values at once, and
    the tighter bound must flip a hard-HBM rejection into an accepted
    candidate — without admitting a genuinely over-capacity plan."""

    def _chain_program(self, depth=24, n=64):
        from paddle_tpu import static
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", (n, n), "float32")
            h = x
            for _ in range(depth):
                h = (h * 1.0009765625) + 0.5
        return prog

    def _score(self, capacity):
        from paddle_tpu.distributed.spmd.propagate import \
            propagate_program
        mesh = _mesh(data=2, tp=4)
        prog = self._chain_program()
        plan = propagate_program(prog, mesh, {"x": None})
        sc = pcost.score_plan(prog, plan, mesh,
                              candidate_name="chain",
                              capacity_bytes=capacity)
        return prog, plan, mesh, sc

    def test_rejection_flips_to_accept(self):
        nb = 64 * 64 * 4
        capacity = 6 * nb
        prog, plan, mesh, sc = self._score(capacity)
        # the OLD all-activations-resident estimate (sum of every op
        # output at its sharded size) is over this capacity...
        old_sum = sum(
            pcost._value_bytes(s)
            * pcost.shard_fraction(spec, mesh, s)
            for op, ann in zip(prog.global_block().ops,
                               plan.annotations)
            for s, spec in zip(op.out_shapes or (), ann.out_specs))
        rest = sc.hbm_bytes - sc.memory_breakdown["activations"]
        assert old_sum + rest > capacity, \
            "fixture too small: old estimate would also fit"
        # ...but the liveness peak of an elementwise chain is ~2
        # buffers, and the candidate is ACCEPTED
        assert sc.rejected is None
        assert sc.hbm_bytes <= capacity
        assert sc.memory_breakdown["activations"] <= 3 * nb
        # attribution names the op at the high-water mark
        assert sc.activation_peak_op in ("multiply", "add", "scale")
        ops = prog.global_block().ops
        assert 0 <= sc.activation_peak_index < len(ops)
        assert "activation_peak_op" in sc.to_dict()

    def test_true_over_capacity_still_rejected(self):
        # the tighter bound must NOT admit a plan whose liveness peak
        # itself busts the device: capacity under the real footprint
        # stays a hard rejection
        _, _, _, probe = self._score(capacity=None or 1e15)
        tight = probe.hbm_bytes * 0.5
        _, _, _, sc = self._score(tight)
        assert sc.rejected is not None and "HBM" in sc.rejected

    def test_gemm_operands_pinned_for_backward(self):
        # a matmul's input is saved for the wgrad: pinning must hold it
        # to program end, so the peak can never be below operand+output
        from paddle_tpu import static
        from paddle_tpu.distributed.spmd.propagate import \
            propagate_program
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", (32, 32), "float32")
            h = x * 2.0              # op-produced GEMM operand
            w = paddle.ones((32, 32), "float32")
            y = paddle.matmul(h, w)
            z = y + 1.0
        mesh = _mesh(data=2, tp=4)
        plan = propagate_program(prog, mesh, {"x": None})
        sc = pcost.score_plan(prog, plan, mesh,
                              candidate_name="pin",
                              capacity_bytes=1e15)
        nb = 32 * 32 * 4
        # h pinned to end + y + z live at the final op -> >= 2 buffers
        assert sc.memory_breakdown["activations"] >= 2 * nb
