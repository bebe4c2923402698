"""Program-level verification: trace the framework's ladder-style
programs and run the static verifier over each recorded op-list IR.

``python -m tools.tpulint --programs`` (and the tier-1 gate in
``tests/test_program_verifier.py``) drives :func:`run`: every program
the test suite already traces — a GPT block with
loss, a tiny llama forward, an SGD train step, in-graph control flow,
the fusion pass's rewritten plan, and a sharded program over a mesh —
must verify CLEAN. A finding here is new framework debt: fix the
program, or suppress it in the verifier call with a justification.
Round 21 adds the serving decode/verify tick programs (the paged
engine's jitted chunk replayed eagerly over live cache state) and the
pipeline stage slices + cross-stage send/recv contract (TPU8xx).

Kept import-light: heavy imports happen inside :func:`build_programs`
so ``python -m tools.tpulint`` without ``--programs`` stays AST-only.
"""
from __future__ import annotations

from typing import Callable, List, Tuple

__all__ = ["build_programs", "run"]


def _gpt_loss_program(batch=2):
    """Tiny GPT forward + loss recorded as a static.Program."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.ops as ops
    from paddle_tpu import static
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import functional as F

    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=16, use_flash_attention=False))
    prog = static.Program()
    with static.program_guard(prog):
        ids = static.data("ids", [batch, 8], "int64")
        logits = model(ids)
        if isinstance(logits, (tuple, list)):
            logits = logits[0]
        v = logits.shape[-1]
        loss = F.cross_entropy(
            ops.reshape(logits[:, :-1, :], [-1, v]),
            ops.reshape(ids[:, 1:], [-1]))
        loss = loss.mean()
    return prog, [id(loss)], model


def _programs_impl() -> List[Tuple[str, Callable[[], object]]]:
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.static import verifier

    def gpt_loss():
        prog, fetch, _m = _gpt_loss_program()
        return verifier.check(prog, fetch_ids=fetch, label="gpt_loss")

    def gpt_loss_sharded():
        import jax
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import mesh as mesh_mod
        n = len(jax.devices())
        # batch == device count: the data axis divides it exactly
        prog, fetch, _m = _gpt_loss_program(batch=n)
        mesh = mesh_mod.build_mesh({"data": n})
        return verifier.check(prog, mesh=mesh,
                              in_specs={"ids": P("data", None)},
                              fetch_ids=fetch, label="gpt_loss_sharded")

    def llama_forward():
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
        return verifier.check(prog, fetch_ids=[id(logits)],
                              label="llama_forward")

    def sgd_train_step():
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        paddle.seed(7)
        model = nn.Sequential(nn.Linear(8, 16), nn.GELU(),
                              nn.Linear(16, 4))
        sgd = opt.SGD(learning_rate=0.1,
                      parameters=model.parameters())
        x = paddle.to_tensor(np.random.rand(4, 8).astype("float32"))

        def step(inp):
            loss = model(inp).mean()
            loss.backward()
            sgd.step()
            sgd.clear_grad()
            return loss

        return verifier.audit_step(step, (x,), label="sgd_train_step")

    def control_flow():
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [4], "float32")
            y = static.nn.cond(paddle.to_tensor(True),
                               lambda: x * 2.0, lambda: x * 3.0)

            def c(i, v):
                return i < 4

            def b(i, v):
                return [i + 1, v + y]

            i0 = paddle.to_tensor(0)
            _i, out = static.nn.while_loop(c, b, [i0, x])
        return verifier.check(prog, fetch_ids=[id(out)],
                              label="control_flow")

    def fused_plan():
        # the fusion pass's rewritten plan must verify clean too: the
        # FusedSteps replay like _OpRecords and carry loc provenance
        from paddle_tpu.compile import fusion
        import paddle_tpu.nn as nn
        paddle.seed(7)
        lin = nn.Linear(16, 16)
        norm = nn.LayerNorm(16)
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [4, 16], "float32")
            h = nn.functional.gelu(lin(norm(x)))
        fetch = [id(h)]
        plan, _stats = fusion.fuse_program_ops(
            prog.global_block().ops, fetch)
        return verifier.check(plan, fetch_ids=fetch, label="fused_plan")

    def _paged_engine(speculate=False):
        """Tiny-GPT paged engine advanced one tick so the K/V caches,
        block tables, and slot state are live decode state."""
        from paddle_tpu.inference import serving as sv
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        paddle.seed(7)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=64, use_flash_attention=False))
        eng = sv.PagedEngine(model, max_batch=2, block_size=8,
                             num_blocks=32, max_blocks_per_seq=8,
                             speculate=speculate, speculate_k=2)
        eng.add_request([3, 5, 7, 9], max_new_tokens=8)
        eng.step()
        return sv, eng

    def _chunk_args(eng, tokens, seq):
        return eng._chunk_args(
            tokens, seq, eng.tables,
            np.zeros((eng.max_batch,), np.float32),
            np.ones((eng.max_batch,), np.float32),
            np.zeros((eng.max_batch,), np.int32),
            np.zeros((eng.max_batch,), np.int32))

    def serving_decode_tick():
        # the engine's decode tick is ONE jitted program
        # (inference/serving._paged_forward); replay it EAGERLY over
        # live engine state so the recorder sees the same op stream the
        # jit traces — a dispatched-but-unregistered op is TPU700 here
        sv, eng = _paged_engine()
        seq = eng.seq_lens.copy()
        if eng.slots[0] is not None:
            seq[0] = eng.slots[0].seq_len
        tokens = eng.last_token[:, None].astype(np.int32)
        return verifier.audit_step(
            sv._paged_forward,
            (eng.arch, tuple(eng._params))
            + tuple(_chunk_args(eng, tokens, seq)),
            label="serving_decode_tick")

    def serving_verify_tick():
        # the speculative sibling: one (B, k+1) verify program with the
        # in-graph accept-prefix — the fused decode path of round 18
        sv, eng = _paged_engine(speculate=True)
        k = eng._spec_k
        seq = eng.seq_lens.copy()
        if eng.slots[0] is not None:
            seq[0] = eng.slots[0].seq_len + k
        tokens = np.zeros((eng.max_batch, k + 1), np.int32)
        tokens[0, 0] = eng.last_token[0]
        return verifier.audit_step(
            sv._paged_verify,
            (eng.arch, tuple(eng._params))
            + tuple(_chunk_args(eng, tokens, seq))
            + (np.full((eng.max_batch,), k, np.int32),),
            label="serving_verify_tick")

    def moe_layer():
        # the GShard MoE block (distributed.fleet.moe): gate + stacked
        # experts dispatch as the registered moe_gate/moe_layer ops;
        # BOTH the output and the aux loss are fetched (the training
        # loop consumes l_aux — unfetched it would read as dead)
        from paddle_tpu.distributed.fleet.moe import MoELayer
        paddle.seed(7)
        layer = MoELayer(d_model=16, num_experts=4, top_k=2,
                         capacity_factor=2.0)
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [8, 16], "float32")
            y = layer(x)
            l_aux = layer.l_aux
        rep = verifier.check(prog, fetch_ids=[id(y), id(l_aux)],
                             label="moe_layer")
        # the liveness pass must be able to price it too: the peak
        # report is part of the op surface contract for ladder programs
        from paddle_tpu.static import liveness
        liveness.peak_report(prog, fetch_ids=[id(y), id(l_aux)])
        return rep

    def pipeline_stages():
        # every stage slice of a cost-partitioned program must verify
        # as a standalone op stream AND the cross-stage send/recv
        # contract must match (TPU801/802/803, verifier.check_stages)
        from paddle_tpu.distributed.pipeline import partition_program
        import paddle_tpu.nn as nn
        paddle.seed(7)
        blocks = []
        for _ in range(4):
            blocks += [nn.Linear(16, 16), nn.GELU()]
        model = nn.Sequential(*blocks)
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [4, 16], "float32")
            loss = (model(x) ** 2).mean()
        part = partition_program(prog, 2, fetch_ids=[id(loss)])
        return verifier.check_stages(part.stage_records(),
                                     label="pipeline_stages")

    return [("gpt_loss", gpt_loss),
            ("gpt_loss_sharded", gpt_loss_sharded),
            ("llama_forward", llama_forward),
            ("sgd_train_step", sgd_train_step),
            ("control_flow", control_flow),
            ("fused_plan", fused_plan),
            ("serving_decode_tick", serving_decode_tick),
            ("serving_verify_tick", serving_verify_tick),
            ("moe_layer", moe_layer),
            ("pipeline_stages", pipeline_stages)]


def build_programs():
    """(label, thunk) pairs; each thunk traces one framework program
    and returns its verifier Report."""
    return _programs_impl()


def run(quiet: bool = False) -> int:
    """Trace + verify every program; print findings; exit status 1 when
    any program is not verifier-clean."""
    failures = 0
    for label, thunk in build_programs():
        try:
            report = thunk()
        except Exception as e:      # a program that cannot trace IS debt
            failures += 1
            print(f"program {label}: TRACE FAILED — "
                  f"{type(e).__name__}: {e}")
            continue
        if report.findings:
            failures += 1
            print(report.render())
        elif not quiet:
            print(f"program {label}: clean "
                  f"({report.stats.get('ops', '?')} ops)")
    tail = "clean" if not failures else f"{failures} program(s) flagged"
    print(f"tpulint --programs: {tail}")
    return 1 if failures else 0
