"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, one touch of JAX, no child that needs the chip. Drives the two
main paths once through the entry points a user calls, at the full width of
models the repo already has (depth as published, weights random from a seed):

* **device**  — the default backend must be ``tpu`` (anything else exits
  non-zero: never a CPU run under a chip name), and the roofline tables must
  know the ``device_kind`` the chip reports.
* **kernels** — every ``pl.pallas_call`` site compiled by Mosaic (not the
  interpreter) and checked against its reference at the shapes the cells
  use: flash fwd / dQ / dKdV for every tile candidate by name, and the four
  ``fused_ops`` kernels at h 1024 / ffn 4096.
* **train**   — ``GPTForCausalLM(gpt2_medium())`` through
  ``distributed.auto_parallel.Engine.fit`` with ``optimizer.AdamW``, bf16
  autocast, the default DataLoader + DevicePrefetcher.
* **serve**   — one ``PagedEngine`` replica behind ``serving.Router`` on
  ``LlamaForCausalLM`` at the serving benchmark's width, greedy parity with
  ``model.generate``.

The last stdout line is the verdict, one JSON object of exactly
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device as
JAX reports it; the line before it is the full report (versions, cache
directory, per-phase ok / wall / compile seconds). Exit code 0 only if every
phase passed. ``python chip_smoke.py`` takes no size option and reads no
environment switch; the tier-1 tests import the phase functions with tiny
configs instead. Every wall / compile figure it prints is a smoke reading,
not a benchmark.
"""
from __future__ import annotations

import gc
import importlib.metadata
import json
import sys
import time
import traceback

import numpy as np

# ---------------------------------------------------------------- sizes
#: flash-attention shape classes the training cells use:
#: (heads, seq, head_dim) — d 64, two heads a 128-lane block (GPT-2 345M,
#: S 1024) and d 128 at S 2048 (the llama cells)
FLASH_SHAPES = ((4, 1024, 64), (4, 2048, 128))
#: fused_ops kernels: (rows, hidden, ffn, head_dim, seq)
FUSED_SHAPE = dict(rows=2048, hidden=1024, ffn=4096, head_dim=64, seq=1024)
#: train: GPT-2 345M at full width and depth; batch 4 x 1024 tokens with
#: block recompute is what fits 16 GB next to f32 params + AdamW moments
TRAIN = dict(batch=4, steps=6, recompute=True)
#: serve: a Llama-shaped decoder (vocab 32000, h 1024, ffn 2816,
#: L 16, 16 heads, bf16 weights), 8 requests of 32-192 prompt tokens
SERVE = dict(n_requests=8, prompt_range=(32, 192), new_tokens=32,
             max_batch=8, block_size=32)


class _CompileClock:
    """Sums the seconds JAX spends in backend compiles (persistent-cache
    lookups included — a warm cache shows as a smaller sum)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


def _mosaic_calls(lowered) -> int:
    """Mosaic kernels in a lowered program (0 = interpreted or replaced
    by an XLA composite)."""
    return lowered.as_text().count("tpu_custom_call")


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-6))


# --------------------------------------------------------------- device
def phase_device(require_platform="tpu") -> dict:
    import jax
    import jaxlib

    from paddle_tpu.observability import perf

    dev = jax.devices()[0]
    if jax.default_backend() != require_platform:
        raise RuntimeError(
            f"no TPU: jax.default_backend() is {jax.default_backend()!r} "
            f"({dev.device_kind}); chip_smoke.py only runs on the chip")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        # the roofline tables must KNOW this chip (unknown kinds raise)
        "peak_bf16_flops": perf.chip_peak_flops(dev),
        "peak_hbm_bytes_per_s": perf.chip_peak_bw(dev),
        "hbm_bytes": perf.chip_hbm_bytes(dev),
    }


# -------------------------------------------------------------- kernels
class _KernelChecks:
    """Compile each kernel by name, run it, compare with its reference.
    A failure does not stop the sweep: every broken kernel is named."""

    def __init__(self, expect_mosaic: bool, tol: float):
        self.expect_mosaic, self.tol = expect_mosaic, tol
        self.passed, self.failed = {}, {}

    def check(self, name, fn, args, refs):
        import jax
        try:
            lowered = jax.jit(fn).lower(*args)
            if self.expect_mosaic and not _mosaic_calls(lowered):
                raise RuntimeError("no Mosaic custom call in the lowered "
                                   "kernel (interpreted?)")
            outs = jax.tree_util.tree_leaves(lowered.compile()(*args))
            errs = [_rel_err(o, r) for o, r in zip(outs, refs, strict=True)]
            if not all(e < self.tol for e in errs):
                raise RuntimeError(f"mismatch vs reference: rel err {errs}")
        except Exception as e:
            self.failed[name] = f"{type(e).__name__}: {e}"[:800]
            print(f"[chip_smoke] kernel {name}: FAILED {self.failed[name]}",
                  flush=True)
            return
        self.passed[name] = round(max(errs), 5)


def _attn_ref(q, k, v, scale, window=None):
    """Plain causal attention over (1, S, H, d), f32 softmax: (out, lse),
    the logsumexp as the kernels lay it out, a row a head, the heads of a
    lane block together: (1, H // hpb, hpb, S). ``window``: a query sees its
    own key and the ``window - 1`` before it."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    n = s.shape[-1]
    seen = jnp.tril(jnp.ones((n, n), bool))
    if window is not None:
        seen = seen & ~jnp.tril(jnp.ones((n, n), bool), k=-window)
    s = jnp.where(seen, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    heads = q.shape[2]
    hpb = fa._head_layout(heads, q.shape[3])[0]
    return (jnp.einsum("bhqk,bkhd->bqhd", p, v),
            jax.nn.logsumexp(s, axis=-1).reshape(1, heads // hpb, hpb, n))


def _check_flash(checks, heads, seq, d):
    import math

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    scale = 1.0 / math.sqrt(d)

    @jax.jit
    def inputs_and_references(key):
        q, k, v, g = (jax.random.normal(kx, (1, seq, heads, d)).astype(
            jnp.bfloat16) for kx in jax.random.split(key, 4))
        (out, lse), vjp = jax.vjp(lambda *a: _attn_ref(*a, scale), q, k, v)
        return (q, k, v, g), out, lse, vjp((g, jnp.zeros_like(lse)))

    # the backward kernels take the XLA reference's own output and
    # logsumexp as residuals, so a broken forward tile cannot mask them
    (q, k, v, g), ref_out, lse, ref_grads = inputs_and_references(
        jax.random.key(seq + d))
    # every tile a call can be given: a causal call's constant, and the
    # non-causal candidates under the causal kernels too
    causal_tile = [(fa.CAUSAL_BLOCK, fa.CAUSAL_BLOCK)]
    for bq, bk in causal_tile + fa.FWD_TILE_CANDIDATES:
        checks.check(
            f"flash_fwd[S={seq},d={d}]({bq},{bk})",
            lambda *a, bq=bq, bk=bk: fa._flash_fwd_bshd(
                *a, causal=True, scale=scale, block_q=bq, block_k=bk),
            (q, k, v), (ref_out, lse))
    for bq, bk in causal_tile + fa.BWD_TILE_CANDIDATES:
        # one jit holds both backward pallas_calls: dQ, then dK/dV
        checks.check(
            f"flash_dq_dkdv[S={seq},d={d}]({bq},{bk})",
            lambda *a, bq=bq, bk=bk: fa._flash_bwd_bshd(
                *a, causal=True, scale=scale, block_q=bq, block_k=bk),
            (q, k, v, ref_out, lse, g), ref_grads)
    # the same kernels under a window of half the sequence, at the causal
    # call's tile and at a quarter of the sequence (tiles under the band
    # that are skipped, tiles its lower edge crosses)
    window = seq // 2

    @jax.jit
    def windowed_references(q, k, v, g):
        (out, lse), vjp = jax.vjp(
            lambda *a: _attn_ref(*a, scale, window), q, k, v)
        return out, lse, vjp((g, jnp.zeros_like(lse)))

    ref_out, lse, ref_grads = windowed_references(q, k, v, g)
    for tile in sorted({fa.CAUSAL_BLOCK, max(seq // 4, 8)}):
        kw = dict(causal=True, scale=scale, block_q=tile, block_k=tile,
                  window=window)
        checks.check(
            f"flash_fwd[S={seq},d={d},window={window}]({tile},{tile})",
            lambda *a, kw=kw: fa._flash_fwd_bshd(*a, **kw),
            (q, k, v), (ref_out, lse))
        checks.check(
            f"flash_dq_dkdv[S={seq},d={d},window={window}]({tile},{tile})",
            lambda *a, kw=kw: fa._flash_bwd_bshd(*a, **kw),
            (q, k, v, ref_out, lse, g), ref_grads)


def _check_fused(checks, rows, hidden, ffn, head_dim, seq):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.llama import rope_rotate
    from paddle_tpu.nn.functional import fused
    from paddle_tpu.ops.pallas import fused_ops as fk

    bf, f32 = jnp.bfloat16, jnp.float32

    @jax.jit
    def inputs_and_references(key):
        ks = jax.random.split(key, 8)
        x = jax.random.normal(ks[0], (rows, hidden)).astype(bf)
        res = jax.random.normal(ks[1], (rows, hidden)).astype(bf)
        nw = (1 + 0.1 * jax.random.normal(ks[2], (hidden,))).astype(bf)
        nb = (0.1 * jax.random.normal(ks[3], (hidden,))).astype(bf)
        w1 = (0.02 * jax.random.normal(ks[4], (hidden, ffn))).astype(bf)
        b1 = (0.1 * jax.random.normal(ks[5], (ffn,))).astype(bf)
        wq = (0.02 * jax.random.normal(ks[6], (hidden, hidden))).astype(bf)
        h1 = jax.random.normal(ks[7], (rows, ffn)).astype(bf)

        def norm(a, kind):
            return fused._norm32(a.astype(f32), nw.astype(f32),
                                 nb.astype(f32), kind, 1e-5).astype(a.dtype)

        refs = {
            "layer_norm": (norm(x + res, "layer_norm"), x + res),
            "rms_norm": (norm(x + res, "rms_norm"), x + res),
            "bias_act": (fused._act(h1 + b1, "gelu"),),
            "matmul": (fused._act(
                jnp.matmul(norm(x, "layer_norm"), w1) + b1, "gelu"),),
            "rope": (rope_rotate(
                jnp.matmul(x, wq).reshape(rows // seq, seq, -1, head_dim),
                10000.0, 0).reshape(rows, hidden),)}
        return (x, res, nw, nb, w1, b1, wq, h1), refs

    (x, res, nw, nb, w1, b1, wq, h1), all_refs = inputs_and_references(
        jax.random.key(7))
    for kind in ("layer_norm", "rms_norm"):
        refs = all_refs[kind]
        for br in fk.NORM_ROW_CANDIDATES:
            checks.check(
                f"fused_residual_norm[{kind}]({br})",
                lambda x, r, kind=kind, br=br: fk.fused_residual_norm(
                    x, r, nw, nb, kind=kind, eps=1e-5, block_rows=br),
                (x, res), refs)
    for br in fk.NORM_ROW_CANDIDATES:
        checks.check(
            f"fused_bias_act[gelu]({br})",
            lambda h, br=br: fk.fused_bias_act(h, b1, act="gelu",
                                               block_rows=br),
            (h1,), all_refs["bias_act"])
    for cand in fk.MATMUL_TILE_CANDIDATES:
        # the shape gates see the clamped tile, as nn.functional.fused
        # applies them
        bm, bn = min(cand[0], rows), cand[1]
        if fk.pallas_ok_matmul(rows, hidden, ffn, bm, min(bn, ffn)):
            checks.check(
                f"fused_matmul[layer_norm,gelu]{cand}",
                lambda x, bm=bm, bn=bn: fk.fused_matmul(
                    x, w1, b1, nw, nb, norm_kind="layer_norm", act="gelu",
                    eps=1e-5, block_m=bm, block_n=bn),
                (x,), all_refs["matmul"])
        if fk.pallas_ok_matmul_rope(rows, hidden, hidden, head_dim, bm,
                                    min(bn, hidden)):
            checks.check(
                f"fused_matmul_rope[d={head_dim}]{cand}",
                lambda x, bm=bm, bn=bn: fk.fused_matmul_rope(
                    x, wq, None, seq=seq, head_dim=head_dim, block_m=bm,
                    block_n=bn),
                (x,), all_refs["rope"])


def phase_kernels(flash_shapes=FLASH_SHAPES, fused_shape=FUSED_SHAPE,
                  tol=2e-2) -> dict:
    import jax

    checks = _KernelChecks(jax.default_backend() == "tpu", tol)
    for heads, seq, d in flash_shapes:
        _check_flash(checks, heads, seq, d)
    _check_fused(checks, **fused_shape)
    if checks.failed:
        raise RuntimeError(
            f"{len(checks.failed)} of "
            f"{len(checks.failed) + len(checks.passed)} kernels failed: "
            + json.dumps(checks.failed))
    return {"kernels_checked": len(checks.passed),
            "max_rel_err": checks.passed}


# ---------------------------------------------------------------- train
def phase_train(cfg=None, batch=TRAIN["batch"], steps=TRAIN["steps"]) -> dict:
    """GPT through ``Engine.fit``: one optimizer step per epoch over one
    fixed batch of tokens, so ``fit``'s per-epoch history IS the per-step
    loss. The Engine's default mesh is data-parallel over every local
    device, so on a four-chip host the same call trains on
    ``build_mesh({"dp": 4})`` — and then params and batch must really
    span the chips."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.io import Dataset
    from paddle_tpu.models import GPTForCausalLM, gpt2_medium
    from paddle_tpu.ops.pallas import autotune as at

    if cfg is None:
        cfg = gpt2_medium(recompute=TRAIN["recompute"])
    seq = cfg.max_seq_len
    paddle.seed(0)

    class CausalLMLoss(nn.Layer):
        """Next-token loss of the wrapped LM under bf16 autocast."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                _, loss = self.lm(ids, labels=ids)
            return loss

    class FixedTokens(Dataset):
        """One batch of seeded random sequences, repeated every epoch."""

        def __init__(self):
            self.ids = np.random.RandomState(0).randint(
                0, cfg.vocab_size, (batch, seq)).astype(np.int64)

        def __len__(self):
            return batch

        def __getitem__(self, i):
            return self.ids[i], self.ids[i]

    data = FixedTokens()
    net = CausalLMLoss(GPTForCausalLM(cfg))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.1,
                                 parameters=net.parameters())
    engine = Engine(net, loss=lambda loss, _labels: loss, optimizer=opt)
    n_dev = engine._mesh.size
    t0 = time.perf_counter()
    losses = engine.fit(data, epochs=steps, batch_size=batch)
    fit_s = time.perf_counter() - t0

    if not (len(losses) == steps and all(np.isfinite(losses))):
        raise RuntimeError(f"non-finite or missing losses: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")
    n_compiles = engine._train_step._cache_size()
    if n_compiles != 1:
        raise RuntimeError(f"train step compiled {n_compiles} times")
    if any(p._data.is_deleted() for p in engine._params):
        raise RuntimeError("fit left Parameters on donated (dead) buffers")
    failed = dict(at.get_cache().failures)
    if failed:
        raise RuntimeError(f"autotune candidates failed: {failed}")

    # the compiled step must hold the Mosaic flash kernel wherever the
    # model asks for it (S >= FLAGS_flash_min_seq_len on a TPU backend)
    pa = [p._data for p in engine._params]
    x = engine._shard_batch(data.ids)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), tree)

    lowered = engine._train_step.lower(
        abstract(pa), jax.eval_shape(engine._init_opt_state, pa),
        jax.ShapeDtypeStruct((), jnp.float32), abstract(x), abstract(x))
    mosaic = _mosaic_calls(lowered)
    want_flash = (jax.default_backend() == "tpu" and cfg.use_flash_attention
                  and seq >= paddle.get_flags(
                      "FLAGS_flash_min_seq_len")["FLAGS_flash_min_seq_len"])
    if want_flash and not mosaic:
        raise RuntimeError("no Mosaic custom call in the lowered train "
                           "step: flash attention fell back to XLA")

    out = {"model": f"gpt2 h{cfg.hidden_size} L{cfg.num_layers} "
                    f"heads{cfg.num_heads} S{seq} V{cfg.vocab_size}",
           "params": int(sum(p.size for p in engine._params)),
           "batch": batch, "steps": steps, "recompute": cfg.recompute,
           "losses": [round(float(l), 4) for l in losses],
           "step_compiles": n_compiles, "mosaic_custom_calls": mosaic,
           "fit_wall_s": round(fit_s, 2),
           "mesh": dict(engine._mesh.shape)}
    if n_dev > 1:
        spans = {len(a.sharding.device_set) for a in [*pa, x]}
        # memory_stats() is None where the backend keeps none (the CPU)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in engine._mesh.devices.flat]
        if spans != {n_dev} or any(p == 0 for p in peaks):
            raise RuntimeError(f"params/batch span {spans} devices, peak "
                               f"bytes per device {peaks}; want {n_dev}")
        out["peak_bytes_per_device"] = peaks
    return out


# ---------------------------------------------------------------- serve
def phase_serve(cfg=None, n_requests=SERVE["n_requests"],
                prompt_range=SERVE["prompt_range"],
                new_tokens=SERVE["new_tokens"],
                max_batch=SERVE["max_batch"],
                block_size=SERVE["block_size"], bf16=True) -> dict:
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.inference.resilience import ReplicaState, RequestStatus
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Router

    if cfg is None:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_layers=16,
                          num_heads=16, max_seq_len=1024,
                          use_flash_attention=False)
    paddle.seed(1)
    model = LlamaForCausalLM(cfg)
    if bf16:
        for p in model.parameters():    # bf16 weights: serving discipline
            if np.dtype(p._data.dtype) == np.float32:
                p._swap_payload(p._data.astype(jnp.bfloat16))
    rng = np.random.RandomState(11)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, size=int(n))]
               for n in rng.randint(*prompt_range, size=n_requests)]
    blocks_per_seq = -(-(prompt_range[1] + new_tokens) // block_size) + 1
    replica = PagedEngine(model, max_batch=max_batch, block_size=block_size,
                          num_blocks=blocks_per_seq * max_batch * 2,
                          max_blocks_per_seq=blocks_per_seq)
    router = Router([replica]).warmup()
    t0 = time.perf_counter()
    rids = [router.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    tokens = router.run_to_completion()
    serve_s = time.perf_counter() - t0

    # the engine CONTAINS tick failures (requests go FAILED, the replica
    # DEGRADED, run_to_completion still returns): assert none happened
    unfinished = {rid: (oc.status, oc.detail)
                  for rid, oc in router.outcomes.items()
                  if oc.status != RequestStatus.FINISHED}
    if unfinished or set(router.outcomes) != set(rids):
        raise RuntimeError(f"requests not FINISHED: {unfinished}")
    if replica.tick_failures:
        raise RuntimeError(f"{replica.tick_failures} tick failures")
    if replica.lifecycle.state != ReplicaState.READY:
        raise RuntimeError(f"replica is {replica.lifecycle.state}")
    if any(len(tokens[r]) != new_tokens for r in rids):
        raise RuntimeError("a request returned the wrong number of tokens")

    # greedy parity with model.generate on one prompt. Both decode paths
    # are checked teacher-forced against ONE full-recompute forward: each
    # token must be that forward's argmax up to the weight dtype's
    # rounding (bf16 near-ties may flip argmax between two correct
    # paths; with f32 weights the tolerance leaves no room and the
    # tokens must be equal).
    prompt, served = prompts[0], tokens[rids[0]]
    gen = model.generate(np.asarray([prompt], np.int32),
                         max_new_tokens=new_tokens)
    gen = [int(t) for t in np.asarray(gen.numpy())[0, len(prompt):]]
    eps = float(jnp.finfo(jnp.bfloat16 if bf16 else jnp.float32).eps)
    forward = paddle.jit.to_static(lambda ids: model(ids))
    margins = {}
    for name, toks in (("router", served), ("generate", gen)):
        ids = np.asarray([prompt + toks[:-1]], np.int32)
        with paddle.no_grad():
            logits = np.asarray(forward(paddle.to_tensor(ids)).numpy(),
                                np.float32)[0, len(prompt) - 1:]
        top = logits.max(-1)
        chosen = logits[np.arange(len(toks)), toks]
        margins[name] = float(np.max((top - chosen) / np.abs(top)))
        if margins[name] > 4 * eps:
            raise RuntimeError(
                f"{name} tokens are not greedy under the reference "
                f"forward: worst relative logit margin {margins[name]:.4f} "
                f"> {4 * eps:.4f}; router {served} generate {gen}")
    agree = next((i for i, (a, b) in enumerate(zip(served, gen)) if a != b),
                 new_tokens)
    if not bf16 and agree != new_tokens:
        raise RuntimeError(f"greedy parity broke at token {agree}: router "
                           f"{served} vs generate {gen}")
    return {"model": f"llama h{cfg.hidden_size} L{cfg.num_layers} "
                     f"heads{cfg.num_heads} V{cfg.vocab_size} "
                     f"{'bf16' if bf16 else 'f32'}",
            "requests": n_requests, "finished": len(rids),
            "prompt_tokens": [len(p) for p in prompts],
            "new_tokens": new_tokens, "ticks": replica._ticks,
            "tick_failures": replica.tick_failures,
            "lifecycle": replica.lifecycle.state,
            "greedy_margin_vs_reference": {
                k: round(v, 5) for k, v in margins.items()},
            "greedy_margin_allowed": 4 * eps,
            "router_generate_common_prefix": agree,
            "serve_wall_s": round(serve_s, 2)}


# ----------------------------------------------------------------- main
def run_phases(phases) -> dict:
    """Run ``[(name, fn)]`` in order; every phase runs even after a
    failure so one chip call reports everything that is broken."""
    import jax

    clock = _CompileClock()
    report = {}
    for name, fn in phases:
        gc.collect()    # the previous phase's params leave HBM first
        t0, c0 = time.perf_counter(), clock.total
        try:
            detail = fn()
            ok = True
        except Exception as e:
            traceback.print_exc()
            detail, ok = {"error": f"{type(e).__name__}: {e}"[:4000]}, False
        stats = jax.devices()[0].memory_stats() or {}
        report[name] = {
            "ok": ok, "wall_s": round(time.perf_counter() - t0, 2),
            "compile_s": round(clock.total - c0, 2),
            "peak_hbm_bytes": stats.get("peak_bytes_in_use"), **detail}
        print(f"[chip_smoke] {name}: {'ok' if ok else 'FAILED'} "
              f"wall {report[name]['wall_s']}s "
              f"compile {report[name]['compile_s']}s", flush=True)
    return report


def verdict(ok: bool, device: dict) -> dict:
    """The last stdout line: these keys and no others (the driver's check
    reads it; everything else belongs to the report line before it)."""
    return {"ok": bool(ok),
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def main() -> int:
    from paddle_tpu.compile.cache import enable_jax_cache

    cache_dir = enable_jax_cache()      # before the first compile
    try:
        device = phase_device()
    except Exception as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    phases = run_phases([("kernels", phase_kernels), ("train", phase_train),
                         ("serve", phase_serve)])
    phases = {"device": {"ok": True, **device}, **phases}
    ok = all(p["ok"] for p in phases.values())
    print(json.dumps({
        "report": "chip_smoke", "ok": ok,
        "versions": device["versions"],
        "compile_cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t0, 2),
        "note": "smoke, not a benchmark",
        "phases": phases}))
    print(json.dumps(verdict(ok, device)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
