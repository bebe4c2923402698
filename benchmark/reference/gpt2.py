"""Plain GPT-2 training reference: float32 ``jax.numpy`` at ``highest`` matmul
precision, written from the published model (Radford et al. 2019; the
``openai-community/gpt2-medium`` config) and the published AdamW (Loshchilov
& Hutter 2019). No kernels, no mixed precision, nothing imported from the
program; weights come from ``benchmark.lib.weights`` and the seed.

Departures from a textbook loop, both for memory only: the batch is walked in
blocks of rows whose gradients are summed (the loss is a mean over tokens, so
the sum is exact), and each block of the model is rematerialised in the
backward pass (``jax.checkpoint``), which changes no number.

``precision="fp8"`` is the CONTROL, not a reference: the same mathematics with
every weight matmul in float8 (e4m3 operands forward, e5m2 gradients backward,
per-tensor scales) — the precision just below the bf16 the configuration
computes in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights as weights_lib

BLOCK_LEAVES = ("ln_1.w", "ln_1.b", "attn.c_attn.w", "attn.c_attn.b",
                "attn.c_proj.w", "attn.c_proj.b", "ln_2.w", "ln_2.b",
                "mlp.c_fc.w", "mlp.c_fc.b", "mlp.c_proj.w", "mlp.c_proj.b")
GLOBAL_LEAVES = ("wte", "wpe", "ln_f.w", "ln_f.b")


def init_params(cfg: dict, seed: int) -> dict:
    """``{"wte": ..., "blocks": {leaf: [n_layer, ...]}}`` in float32."""
    flat = weights_lib.make("gpt2", cfg, seed, jnp.float32)
    out = {name: flat[(-1, name)] for name in GLOBAL_LEAVES}
    out["blocks"] = {
        name: jnp.stack([flat[(i, name)] for i in range(cfg["n_layer"])])
        for name in BLOCK_LEAVES}
    return out


def leaf_norms(tree: dict) -> dict:
    """``{(layer, name): l2 norm}`` with the table's own keys."""
    out = {(-1, name): jnp.linalg.norm(tree[name]) for name in GLOBAL_LEAVES}
    for name in BLOCK_LEAVES:
        stacked = tree["blocks"][name]
        per = jnp.sqrt(jnp.sum(
            jnp.square(stacked).reshape(stacked.shape[0], -1), axis=1))
        for i in range(stacked.shape[0]):
            out[(i, name)] = per[i]
    return out


def _round_fp8(a, dtype, top):
    """Round to a float8 type with a per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-12) / top
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_matmul(x, w):
    """The usual float8 training recipe: both operands of the forward
    matmul in e4m3, the incoming gradient of both backward matmuls in e5m2,
    accumulation in float32."""
    return jnp.matmul(_round_fp8(x, jnp.float8_e4m3fn, 448.0),
                      _round_fp8(w, jnp.float8_e4m3fn, 448.0))


def _fp8_fwd(x, w):
    xq = _round_fp8(x, jnp.float8_e4m3fn, 448.0)
    wq = _round_fp8(w, jnp.float8_e4m3fn, 448.0)
    return jnp.matmul(xq, wq), (xq, wq)


def _fp8_bwd(res, g):
    xq, wq = res
    gq = _round_fp8(g, jnp.float8_e5m2, 57344.0)
    dx = jnp.matmul(gq, jnp.swapaxes(wq, -1, -2))
    dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                    gq.reshape(-1, gq.shape[-1]))
    return dx, dw


_fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def loss_sum(params: dict, ids, cfg: dict, precision: str = "float32"):
    """Sum over rows and positions of the next-token cross entropy."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    rows, seq = ids.shape
    hd = cfg["n_embd"] // heads

    def mm(x, w):
        if precision == "fp8":
            return _fp8_matmul(x, w)
        return jnp.matmul(x, w)

    def block(x, lp):
        y = _layer_norm(x, lp["ln_1.w"], lp["ln_1.b"], eps)
        qkv = mm(y, lp["attn.c_attn.w"]) + lp["attn.c_attn.b"]
        q, k, v = (t.reshape(rows, seq, heads, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        a = a.transpose(0, 2, 1, 3).reshape(rows, seq, heads * hd)
        x = x + mm(a, lp["attn.c_proj.w"]) + lp["attn.c_proj.b"]
        y = _layer_norm(x, lp["ln_2.w"], lp["ln_2.b"], eps)
        y = _gelu_new(mm(y, lp["mlp.c_fc.w"]) + lp["mlp.c_fc.b"])
        return x + mm(y, lp["mlp.c_proj.w"]) + lp["mlp.c_proj.b"], None

    x = params["wte"][ids] + params["wpe"][jnp.arange(seq)]
    x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
    x = _layer_norm(x, params["ln_f.w"], params["ln_f.b"], eps)
    logits = mm(x[:, :-1], params["wte"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
    return -jnp.sum(picked)


def loss_and_grads(params, batch, cfg, precision="float32", rows_per_block=2):
    """Mean loss over the batch's predicted tokens and its gradients, the
    batch walked ``rows_per_block`` rows at a time."""
    fn = jax.jit(jax.value_and_grad(loss_sum), static_argnums=(2, 3))
    frozen = _Frozen(cfg)
    total, grads = 0.0, None
    batch = np.asarray(batch)
    for r in range(0, batch.shape[0], rows_per_block):
        part, g = fn(params, jnp.asarray(batch[r:r + rows_per_block]),
                     frozen, precision)
        total = total + part
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    n = batch.shape[0] * (batch.shape[1] - 1)
    return total / n, jax.tree_util.tree_map(lambda a: a / n, grads)


class _Frozen(dict):
    """A config dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@jax.jit
def _adamw(params, grads, m, v, t, lr, wd, b1, b2, eps):
    def leaf(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        step = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
        return p * (1 - lr * wd) - lr * step, m2, v2

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _p, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def follow(cfg: dict, seed: int, batches, opt: dict, calls,
           precision: str = "float32") -> dict:
    """Train from the seed's weights over ``batches`` (one per step), the
    steps grouped into ``calls`` (``[1, 2]``: one step, then two); AdamW's
    moments and step count start afresh at every call, as each ``fit`` call of
    the program starts them. Returns float lists and ``{leaf: norm}`` dicts:
    ``losses`` per step, ``grad_norms`` of the first step's gradient, and
    ``delta_norms`` of the parameters' change after the last step."""
    params = init_params(cfg, seed)
    start = params
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    losses, grad_norms, step = [], None, 0
    for n_steps in calls:
        m, v = zeros(), zeros()
        for t in range(1, n_steps + 1):
            loss, grads = loss_and_grads(params, batches[step], cfg, precision)
            if grad_norms is None:
                grad_norms = leaf_norms(grads)
            params, m, v = _adamw(
                params, grads, m, v, jnp.float32(t), opt["learning_rate"],
                opt["weight_decay"], opt["beta1"], opt["beta2"],
                opt["epsilon"])
            losses.append(loss)
            step += 1
    delta = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, start))
    to_float = lambda d: {k: float(x) for k, x in d.items()}  # noqa: E731
    return {"losses": [float(x) for x in losses],
            "grad_norms": to_float(grad_norms),
            "delta_norms": to_float(delta)}
