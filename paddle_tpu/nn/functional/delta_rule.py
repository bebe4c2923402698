"""Gated delta-rule linear attention with a carried state.

Reference: Yang, Kautz and Hatamizadeh 2024, "Gated Delta Networks"
(arXiv:2412.06464), as the ``olmo_hybrid`` / gated-delta-net model families
use it. Per head with a state ``S`` of ``(d_k, d_v)``, a decay ``alpha_t`` in
(0, 1) and a write strength ``beta_t`` (in (0, 2) where the family allows
negative eigenvalues, Grazzi et al., arXiv:2411.12537)::

    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
        = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

where Mamba-2's transition is a scalar a head (``ssm.py``: a masked product
inside a chunk), this one is a matrix, so the chunked form needs a triangular
solve inside every chunk. Three pieces, each taking and returning what a
serving cache carries so a sequence can be fed in pieces:

* ``gated_delta_chunk`` — the chunked form for a run of tokens that
  CONTINUES from a carried state. Over ``C`` tokens with ``g_i`` the
  cumulative log decay, ``Gamma_ij = exp(g_i - g_j)`` (the exp of a masked
  difference, never a quotient), ``A = strict_lower(diag(beta) (Gamma * K
  K^T))`` and ``T = (I + A)^-1 diag(beta)``: ``W = T (gamma * K)``, ``U = T
  V``, against the carried state ``U' = U - W S``, ``O = (gamma * Q) S +
  lower(Gamma * Q K^T) U'``, ``S <- gamma_C S + ((gamma_C / gamma) * K)^T
  U'``. ``T`` does not depend on ``S``, so every chunk's solve runs at once
  and only the short state carry is a scan. ``(I + A)^-1`` is
  ``jax.scipy.linalg.solve_triangular`` (unit lower, ``C x C``, float32).
* ``gated_delta_step`` — the recurrence itself for one token (decode),
  written so that the state is READ ONCE: ``S^T k`` and ``S^T q`` are two
  reductions of one pass, and ``o = alpha S^T q + (k . q) u`` needs no second
  look at the new state.
* ``gated_rms_norm`` — ``RMSNorm(o) * w * silu(g)``: the norm FIRST, then the
  gate (``ssm.gated_group_rms_norm`` gates first).

Everything here is float32 at ``highest`` matmul precision: entries of ``A``
reach 2, the rule is ~5.6 MFLOP a token a layer beside 177 MFLOP of
projections, and bfloat16 operands would buy nothing that shows. A row with
``valid`` false is ``beta = 0, alpha = 1``: it leaves the state as it was.

**The packed state** (what a serving slot keeps, and what the decode kernel
``ops.pallas.delta_rule`` reads): ``(B, H / p, d_k, p * d_v)``, ``p`` heads
side by side in the minor dimension so that it is whole 128-lane tiles (a
192-wide minor dimension is laid out in 256 lanes on a TPU: a third more
bytes in HBM and in every step). ``heads_packed`` gives ``p``;
``pack_state`` / ``unpack_state`` convert; ``gated_delta_step`` takes the
packed form when ``packed=p`` is given.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import dispatch, flags
from ...core.tensor import Tensor, as_tensor

__all__ = ["gated_delta_chunk", "gated_delta_step", "gated_rms_norm",
           "heads_packed", "pack_state", "unpack_state"]

_HI = jax.lax.Precision.HIGHEST
_LANES = 128


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


# --------------------------------------------------------- the packed state
def heads_packed(heads: int, value_dim: int) -> int:
    """How many heads share a row of the packed state: the fewest that make
    the minor dimension whole lane tiles and divide ``heads``, else 1."""
    for p in range(1, heads + 1):
        if heads % p == 0 and (p * value_dim) % _LANES == 0:
            return p
    return 1


def pack_state(state, p: int):
    """``(B, H, d_k, d_v)`` -> ``(B, H / p, d_k, p * d_v)``."""
    b, h, dk, dv = state.shape
    return state.reshape(b, h // p, p, dk, dv).transpose(
        0, 1, 3, 2, 4).reshape(b, h // p, dk, p * dv)


def unpack_state(packed, p: int):
    """``(B, H / p, d_k, p * d_v)`` -> ``(B, H, d_k, d_v)``."""
    b, g, dk, l = packed.shape
    return packed.reshape(b, g, dk, p, l // p).transpose(
        0, 1, 3, 2, 4).reshape(b, g * p, dk, l // p)


# ------------------------------------------------------------ array level
def chunk_arrays(q, k, v, alpha_log, beta, state, valid, chunk_size):
    """``q`` / ``k`` (B, T, H, d_k) (``k`` of unit length, ``q`` scaled: the
    caller's), ``v`` (B, T, H, d_v), ``alpha_log`` (B, T, H) <= 0, ``beta``
    (B, T, H), ``state`` (B, H, d_k, d_v) float32, ``valid`` (B, T) bool or
    None. Returns ``(o (B, T, H, d_v) float32, the state after the last
    token)``."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha_log, beta = alpha_log.astype(f32), beta.astype(f32)
    if valid is not None:
        live = valid[..., None]
        alpha_log = jnp.where(live, alpha_log, 0.0)
        beta = jnp.where(live, beta, 0.0)
    c = min(chunk_size, t)
    pad = -t % c
    if pad:     # beta = 0, alpha = 1 rows at the end: the state passes
        q, k, v, alpha_log, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, alpha_log, beta))
    n = (t + pad) // c

    def heads_first(a):     # (B, T, H, ...) -> (B, H, n, c, ...)
        a = a.reshape((bsz, n, c, h) + a.shape[3:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    g = jnp.cumsum(heads_first(alpha_log), axis=-1)        # (B, H, n, c)
    beta = heads_first(beta)
    lower = jnp.tril(jnp.ones((c, c), bool))
    # Gamma_ij = gamma_i / gamma_j for i >= j, as the exp of a difference
    # that is masked BEFORE the exp (an unmasked upper entry overflows)
    gam = jnp.where(lower, jnp.exp(jnp.where(
        lower, g[..., :, None] - g[..., None, :], 0.0)), 0.0)
    kk = jnp.einsum("bhnik,bhnjk->bhnij", k, k, precision=_HI)
    a_mat = jnp.where(jnp.tril(lower, -1), beta[..., :, None] * gam * kk,
                      0.0)
    gamma = jnp.exp(g)[..., None]                          # (B, H, n, c, 1)
    rhs = beta[..., None] * jnp.concatenate([gamma * k, v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        a_mat + jnp.eye(c, dtype=f32), rhs, lower=True, unit_diagonal=True)
    w, u = solved[..., :dk], solved[..., dk:]
    qk = gam * jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=_HI)
    to_end = jnp.exp(g[..., -1:] - g)[..., None]           # gamma_C / gamma
    total = jnp.exp(g[..., -1])                            # (B, H, n)

    def carry(s, chunk):
        w_c, u_c, q_c, qk_c, k_end, total_c = chunk
        u_p = u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, s, precision=_HI)
        o_c = (jnp.einsum("bhik,bhkv->bhiv", q_c, s, precision=_HI)
               + jnp.einsum("bhij,bhjv->bhiv", qk_c, u_p, precision=_HI))
        s = (total_c[..., None, None] * s
             + jnp.einsum("bhik,bhiv->bhkv", k_end, u_p, precision=_HI))
        return s, o_c

    per_chunk = tuple(jnp.moveaxis(a, 2, 0) for a in
                      (w, u, gamma * q, qk, to_end * k, total))
    last, o = jax.lax.scan(carry, state.astype(f32), per_chunk)
    o = jnp.moveaxis(o, 0, 2)                              # (B, H, n, c, dv)
    o = jnp.moveaxis(o, 1, 3).reshape(bsz, n * c, h, dv)[:, :t]
    return o, last


def step_arrays(q, k, v, alpha, beta, state, fresh=None, idle=None,
                packed=None):
    """One token: ``q`` / ``k`` (B, H, d_k), ``v`` (B, H, d_v), ``alpha`` /
    ``beta`` (B, H), ``state`` (B, H, d_k, d_v) float32, or with ``packed =
    p`` (B, H / p, d_k, p * d_v). ``fresh`` (B,) bool: start from zeros;
    ``idle`` (B,) bool: the state comes back as it was. Returns ``(o (B, H,
    d_v) float32, new state)``. One read of the state: ``S^T k`` and ``S^T
    q`` together, then ``o = alpha S^T q + (k . q) u``."""
    f32 = jnp.float32
    bsz, h, dk = q.shape
    dv = v.shape[-1]
    p = packed or 1
    grp = h // p
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha, beta = alpha.astype(f32), beta.astype(f32)

    def col(a):     # (B, H, d_k) -> (B, grp, d_k, p * d_v)
        return jnp.repeat(a.reshape(bsz, grp, p, dk).transpose(0, 1, 3, 2),
                          dv, axis=-1)

    def row(a):     # (B, H, d_v) or (B, H) -> (B, grp, 1, p * d_v)
        if a.ndim == 2:
            a = jnp.repeat(a[..., None], dv, axis=-1)
        return a.reshape(bsz, grp, 1, p * dv)

    s = state.astype(f32)
    if fresh is not None:
        s = jnp.where(fresh[:, None, None, None], 0.0, s)
    kb, qb = col(k), col(q)
    s_k = jnp.sum(s * kb, axis=2, keepdims=True)           # S^T k
    s_q = jnp.sum(s * qb, axis=2, keepdims=True)           # S^T q
    a_r, b_r = row(alpha), row(beta)
    u = b_r * (row(v) - a_r * s_k)
    new = a_r * s + kb * u
    o = a_r * s_q + row(jnp.sum(q * k, axis=-1)) * u
    if idle is not None:
        new = jnp.where(idle[:, None, None, None], state.astype(f32), new)
    return o.reshape(bsz, h, dv), new


def conv_arrays(x, weight, window):
    """``silu`` of the causal depthwise convolution in front of the rule, no
    bias: ``x`` (B, T, C), ``weight`` (C, K), ``window`` (B, K-1, C) the
    inputs before ``x``. Returns ``(y (B, T, C), the last K-1 inputs)``.
    ``ssm.conv_arrays`` without its bias, and with ONE sequence run as (T,
    C): over (1, T, C) the chip's compiler has laid the float32 taps of a
    256-token chunk out with the axis of one minor-most, 128 lanes for one
    element, 1.5 GB for 12 MB."""
    k = weight.shape[1]
    if x.shape[0] == 1:
        x, window = x[0], window[0]
    t = x.shape[-2]
    full = jnp.concatenate([window.astype(x.dtype), x], axis=-2)
    acc = 0.0
    for j in range(k):
        acc = acc + (full[..., j:j + t, :].astype(jnp.float32)
                     * weight[:, j].astype(jnp.float32))
    y, last = jax.nn.silu(acc).astype(x.dtype), full[..., -(k - 1):, :]
    return (y[None], last[None]) if y.ndim == 2 else (y, last)


def gated_norm_arrays(o, gate, weight, epsilon):
    """``RMSNorm(o) * weight * silu(gate)`` over the last axis: ``o`` /
    ``gate`` (..., H, d_v), ``weight`` (d_v,). Norm first, then gate."""
    f32 = jnp.float32
    x = o.astype(f32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + epsilon)
    return (x * weight.astype(f32) * jax.nn.silu(gate.astype(f32))).astype(
        gate.dtype)


def use_step_kernel(state_shape, key_dim, packed) -> bool:
    """Whether a decode step of these shapes goes through the Pallas kernel
    (``ops.pallas.delta_rule``): a TPU (or the kernel module's ``INTERPRET``
    switch) and a packed state the kernel was written for."""
    if not packed or not flags.get_flag("use_pallas_kernels"):
        return False
    # Pallas is imported where a call first needs it, not with the package
    from ...ops.pallas import delta_rule as kernel
    if not (kernel.INTERPRET or jax.default_backend() == "tpu"):
        return False
    return kernel.supports(state_shape, key_dim, packed)


def step_any(q, k, v, alpha, beta, state, fresh=None, idle=None, packed=None):
    """``step_arrays``, through the kernel where ``use_step_kernel`` says."""
    if use_step_kernel(state.shape, q.shape[-1], packed):
        from ...ops.pallas.delta_rule import delta_rule_step
        return delta_rule_step(q, k, v, alpha, beta, state, fresh, idle,
                               packed)
    return step_arrays(q, k, v, alpha, beta, state, fresh, idle, packed)


# ----------------------------------------------------------- tensor level
def gated_delta_chunk(q, k, v, alpha_log, beta, state=None, valid=None,
                      chunk_size=64, name=None):
    """The gated delta rule in its chunked form over T tokens that continue
    from ``state``. ``q`` / ``k`` (B, T, H, d_k), ``v`` (B, T, H, d_v),
    ``alpha_log`` (B, T, H) the log of the decay (<= 0), ``beta`` (B, T, H),
    ``state`` (B, H, d_k, d_v) float32 (zeros when omitted), ``valid`` (B, T)
    bool (a false row leaves the state alone). Returns ``(o (B, T, H, d_v)
    float32, state after the last token)``."""
    q, v = _t(q), _t(v)
    if state is None:
        state = Tensor(jnp.zeros((q.shape[0], q.shape[2], q.shape[3],
                                  v.shape[3]), jnp.float32))
    if valid is None:
        valid = Tensor(jnp.ones(tuple(q.shape[:2]), bool))

    def f(qa, ka, va, la, ba, sa, ma, **_attrs):
        return chunk_arrays(qa, ka, va, la, ba, sa, ma, chunk_size)

    return dispatch.call(
        "gated_delta_chunk", f,
        [q, _t(k), v, _t(alpha_log), _t(beta), _t(state), _t(valid)],
        attrs={"chunk_size": int(chunk_size)})


def gated_delta_step(q, k, v, alpha, beta, state, packed=None, name=None):
    """The gated delta rule for ONE token (decode): ``q`` / ``k`` (B, H,
    d_k), ``v`` (B, H, d_v), ``alpha`` / ``beta`` (B, H), ``state`` (B, H,
    d_k, d_v) float32 (or packed, see the module docstring). Returns ``(o
    (B, H, d_v) float32, new state)``."""
    def f(qa, ka, va, aa, ba, sa, **_attrs):
        return step_any(qa, ka, va, aa, ba, sa, packed=packed)

    return dispatch.call(
        "gated_delta_step", f,
        [_t(q), _t(k), _t(v), _t(alpha), _t(beta), _t(state)],
        attrs={"packed": int(packed or 0)})


def gated_rms_norm(o, gate, weight, epsilon=1e-6, name=None):
    """``RMSNorm(o) * weight * silu(gate)`` over the last axis (norm first,
    then gate), in ``gate``'s dtype."""
    def f(oa, ga, wa, **_attrs):
        return gated_norm_arrays(oa, ga, wa, epsilon)

    return dispatch.call("gated_rms_norm", f, [_t(o), _t(gate), _t(weight)],
                         attrs={"epsilon": float(epsilon)})
