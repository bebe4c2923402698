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
inside a chunk), this one is a matrix, so the chunked form needs the inverse
of a unit lower triangular matrix inside every chunk. Three pieces, each
taking and returning what a serving cache carries so a sequence can be fed
in pieces:

* ``gated_delta_chunk`` — the chunked form for a run of tokens that
  CONTINUES from a carried state. Over ``C`` tokens with ``g_i`` the
  cumulative log decay, ``Gamma_ij = exp(g_i - g_j)`` (the exp of a masked
  difference, never a quotient), ``A = strict_lower(diag(beta) (Gamma * K
  K^T))`` and ``T = (I + A)^-1 diag(beta)``: ``W = T (gamma * K)``, ``U = T
  V``, against the carried state ``U' = U - W S``, ``O = (gamma * Q) S +
  lower(Gamma * Q K^T) U'``, ``S <- gamma_C S + ((gamma_C / gamma) * K)^T
  U'``. ``T`` does not depend on ``S``, so every chunk's inverse is taken at
  once and only the short state carry is a scan. ``(I + A)^-1`` (unit lower,
  ``C x C``, float32) is built by blocks, ``unit_lower_inverse``: the
  diagonal blocks of width ``BLOCK`` by substitution, every block of every
  system in one batched sweep, then pairs of inverted blocks merged upward by
  ``[[P, 0], [L, Q]]^-1 = [[P^-1, 0], [-Q^-1 L P^-1, Q^-1]]``, then ONE
  product with ``diag(beta) [gamma * K | V]``. Until PR 38 it was
  ``jax.scipy.linalg``'s triangular solve: XLA's ``triangular_solve``
  custom call took 0.60 ms a layer for a (1, 256) chunk's 120 systems of
  64 x 64 against 288 columns, 3.6 of a chunk's 13.8 ms on the chip, for
  ~0.3 GFLOP: latency, not work. Alone on a v5e that call reads 0.650 ms
  and this form 0.114 (blocks of 8: 0.122; PR 37 read 8 / 16 / 32 at 0.377
  / 0.360 / 0.398 against 0.660 under the host's dispatch floor).
  ``chunk_plan`` says what a call was built with. The finite product ``(I -
  N)(I + N^2)(I + N^4)(I + N^8)`` is NOT used for the diagonal blocks: where
  a block is 2 on its whole lower triangle its powers reach 1e6 and cancel
  (0.12 off at 0.999 of that case, float32).
* ``gated_delta_step`` — the recurrence itself for one token (decode),
  written so that the state is READ ONCE: ``S^T k`` and ``S^T q`` are two
  reductions of one pass, and ``o = alpha S^T q + (k . q) u`` needs no second
  look at the new state.
* ``gated_rms_norm`` — ``RMSNorm(o) * w * silu(g)``: the norm FIRST, then the
  gate (``ssm.gated_group_rms_norm`` gates first).

**A decay a key channel** (Kimi delta attention, Kimi Linear,
arXiv:2510.26692; ``alpha_t`` in (0, 1)^{d_k}, ``alpha_log`` (B, T, H, d_k))::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T

The same two pieces, beside the scalar ones and leaving them as they are (a
call with ``alpha`` a head traces the program it traced):

* ``channel_chunk_arrays`` — the chunk's algebra with every ``gamma`` a
  vector. The pair term ``P_ij = sum_c a_ic b_jc exp(g_ic - g_jc)`` (``A``:
  ``a = b = k``, strictly lower, times ``beta_i``; the output: ``a = q``, ``b
  = k``, lower) is no product of a Gram matrix with a mask, and the obvious
  ``(K * e^g)(K * e^-g)^T`` overflows float32 as soon as one channel's
  cumulative log decay over a chunk passes -88. ``_pair_terms`` builds it by
  the sub-blocks ``unit_lower_inverse`` has (``BLOCK`` 16), and **every
  exponent is <= 0**: inside a diagonal sub-block the difference a channel,
  masked before the ``exp`` ((16, 16, d_k) a sub-block, reduced in the same
  fusion); between sub-block ``I`` and an earlier ``J``, ``exp(g_i -
  e_{I-1}) exp(e_{I-1} - e_J) exp(e_J - g_j)`` with ``e_m`` the cumulative
  log decay at sub-block ``m``'s last token: three factors each <= 1, the
  outer two folded into the operands, the middle one (m, m, d_k) a chunk, one
  three-operand product. ``g`` is non-increasing, so no difference taken is
  positive; a factor that underflows does so where the true product is
  smaller still. ``W = T (gamma * K)``, ``(gamma * Q) S`` and ``Diag(gamma_C)
  S + ((gamma_C / gamma) * K)^T U'`` scale channel by channel. The carry
  scan (``_carry_scan``) is shared. Alone on a v5e at (1, 256), 64 heads of
  128 x 128 (PR 44): chunks of 64 **0.90 ms** (32: 0.86; 128: 1.19), the
  scalar form at the same sizes 0.50; against the token recurrence on the
  chip 9e-7 in ``o``, 8e-6 in the state.
* ``channel_step_arrays`` — one token: ``u = beta (v - S^T (alpha * k))``,
  ``S' = Diag(alpha) S + k u^T``, ``o = S^T (alpha * q) + (k . q) u``: the
  rows of the state are scaled once, each by its own factor, and both
  reductions read the scaled rows (one read, one write). The kernel
  (``ops.pallas.delta_rule``, the decay as a third column) at 256 lanes of
  64 x 128 x 128: 3.71 ms for 2.15 GB in and out, this form 4.93.

``chunk_plan``'s ``decay`` (``"head"`` / ``"channel"``) and the stamp's name
(``delta_rule_chunk[T,C]`` / ``delta_rule_chunk_channel[T,C]``) say which
form a call was built with. ``gated_delta_chunk`` / ``gated_delta_step``
take either decay; the state of this form is not packed (``d_v`` 128 is
whole lane tiles).

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

import logging

import jax
import jax.numpy as jnp

from ...core import dispatch, flags
from ...core.tensor import Tensor, as_tensor

__all__ = ["gated_delta_chunk", "gated_delta_step", "gated_rms_norm",
           "heads_packed", "pack_state", "unpack_state"]

_HI = jax.lax.Precision.HIGHEST
_LANES = 128
#: width of the diagonal blocks ``unit_lower_inverse`` inverts by
#: substitution (the measured best at a chunk of 64: module docstring)
BLOCK = 16


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


# ------------------------------------------------- the inverse of I + A
def chunk_plan(tokens: int, chunk_size: int, decay: str = "head") -> dict:
    """What the chunked form executes for ``tokens`` rows at ``chunk_size``,
    fixed when it is traced: ``chunk`` (rows a chunk, ``C``), ``block`` (the
    width of the diagonal blocks of ``I + A`` inverted by substitution, and
    with a decay a channel of the sub-blocks the pair terms are built by),
    ``merge_levels`` (how often pairs of inverted blocks are merged),
    ``padded`` (``block << merge_levels``: the size the inverse is built
    at, ``C`` completed with identity rows and columns) and ``decay``, the
    form the call was built with: ``"head"`` (one number a head a token) or
    ``"channel"`` (one a key channel)."""
    c = min(chunk_size, tokens)
    block = min(BLOCK, c)
    levels = (-(-c // block) - 1).bit_length()
    return {"chunk": c, "block": block, "merge_levels": levels,
            "padded": block << levels, "decay": decay}


def _stamp_plan(tokens, chunk_size, decay="head"):
    """The plan on the ``compile.trace`` entry of the program being traced
    (the start-up record; outside a trace, nothing)."""
    from ...observability import trace as _trace
    tag = "delta_rule_chunk" + ("" if decay == "head" else "_" + decay)
    _trace.compile_note(f"{tag}[{tokens},{chunk_size}]",
                        chunk_plan(tokens, chunk_size, decay))


# One jitted function: a model's linear layers call it with one signature, so
# the program that holds them traces the sweep and the merges once, not once
# a layer (``ops.pallas.flash_attention`` has what forgetting that cost).
@jax.jit
def unit_lower_inverse(a_mat):
    """``(I + A)^-1`` for strictly lower ``a_mat`` (..., C, C) float32: the
    diagonal blocks by substitution, merged by products at ``highest``."""
    c = a_mat.shape[-1]
    plan = chunk_plan(c, c)
    if flags.get_flag("log_level") >= 1:    # here: once a signature
        logging.getLogger("paddle_tpu.delta_rule").info(
            "unit_lower_inverse%s: %s", a_mat.shape, plan)
    b, size = plan["block"], plan["padded"]
    if size > c:    # zero rows and columns of A: identity ones of I + A
        a_mat = jnp.pad(a_mat, [(0, 0)] * (a_mat.ndim - 2)
                        + [(0, size - c)] * 2)

    def block(width, row, col):
        return a_mat[..., row * width:(row + 1) * width,
                     col * width:(col + 1) * width]

    # (I + N) X = I a row at a time, as rank-one sweeps over every block of
    # every system at once: after sweep j row j + 1 is final. N is strictly
    # lower, so a sweep leaves rows <= j alone without a mask, and N = 0
    # gives the identity exactly. A loop, not b - 1 unrolled sweeps: those
    # were 2.2 MB more of executable a layer (a serving program of six
    # layers loaded 0.4 s slower from the compile cache) and ran 0.05 ms a
    # layer slower on the chip.
    n = jnp.stack([block(b, i, i) for i in range(size // b)], axis=-3)

    def sweep(j, inv):
        col = jax.lax.dynamic_slice_in_dim(n, j, 1, axis=n.ndim - 1)
        row = jax.lax.dynamic_slice_in_dim(inv, j, 1, axis=n.ndim - 2)
        return inv - col * row

    inv = jax.lax.fori_loop(0, b - 1, sweep, jnp.broadcast_to(
        jnp.eye(b, dtype=a_mat.dtype), n.shape))
    width = b
    while width < size:     # [[P, 0], [L, Q]]^-1, pairs of neighbours
        p_inv, q_inv = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = jnp.stack([block(width, i + 1, i)
                         for i in range(0, size // width, 2)], axis=-3)
        low = -jnp.matmul(q_inv, jnp.matmul(low, p_inv, precision=_HI),
                          precision=_HI)
        inv = jnp.concatenate(
            [jnp.concatenate([p_inv, jnp.zeros_like(p_inv)], axis=-1),
             jnp.concatenate([low, q_inv], axis=-1)], axis=-2)
        width *= 2
    return inv[..., 0, :c, :c]


# ------------------------------------------------------------ array level
def _into_chunks(q, k, v, alpha_log, beta, valid, chunk_size):
    """What both chunked forms start with: float32 operands, rows that are
    not ``valid`` turned into ``beta = 0, alpha = 1`` rows (they leave the
    state as it was), the sequence completed with such rows to whole chunks
    and cut into them, heads first: ``(B, T, H, ...) -> (B, H, n, c, ...)``.
    Returns ``q``, ``k``, ``v``, the CUMULATIVE log decay along a chunk
    ``g``, ``beta``, ``n`` and ``c``."""
    f32 = jnp.float32
    bsz, t, h, _dk = q.shape
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha_log, beta = alpha_log.astype(f32), beta.astype(f32)
    if valid is not None:
        live = valid[..., None]
        alpha_log = jnp.where(live if alpha_log.ndim == 3
                              else live[..., None], alpha_log, 0.0)
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
    g = jnp.cumsum(heads_first(alpha_log), axis=3)
    return q, k, v, g, heads_first(beta), n, c


def chunk_arrays(q, k, v, alpha_log, beta, state, valid, chunk_size):
    """``q`` / ``k`` (B, T, H, d_k) (``k`` of unit length, ``q`` scaled: the
    caller's), ``v`` (B, T, H, d_v), ``alpha_log`` (B, T, H) <= 0, ``beta``
    (B, T, H), ``state`` (B, H, d_k, d_v) float32, ``valid`` (B, T) bool or
    None. Returns ``(o (B, T, H, d_v) float32, the state after the last
    token)``. ``(I + A)^-1`` is ``unit_lower_inverse``, a block inverse in
    ``jnp`` whatever the backend (XLA's ``triangular_solve`` custom call was
    the largest piece of a prefill chunk's rule: module docstring), applied
    to ``[gamma * K | V]`` in one product."""
    bsz, t, h, dk = q.shape
    _stamp_plan(t, chunk_size)
    dv = v.shape[-1]
    q, k, v, g, beta, n, c = _into_chunks(q, k, v, alpha_log, beta, valid,
                                          chunk_size)   # g (B, H, n, c)
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
    solved = jnp.matmul(unit_lower_inverse(a_mat), rhs, precision=_HI)
    w, u = solved[..., :dk], solved[..., dk:]
    qk = gam * jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=_HI)
    to_end = jnp.exp(g[..., -1:] - g)[..., None]           # gamma_C / gamma
    total = jnp.exp(g[..., -1])                            # (B, H, n)

    o, last = _carry_scan(state, w, u, gamma * q, qk, to_end * k, total)
    return o.reshape(bsz, n * c, h, dv)[:, :t], last


def _carry_scan(state, w, u, gq, qk, k_end, total):
    """The short scan both chunked forms end in: chunk by chunk ``U' = U -
    W S``, ``O = (gamma * Q) S + lower(pair(Q, K)) U'``, ``S <- decay_C S +
    ((gamma_C / gamma) * K)^T U'``. Operands (B, H, n, c, ...); ``total`` the
    whole chunk's decay, (B, H, n) a head or (B, H, n, d_k) a channel.
    Returns ``(o (B, n, c, H, d_v), the state after the last chunk)``."""
    def carry(s, chunk):
        w_c, u_c, q_c, qk_c, k_end, total_c = chunk
        u_p = u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, s, precision=_HI)
        o_c = (jnp.einsum("bhik,bhkv->bhiv", q_c, s, precision=_HI)
               + jnp.einsum("bhij,bhjv->bhiv", qk_c, u_p, precision=_HI))
        decay = (total_c[..., None, None] if total_c.ndim == 2
                 else total_c[..., None])
        s = (decay * s
             + jnp.einsum("bhik,bhiv->bhkv", k_end, u_p, precision=_HI))
        return s, o_c

    per_chunk = tuple(jnp.moveaxis(a, 2, 0) for a in
                      (w, u, gq, qk, k_end, total))
    last, o = jax.lax.scan(carry, state.astype(jnp.float32), per_chunk)
    o = jnp.moveaxis(o, 0, 2)                              # (B, H, n, c, dv)
    return jnp.moveaxis(o, 1, 3), last


def _pair_terms(a, b, g, strict):
    """``P_ij = sum_c a_ic b_jc exp(g_ic - g_jc)`` for ``i >= j`` (``i > j``
    with ``strict``), 0 elsewhere: ``a`` / ``b`` / ``g`` (..., c, d_k), ``g``
    the cumulative log decay a channel (non-increasing along ``c``; a ``c``
    that is no multiple of the sub-block width is completed with rows of
    ``a = b = 0`` that decay no further). No ``exp`` of a positive number:

    * inside a diagonal sub-block (width ``BLOCK``) the difference a channel,
      masked BEFORE the ``exp``: (w, w, d_k) a sub-block;
    * between sub-block ``I`` and an earlier ``J``, ``exp(g_i - e_{I-1}) *
      exp(e_{I-1} - e_J) * exp(e_J - g_j)`` with ``e_m`` the value of ``g``
      at sub-block ``m``'s last token: three factors each <= 1, the outer
      two folded into ``a`` and ``b``, the middle one (m, m, d_k) a chunk.
      A factor that underflows to 0 does so where the product is smaller
      still."""
    c, dk = g.shape[-2:]
    w = min(BLOCK, c)
    m = -(-c // w)
    lead = g.shape[:-2]
    if m * w > c:
        rows = [(0, 0)] * len(lead) + [(0, m * w - c), (0, 0)]
        a, b, g = jnp.pad(a, rows), jnp.pad(b, rows), jnp.pad(
            g, rows, mode="edge")
    a, b, g = (x.reshape(lead + (m, w, dk)) for x in (a, b, g))
    ends = g[..., -1, :]                                   # e_m (..., m, dk)
    starts = jnp.concatenate(                              # e_{m-1}; e_-1 = 0
        [jnp.zeros_like(ends[..., :1, :]), ends[..., :-1, :]], axis=-2)
    # the strict form masks the SUM, so that both of a chunk's pair matrices
    # take the exponentials of one expression (the compiler shares them)
    inside = jnp.tril(jnp.ones((w, w), bool))
    diff = g[..., :, None, :] - g[..., None, :, :]         # (m, w, w, dk)
    near = jnp.where(inside[..., None], jnp.exp(
        jnp.where(inside[..., None], diff, 0.0)), 0.0)
    diag = jnp.sum(a[..., :, None, :] * b[..., None, :, :] * near, axis=-1)
    if strict:
        diag = jnp.where(jnp.tril(inside, -1), diag, 0.0)
    if m == 1:
        return diag[..., 0, :c, :c]
    left = a * jnp.exp(g - starts[..., None, :])           # (m, w, dk)
    right = b * jnp.exp(ends[..., None, :] - g)
    earlier = jnp.tril(jnp.ones((m, m), bool), -1)[..., None]
    across = jnp.where(earlier, jnp.exp(jnp.where(
        earlier, starts[..., :, None, :] - ends[..., None, :, :], 0.0)), 0.0)
    far = jnp.einsum("...Iic,...IJc,...Jjc->...IiJj", left, across, right,
                     precision=_HI)
    eye = jnp.eye(m, dtype=diag.dtype)[:, None, :, None]
    full = far + diag[..., :, :, None, :] * eye            # (m, w, m, w)
    return full.reshape(lead + (m * w, m * w))[..., :c, :c]


def channel_chunk_arrays(q, k, v, alpha_log, beta, state, valid, chunk_size):
    """``chunk_arrays`` with a decay a key CHANNEL: ``alpha_log`` (B, T, H,
    d_k) <= 0, everything else as there. ``S_t = (I - beta_t k_t k_t^T)
    Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``. The
    chunk's algebra is the scalar form's with every ``gamma`` a vector:
    ``A`` and the output's pair matrix are ``_pair_terms`` (no Gram matrix
    times a mask exists), ``W = T (gamma * K)``, ``(gamma * Q) S`` and
    ``Diag(gamma_C) S + ((gamma_C / gamma) * K)^T U'`` scale channel by
    channel, every factor <= 1."""
    bsz, t, h, dk = q.shape
    _stamp_plan(t, chunk_size, "channel")
    dv = v.shape[-1]
    q, k, v, g, beta, n, c = _into_chunks(q, k, v, alpha_log, beta, valid,
                                          chunk_size)   # g (B, H, n, c, dk)
    a_mat = beta[..., :, None] * _pair_terms(k, k, g, strict=True)
    gamma = jnp.exp(g)
    rhs = beta[..., None] * jnp.concatenate([gamma * k, v], axis=-1)
    solved = jnp.matmul(unit_lower_inverse(a_mat), rhs, precision=_HI)
    w, u = solved[..., :dk], solved[..., dk:]
    qk = _pair_terms(q, k, g, strict=False)
    to_end = jnp.exp(g[..., -1:, :] - g)                   # gamma_C / gamma
    total = jnp.exp(g[..., -1, :])                         # (B, H, n, dk)
    o, last = _carry_scan(state, w, u, gamma * q, qk, to_end * k, total)
    return o.reshape(bsz, n * c, h, dv)[:, :t], last


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


def channel_step_arrays(q, k, v, alpha, beta, state, fresh=None, idle=None):
    """``step_arrays`` with a decay a key channel: ``alpha`` (B, H, d_k),
    ``state`` (B, H, d_k, d_v) float32 (no packing: the family's ``d_v`` is
    whole lane tiles). ``u = beta (v - S^T (alpha * k))``, ``S' = Diag(alpha)
    S + k u^T``, ``o = S^T (alpha * q) + (k . q) u``: the state's rows are
    scaled once, each by its own factor, and both reductions read the scaled
    rows."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha, beta = alpha.astype(f32), beta.astype(f32)
    s = state.astype(f32)
    if fresh is not None:
        s = jnp.where(fresh[:, None, None, None], 0.0, s)
    decayed = alpha[..., None] * s                         # Diag(alpha) S
    s_k = jnp.sum(decayed * k[..., None], axis=2)          # S^T (alpha * k)
    s_q = jnp.sum(decayed * q[..., None], axis=2)
    u = beta[..., None] * (v - s_k)
    new = decayed + k[..., None] * u[..., None, :]
    o = s_q + jnp.sum(q * k, axis=-1, keepdims=True) * u
    if idle is not None:
        new = jnp.where(idle[:, None, None, None], state.astype(f32), new)
    return o, new


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


def gated_norm_arrays(o, gate, weight, epsilon, gate_fn=jax.nn.silu):
    """``RMSNorm(o) * weight * gate_fn(gate)`` over the last axis: ``o`` /
    ``gate`` (..., H, d_v), ``weight`` (d_v,). Norm first, then gate."""
    f32 = jnp.float32
    x = o.astype(f32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + epsilon)
    return (x * weight.astype(f32) * gate_fn(gate.astype(f32))).astype(
        gate.dtype)


def use_step_kernel(state_shape, key_dim, packed, channel=False) -> bool:
    """Whether a decode step of these shapes goes through the Pallas kernel
    (``ops.pallas.delta_rule``): a TPU (or the kernel module's ``INTERPRET``
    switch) and a packed state the kernel was written for (``channel``: with
    the decay a key channel, where ``packed`` is 1)."""
    if not packed or not flags.get_flag("use_pallas_kernels"):
        return False
    # Pallas is imported where a call first needs it, not with the package
    from ...ops.pallas import delta_rule as kernel
    if not (kernel.INTERPRET or jax.default_backend() == "tpu"):
        return False
    return kernel.supports(state_shape, key_dim, packed, channel)


def step_any(q, k, v, alpha, beta, state, fresh=None, idle=None, packed=None):
    """``step_arrays`` (``alpha`` (B, H)) or ``channel_step_arrays``
    (``alpha`` (B, H, d_k)), through the kernel where ``use_step_kernel``
    says."""
    channel = alpha.ndim == 3
    if channel:
        if packed not in (None, 1):
            raise ValueError("a decay a channel takes an unpacked state")
        packed = 1      # the kernel's word for a state that is not packed
    if use_step_kernel(state.shape, q.shape[-1], packed, channel):
        from ...ops.pallas.delta_rule import delta_rule_step
        return delta_rule_step(q, k, v, alpha, beta, state, fresh, idle,
                               packed)
    if channel:
        return channel_step_arrays(q, k, v, alpha, beta, state, fresh, idle)
    return step_arrays(q, k, v, alpha, beta, state, fresh, idle, packed)


# ----------------------------------------------------------- tensor level
def gated_delta_chunk(q, k, v, alpha_log, beta, state=None, valid=None,
                      chunk_size=64, name=None):
    """The gated delta rule in its chunked form over T tokens that continue
    from ``state``. ``q`` / ``k`` (B, T, H, d_k), ``v`` (B, T, H, d_v),
    ``alpha_log`` the log of the decay (<= 0): (B, T, H), one number a head,
    or (B, T, H, d_k), one a key channel (Kimi delta attention); ``beta``
    (B, T, H), ``state`` (B, H, d_k, d_v) float32 (zeros when omitted),
    ``valid`` (B, T) bool (a false row leaves the state alone). Returns
    ``(o (B, T, H, d_v) float32, state after the last token)``."""
    q, v = _t(q), _t(v)
    if state is None:
        state = Tensor(jnp.zeros((q.shape[0], q.shape[2], q.shape[3],
                                  v.shape[3]), jnp.float32))
    if valid is None:
        valid = Tensor(jnp.ones(tuple(q.shape[:2]), bool))

    def f(qa, ka, va, la, ba, sa, ma, **_attrs):
        form = channel_chunk_arrays if la.ndim == 4 else chunk_arrays
        return form(qa, ka, va, la, ba, sa, ma, chunk_size)

    return dispatch.call(
        "gated_delta_chunk", f,
        [q, _t(k), v, _t(alpha_log), _t(beta), _t(state), _t(valid)],
        attrs={"chunk_size": int(chunk_size)})


def gated_delta_step(q, k, v, alpha, beta, state, packed=None, name=None):
    """The gated delta rule for ONE token (decode): ``q`` / ``k`` (B, H,
    d_k), ``v`` (B, H, d_v), ``beta`` (B, H), ``alpha`` (B, H) or, a decay
    a key channel, (B, H, d_k); ``state`` (B, H, d_k, d_v) float32 (or
    packed, see the module docstring; not with a decay a channel). Returns
    ``(o (B, H, d_v) float32, new state)``."""
    def f(qa, ka, va, aa, ba, sa, **_attrs):
        return step_any(qa, ka, va, aa, ba, sa, packed=packed)

    return dispatch.call(
        "gated_delta_step", f,
        [_t(q), _t(k), _t(v), _t(alpha), _t(beta), _t(state)],
        attrs={"packed": int(packed or 0)})


_GATES = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}


def gated_rms_norm(o, gate, weight, epsilon=1e-6, activation="silu",
                   name=None):
    """``RMSNorm(o) * weight * act(gate)`` over the last axis (norm first,
    then gate), in ``gate``'s dtype; ``activation`` ``"silu"`` or
    ``"sigmoid"``."""
    def f(oa, ga, wa, **_attrs):
        return gated_norm_arrays(oa, ga, wa, epsilon, _GATES[activation])

    return dispatch.call("gated_rms_norm", f, [_t(o), _t(gate), _t(weight)],
                         attrs={"epsilon": float(epsilon),
                                "activation": activation})
