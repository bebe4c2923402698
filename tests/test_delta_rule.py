"""The gated delta rule (``nn.functional.delta_rule``) against the token-by-
token loop in float64: the chunked form, the one-token step applied T times
and the loop agree to float32 rounding, from a zero and from a carried
state, with ragged ``valid`` rows, at ``beta`` up to 2; the packed state and
the decode kernel (``ops.pallas.delta_rule``, in interpret mode) are the
same function.

Tolerances: ``TIGHT`` 2e-5 absolute on values of order 1. Everything is
float32 at ``highest`` precision; what differs is the order of a few hundred
float32 additions (a block inverse and five products a chunk against one
rank-one update a token), not a precision. The inverse alone
(``unit_lower_inverse``) is held to ``jax.scipy.linalg.solve_triangular``'s
own error against ``numpy.linalg.solve`` in float64.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.nn.functional import delta_rule as dr
from paddle_tpu.nn.functional import ssm
from paddle_tpu.ops.pallas import delta_rule as kernel

TIGHT = 2e-5
B, H, DK, DV = 2, 4, 12, 32


def inputs(tokens, seed=0, beta_max=2.0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, tokens, H, DK))
    k = rng.normal(size=(B, tokens, H, DK))
    v = rng.normal(size=(B, tokens, H, DV))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(DK)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    alpha_log = -np.exp(rng.normal(size=(B, tokens, H))) * 0.3
    # up to the bound: a tenth of the rows sit AT beta_max
    beta = beta_max / (1 + np.exp(-rng.normal(size=(B, tokens, H)) * 3))
    beta[rng.uniform(size=beta.shape) < 0.1] = beta_max
    return q, k, v, alpha_log, beta


def loop(q, k, v, alpha_log, beta, state, valid):
    """The recurrence as written, a token and a head at a time, float64."""
    s = np.array(state, np.float64)
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        for b in range(q.shape[0]):
            if not valid[b, t]:
                continue
            for h in range(q.shape[2]):
                decayed = np.exp(alpha_log[b, t, h]) * s[b, h]
                u = beta[b, t, h] * (v[b, t, h] - decayed.T @ k[b, t, h])
                s[b, h] = decayed + np.outer(k[b, t, h], u)
                out[b, t, h] = s[b, h].T @ q[b, t, h]
    return out, s


#: the rule's functions as ONE traced program a shape (what the models run),
#: not a dispatch and a compile an operation
chunk_arrays = jax.jit(dr.chunk_arrays, static_argnums=(7,))
step_arrays = jax.jit(dr.step_arrays, static_argnames=("packed",))
conv_arrays = jax.jit(dr.conv_arrays)


def close(got, want, atol=TIGHT):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol,
                               rtol=0)


def ragged(tokens, kind):
    valid = np.ones((B, tokens), bool)
    if kind == "left":          # left padding of a first chunk
        valid[0, :min(5, tokens - 1)] = False
    elif kind == "right":       # a last sub-chunk that is not full
        valid[1, max(1, tokens - 7):] = False
    elif kind == "none":        # a lane with no real row at all
        valid[0] = False
    return valid


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("kind", ["all", "left", "right", "none"])
@pytest.mark.parametrize("tokens,chunk", [(1, 64), (7, 4), (64, 64),
                                          (150, 64), (33, 8), (256, 64),
                                          (200, 128)])
def test_chunked_form_is_the_token_loop(tokens, chunk, kind, carried):
    q, k, v, alpha_log, beta = inputs(tokens, seed=tokens)
    state = (np.random.default_rng(1).normal(size=(B, H, DK, DV))
             if carried else np.zeros((B, H, DK, DV)))
    valid = ragged(tokens, kind)
    want_o, want_s = loop(q, k, v, alpha_log, beta, state, valid)
    got_o, got_s = chunk_arrays(
        *map(jnp.asarray, (q, k, v, alpha_log, beta, state)),
        jnp.asarray(valid), chunk)
    live = valid[:, :, None, None]
    close(np.where(live, got_o, 0.0), np.where(live, want_o, 0.0))
    close(got_s, want_s)
    if kind == "none":      # no valid row: the state back bit for bit
        assert np.array_equal(np.asarray(got_s[0]),
                              np.asarray(state[0], np.float32))


# ------------------------------------------------------------ the inverse
def strict_lower(kind, c, rng, systems=(2, 3)):
    """``A`` of ``systems`` chunks of ``c`` rows, float32 as the rule builds
    it: ``random`` keys with ``beta`` to 2; ``worst``, the largest
    admissible entries (every key the same unit vector, ``beta`` 2, ``alpha``
    1: 2 on the whole strict lower triangle); ``zero``, no valid row."""
    if kind == "zero":
        return np.zeros(systems + (c, c), np.float32)
    if kind == "worst":
        return np.broadcast_to(np.tril(np.full((c, c), 2.0, np.float32), -1),
                               systems + (c, c))
    k = rng.normal(size=systems + (c, DK))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2 / (1 + np.exp(-rng.normal(size=systems + (c,)) * 3))
    beta[rng.uniform(size=beta.shape) < 0.1] = 2.0
    g = np.cumsum(-np.exp(rng.normal(size=systems + (c,))) * 0.3, axis=-1)
    gam = np.exp(np.tril(g[..., :, None] - g[..., None, :]))
    return np.tril(beta[..., None] * gam * (k @ np.swapaxes(k, -1, -2)),
                   -1).astype(np.float32)


def inverse_in_float64(a):
    eye = np.eye(a.shape[-1])
    return np.linalg.solve(a.astype(np.float64) + eye,
                           np.broadcast_to(eye, a.shape))


# the block form may be this many times as far from float64 as the
# triangular solve it replaced (or one float32 rounding of an entry of 2,
# where that solve is exact)
INVERSE_FACTOR = 4.0


@pytest.mark.parametrize("kind", ["random", "worst", "zero"])
@pytest.mark.parametrize("c", [1, 4, 7, 8, 16, 24, 64, 128])
def test_block_inverse_is_the_triangular_solve(c, kind):
    a = strict_lower(kind, c, np.random.default_rng(c))
    want = inverse_in_float64(a)
    got = np.asarray(dr.unit_lower_inverse(jnp.asarray(a)))
    assert got.dtype == np.float32 and got.shape == a.shape
    solve = np.asarray(jax.scipy.linalg.solve_triangular(
        jnp.asarray(a) + jnp.eye(c, dtype=jnp.float32),
        jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), a.shape),
        lower=True, unit_diagonal=True))
    err = np.abs(got - want).max()
    assert err <= INVERSE_FACTOR * max(np.abs(solve - want).max(),
                                       2 * np.finfo(np.float32).eps)
    assert not np.triu(got, 1).any()
    if kind == "zero":      # no valid row: the identity, exactly
        assert np.array_equal(got, np.broadcast_to(
            np.eye(c, dtype=np.float32), a.shape))
    if kind == "worst":     # whole numbers throughout: exact, entries to 2
        assert err == 0 and np.abs(got).max() == min(c, 2)


def test_block_inverse_where_the_matrix_is_ill_conditioned():
    """Next to the worst admissible case but off the whole numbers, the
    merges' products cancel (``L P^-1`` sums 32 terms of 4 to a 2) where a
    substitution does not: the block form is held to 2e-4 on entries of 2 at
    ``C`` = 64, not to the solve's 3e-7. 64 keys a chunk that are one
    vector, each written at ``beta`` 2 without decay, is no served input."""
    a = strict_lower("worst", 64, None) * np.float32(0.999)
    got = np.asarray(dr.unit_lower_inverse(jnp.asarray(a)))
    assert np.abs(got - inverse_in_float64(a)).max() < 2e-4


@pytest.mark.parametrize("tokens,chunk,plan", [
    (256, 64, {"chunk": 64, "block": 16, "merge_levels": 2, "padded": 64}),
    (200, 128, {"chunk": 128, "block": 16, "merge_levels": 3,
                "padded": 128}),
    (24, 24, {"chunk": 24, "block": 16, "merge_levels": 1, "padded": 32}),
    (7, 4, {"chunk": 4, "block": 4, "merge_levels": 0, "padded": 4}),
    (1, 64, {"chunk": 1, "block": 1, "merge_levels": 0, "padded": 1})])
def test_chunk_plan_says_what_a_call_is_built_with(tokens, chunk, plan):
    assert dr.chunk_plan(tokens, chunk) == dict(plan, decay="head")
    assert dr.chunk_plan(tokens, chunk, "channel") == dict(plan,
                                                           decay="channel")


def test_chunk_arrays_stamps_its_plan_on_the_trace_entry():
    """As the flash kernels do: the plan on the ``compile.trace`` entry of
    the program being traced, once a signature with a count of the calls
    (one a linear layer), and the inverse traced ONCE for them all."""
    from paddle_tpu.observability import trace

    args = tuple(map(jnp.asarray, inputs(40, seed=5)))
    zero, valid = jnp.zeros((B, H, DK, DV)), jnp.ones((B, 40), bool)

    def two_layers(*a):
        o, s = dr.chunk_arrays(*a, zero, valid, 16)
        return dr.chunk_arrays(*a, s, valid, 16)[0] + o

    trace.startup_clear()
    text = jax.jit(two_layers).lower(*args).as_text()
    assert text.count("func.func private @unit_lower_inverse") == 1
    assert text.count("call @unit_lower_inverse") == 2
    (traced,) = [e for e in trace.startup_record()["entries"]
                 if e[0] == "compile.trace"
                 and e[5]["program"] == "two_layers"]
    assert traced[5]["delta_rule_chunk[40,16]"] == dict(
        dr.chunk_plan(40, 16), calls=2)
    trace.startup_clear()


@pytest.mark.parametrize("packed", [None, 4], ids=["plain", "packed"])
@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
def test_step_applied_t_times_is_the_token_loop(carried, packed):
    tokens = 40
    q, k, v, alpha_log, beta = inputs(tokens, seed=3)
    state = (np.random.default_rng(2).normal(size=(B, H, DK, DV))
             if carried else np.zeros((B, H, DK, DV)))
    valid = ragged(tokens, "right")
    want_o, want_s = loop(q, k, v, alpha_log, beta, state, valid)
    s = jnp.asarray(state, jnp.float32)
    if packed:
        assert dr.heads_packed(H, DV) == packed
        s = dr.pack_state(s, packed)
    step = jax.jit(lambda *a: dr.step_arrays(*a, packed=packed))
    outs = []
    for t in range(tokens):
        o, s = step(*(jnp.asarray(a[:, t]) for a in (q, k, v)),
                    jnp.exp(jnp.asarray(alpha_log[:, t])),
                    jnp.asarray(beta[:, t]), s, None,
                    jnp.asarray(~valid[:, t]))
        outs.append(o)
    if packed:
        s = dr.unpack_state(s, packed)
    live = valid[:, :, None, None]
    close(np.where(live, np.stack(outs, 1), 0.0), np.where(live, want_o, 0.0))
    close(s, want_s)


@pytest.mark.parametrize("cuts", [(11,), (64, 128), (1, 2, 3), (70, 71)])
def test_a_chunk_continues_from_the_carried_state(cuts):
    tokens = 150
    q, k, v, alpha_log, beta = map(jnp.asarray, inputs(tokens, seed=9))
    valid = jnp.ones((B, tokens), bool)
    zero = jnp.zeros((B, H, DK, DV))
    whole_o, whole_s = chunk_arrays(q, k, v, alpha_log, beta, zero, valid, 64)
    s, parts = zero, []
    for lo, hi in zip((0,) + cuts, cuts + (tokens,)):
        o, s = chunk_arrays(*(a[:, lo:hi] for a in
                              (q, k, v, alpha_log, beta)), s,
                            valid[:, lo:hi], 64)
        parts.append(o)
    close(jnp.concatenate(parts, axis=1), whole_o)
    close(s, whole_s)


def test_pack_and_unpack_are_inverse_and_whole_lane_tiles():
    assert dr.heads_packed(30, 192) == 2        # the published sizes
    assert dr.heads_packed(6, 64) == 2 and dr.heads_packed(4, 32) == 4
    assert dr.heads_packed(8, 128) == 1 and dr.heads_packed(3, 100) == 1
    s = jnp.asarray(np.random.default_rng(0).normal(size=(3, 6, 8, 64)),
                    jnp.float32)
    packed = dr.pack_state(s, 2)
    assert packed.shape == (3, 3, 8, 128)
    # heads 2g and 2g + 1 side by side in row g
    assert np.array_equal(np.asarray(packed[1, 2, :, 64:]),
                          np.asarray(s[1, 5]))
    assert np.array_equal(np.asarray(dr.unpack_state(packed, 2)),
                          np.asarray(s))


# -------------------------------------------------------------- the kernel
@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(kernel, "INTERPRET", True)


@pytest.mark.parametrize("shape", [(3, 4, 16, 64), (2, 6, 8, 64),
                                   (5, 30, 96, 192), (2, 8, 16, 128)],
                         ids=["p2", "tiny-model", "published", "p1"])
def test_decode_kernel_is_the_composite(interpreted, shape):
    """``delta_rule_step`` in interpret mode equals ``step_arrays`` on the
    packed state: a fresh lane starts from zeros whatever its slot held, an
    idle lane gets its state back bit for bit."""
    bsz, h, dk, dv = shape
    p = dr.heads_packed(h, dv)
    rng = np.random.default_rng(7)
    q, k = (jnp.asarray(a / np.linalg.norm(a, axis=-1, keepdims=True),
                        jnp.float32)
            for a in rng.normal(size=(2, bsz, h, dk)))
    v = jnp.asarray(rng.normal(size=(bsz, h, dv)), jnp.float32)
    alpha = jnp.asarray(rng.uniform(0.5, 1, size=(bsz, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, size=(bsz, h)), jnp.float32)
    state = dr.pack_state(jnp.asarray(
        rng.normal(size=(bsz, h, dk, dv)) / np.sqrt(dk), jnp.float32), p)
    fresh = jnp.zeros((bsz,), bool).at[0].set(True)
    idle = jnp.zeros((bsz,), bool).at[bsz - 1].set(True)
    assert kernel.supports(state.shape, dk, p)
    assert dr.use_step_kernel(state.shape, dk, p)
    want_o, want_s = step_arrays(q, k, v, alpha, beta, state, fresh, idle,
                                 packed=p)
    got_o, got_s = kernel.delta_rule_step(q, k, v, alpha, beta, state, fresh,
                                          idle, p)
    close(got_o[:-1], want_o[:-1])          # an idle lane's row means nothing
    close(got_s, want_s)
    assert np.array_equal(np.asarray(got_s[-1]), np.asarray(state[-1]))
    # the fresh lane: as from a zero state
    zero_o, zero_s = step_arrays(q[:1], k[:1], v[:1], alpha[:1], beta[:1],
                                 jnp.zeros_like(state[:1]), packed=p)
    close(got_o[0], zero_o[0])
    close(got_s[0], zero_s[0])


def test_kernel_refuses_what_it_was_not_written_for(interpreted):
    assert not kernel.supports((4, 30, 96, 192), 96, 1)    # 192: no lane tile
    assert not kernel.supports((4, 15, 96, 384), 64, 2)    # another d_k
    assert not kernel.supports((4, 15, 12, 384), 12, 2)    # no sublane tile
    assert not kernel.supports((4, 15, 96, 384), 96, 0)    # not packed
    # and the functional falls back to the composite there
    assert not dr.use_step_kernel((4, 30, 96, 192), 96, None)
    assert kernel.rows_per_block(15, 96, 384, 2) in (1, 3, 5, 15)


def test_without_a_tpu_or_the_switch_the_step_is_the_composite():
    assert not dr.use_step_kernel((4, 15, 96, 384), 96, 2)


# ------------------------------------------------------- the small pieces
def test_gated_norm_norms_first_then_gates():
    rng = np.random.default_rng(0)
    o = rng.normal(size=(2, 5, 4, 32)) * 3
    gate = rng.normal(size=(2, 5, 4, 32))
    w = rng.uniform(0.5, 1.5, size=32)
    got = F.gated_rms_norm(paddle.to_tensor(o.astype(np.float32)),
                           paddle.to_tensor(gate.astype(np.float32)),
                           paddle.to_tensor(w.astype(np.float32)),
                           epsilon=1e-6)
    normed = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * w
    want = normed * gate / (1 + np.exp(-gate))
    close(got._data, want)
    # ssm's gates first: another function
    other = ssm.gated_norm_arrays(jnp.asarray(o, jnp.float32),
                                  jnp.asarray(gate, jnp.float32),
                                  jnp.asarray(w, jnp.float32), 4, 1e-6)
    assert np.abs(np.asarray(other).reshape(want.shape) - want).max() > 0.1


@pytest.mark.parametrize("bsz", [1, 3])
def test_convolution_carries_its_window(bsz):
    """One sequence runs as (T, C), several as (B, T, C): both are
    ``ssm.conv_arrays`` with a zero bias, and a cut sequence continues from
    the window."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(bsz, 20, 24)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, size=(24, 4)), jnp.float32)
    window = jnp.asarray(rng.normal(size=(bsz, 3, 24)), jnp.float32)
    got, last = conv_arrays(x, w, window)
    want, want_last = ssm.conv_arrays(x, w, jnp.zeros((24,)), window)
    close(got, want)
    assert np.array_equal(np.asarray(last), np.asarray(want_last))
    first, mid = conv_arrays(x[:, :9], w, window)
    second, end = conv_arrays(x[:, 9:], w, mid)
    close(jnp.concatenate([first, second], axis=1), want)
    assert np.array_equal(np.asarray(end), np.asarray(want_last))


def test_op_wrappers_take_tensors_and_default_the_state():
    q, k, v, alpha_log, beta = inputs(9, seed=4)
    t = [paddle.to_tensor(a.astype(np.float32))
         for a in (q, k, v, alpha_log, beta)]
    o, s = F.gated_delta_chunk(*t, chunk_size=4)
    want_o, want_s = loop(q, k, v, alpha_log, beta,
                          np.zeros((B, H, DK, DV)), np.ones((B, 9), bool))
    close(o._data, want_o)
    close(s._data, want_s)
    o1, s1 = F.gated_delta_step(
        *(paddle.to_tensor(a[:, 0].astype(np.float32)) for a in (q, k, v)),
        paddle.to_tensor(np.exp(alpha_log[:, 0]).astype(np.float32)),
        paddle.to_tensor(beta[:, 0].astype(np.float32)),
        paddle.to_tensor(np.zeros((B, H, DK, DV), np.float32)))
    close(o1._data, want_o[:, 0])


# ====================================================== a decay a channel
# Kimi delta attention: ``alpha`` is a vector a head, ``S_t = (I - beta_t
# k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``.
channel_chunk_arrays = jax.jit(dr.channel_chunk_arrays, static_argnums=(7,))
channel_step_arrays = jax.jit(dr.channel_step_arrays)


def channel_inputs(tokens, seed=0, steep=None):
    """``inputs`` with a log decay a key channel; ``steep``: every third
    channel decays by that much a token (``exp(-30)`` is 1e-13: over a full
    chunk its cumulative log decay passes -1900, where ``exp`` of the
    negated difference is float32's infinity)."""
    q, k, v, _alpha_log, beta = inputs(tokens, seed)
    rng = np.random.default_rng(seed + 100)
    alpha_log = -np.exp(rng.normal(size=(B, tokens, H, DK))) * 0.3
    if steep is not None:
        alpha_log[..., ::3] = -steep
    return q, k, v, alpha_log, beta


def channel_loop(q, k, v, alpha_log, beta, state, valid):
    """The recurrence as written, a token and a head at a time, float64."""
    s = np.array(state, np.float64)
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        for b in range(q.shape[0]):
            if not valid[b, t]:
                continue
            for h in range(q.shape[2]):
                decayed = np.exp(alpha_log[b, t, h])[:, None] * s[b, h]
                u = beta[b, t, h] * (v[b, t, h] - decayed.T @ k[b, t, h])
                s[b, h] = decayed + np.outer(k[b, t, h], u)
                out[b, t, h] = s[b, h].T @ q[b, t, h]
    return out, s


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("kind", ["all", "left", "right", "none"])
@pytest.mark.parametrize("tokens,chunk", [(1, 64), (7, 4), (64, 64),
                                          (150, 64), (40, 64), (70, 16),
                                          (200, 128)])
def test_channel_chunked_form_is_the_token_loop(tokens, chunk, kind, carried):
    """From a zero and from a carried state, ragged ``valid``, chunk lengths
    that are no multiple of 64 (nor of the sub-block's 16)."""
    q, k, v, alpha_log, beta = channel_inputs(tokens, seed=tokens)
    state = (np.random.default_rng(1).normal(size=(B, H, DK, DV))
             if carried else np.zeros((B, H, DK, DV)))
    valid = ragged(tokens, kind)
    want_o, want_s = channel_loop(q, k, v, alpha_log, beta, state, valid)
    got_o, got_s = channel_chunk_arrays(
        *map(jnp.asarray, (q, k, v, alpha_log, beta, state)),
        jnp.asarray(valid), chunk)
    live = valid[:, :, None, None]
    close(np.where(live, got_o, 0.0), np.where(live, want_o, 0.0))
    close(got_s, want_s)
    if kind == "none":      # no valid row: the state back bit for bit
        assert np.array_equal(np.asarray(got_s[0]),
                              np.asarray(state[0], np.float32))


@pytest.mark.parametrize("steep", [30.0, 88.0])
def test_a_channel_that_decays_by_e30_a_token_is_finite_and_the_loop(steep):
    """No ``exp`` of a positive number anywhere in the chunk: channels whose
    log decay reaches -30 (and -88, the edge of float32) a token over a full
    chunk of 64 beside channels that hardly decay. ``(K * e^g)(K * e^-g)^T``
    would hold ``e^1900``."""
    tokens = 128
    q, k, v, alpha_log, beta = channel_inputs(tokens, seed=4, steep=steep)
    state = np.random.default_rng(3).normal(size=(B, H, DK, DV))
    valid = np.ones((B, tokens), bool)
    want_o, want_s = channel_loop(q, k, v, alpha_log, beta, state, valid)
    got_o, got_s = channel_chunk_arrays(
        *map(jnp.asarray, (q, k, v, alpha_log, beta, state)),
        jnp.asarray(valid), 64)
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    close(got_o, want_o)
    close(got_s, want_s)


@pytest.mark.parametrize("tokens,chunk", [(40, 16), (150, 64)])
def test_a_decay_constant_over_channels_is_the_scalar_call(tokens, chunk):
    """The per-channel forms handed one number a head repeated over the
    channels are the scalar forms (other sums, the same function), through
    the public ops as through the arrays."""
    q, k, v, alpha_log, beta = map(jnp.asarray, inputs(tokens, seed=7))
    wide = jnp.broadcast_to(alpha_log[..., None], alpha_log.shape + (DK,))
    state = jnp.asarray(np.random.default_rng(5).normal(
        size=(B, H, DK, DV)), jnp.float32)
    valid = jnp.asarray(ragged(tokens, "right"))
    want_o, want_s = F.gated_delta_chunk(q, k, v, alpha_log, beta, state,
                                         valid, chunk_size=chunk)
    got_o, got_s = F.gated_delta_chunk(q, k, v, wide, beta, state, valid,
                                       chunk_size=chunk)
    live = np.asarray(valid)[:, :, None, None]
    close(np.where(live, got_o.numpy(), 0.0),
          np.where(live, want_o.numpy(), 0.0))
    close(got_s.numpy(), want_s.numpy())
    want_o, want_s = F.gated_delta_step(
        q[:, 0], k[:, 0], v[:, 0], jnp.exp(alpha_log[:, 0]), beta[:, 0],
        state)
    got_o, got_s = F.gated_delta_step(
        q[:, 0], k[:, 0], v[:, 0], jnp.exp(wide[:, 0]), beta[:, 0], state)
    close(got_o.numpy(), want_o.numpy())
    close(got_s.numpy(), want_s.numpy())


def test_the_scalar_call_traces_the_program_it_traced():
    """The scalar chunked form is untouched by the per-channel one: its
    jaxpr holds no operand with a trailing channel axis on the decay, and
    its stamp still reads ``delta_rule_chunk[T,C]`` where the per-channel
    call's reads ``delta_rule_chunk_channel[T,C]``."""
    from paddle_tpu.observability import trace

    q, k, v, alpha_log, beta = map(jnp.asarray, inputs(40, seed=5))
    wide = jnp.broadcast_to(alpha_log[..., None], alpha_log.shape + (DK,))
    zero, valid = jnp.zeros((B, H, DK, DV)), jnp.ones((B, 40), bool)

    def scalar(*a):
        return dr.chunk_arrays(*a, zero, valid, 16)

    def channel(*a):
        return dr.channel_chunk_arrays(*a, zero, valid, 16)

    trace.startup_clear()
    jax.jit(scalar).lower(q, k, v, alpha_log, beta)
    jax.jit(channel).lower(q, k, v, wide, beta)
    notes = {e[5]["program"]: e[5] for e in trace.startup_record()["entries"]
             if e[0] == "compile.trace"}
    assert notes["scalar"]["delta_rule_chunk[40,16]"]["decay"] == "head"
    assert "delta_rule_chunk_channel[40,16]" not in notes["scalar"]
    assert notes["channel"]["delta_rule_chunk_channel[40,16]"] == dict(
        dr.chunk_plan(40, 16, "channel"), calls=1)
    trace.startup_clear()


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
def test_channel_step_applied_t_times_is_the_token_loop(carried):
    tokens = 40
    q, k, v, alpha_log, beta = channel_inputs(tokens, seed=3)
    state = (np.random.default_rng(2).normal(size=(B, H, DK, DV))
             if carried else np.zeros((B, H, DK, DV)))
    valid = ragged(tokens, "right")
    want_o, want_s = channel_loop(q, k, v, alpha_log, beta, state, valid)
    s = jnp.asarray(state, jnp.float32)
    outs = []
    for t in range(tokens):
        o, s = channel_step_arrays(
            *(jnp.asarray(a[:, t]) for a in (q, k, v)),
            jnp.exp(jnp.asarray(alpha_log[:, t])), jnp.asarray(beta[:, t]),
            s, None, jnp.asarray(~valid[:, t]))
        outs.append(o)
    live = valid[:, :, None, None]
    close(np.where(live, np.stack(outs, 1), 0.0), np.where(live, want_o, 0.0))
    close(s, want_s)


@pytest.mark.parametrize("heads,lanes", [(8, 3), (32, 2)],
                         ids=["one-block", "two-blocks"])
def test_channel_step_kernel_is_step_arrays(monkeypatch, heads, lanes):
    """``delta_rule_step`` with the decay as a column, interpreted, against
    ``channel_step_arrays``: a fresh lane starts from zeros whatever its
    tile held, an idle lane gets its state back bit for bit, the others
    agree to float32 rounding. 32 heads of 128 x 128 are two grid steps of
    16 (three columns a head fill a 128-lane tile at 42)."""
    monkeypatch.setattr(kernel, "INTERPRET", True)
    d = 128
    rng = np.random.default_rng(heads)
    q, k, v = (jnp.asarray(rng.normal(size=(lanes, heads, d)), jnp.float32)
               for _ in range(3))
    alpha = jnp.asarray(rng.uniform(0.05, 1.0, (lanes, heads, d)),
                        jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (lanes, heads)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(lanes, heads, d, d)), jnp.float32)
    fresh = jnp.zeros((lanes,), bool).at[0].set(True)
    idle = jnp.zeros((lanes,), bool).at[lanes - 1].set(True)
    assert kernel.supports(state.shape, d, 1, channel=True)
    assert kernel.rows_per_block(heads, d, d, 1, columns=3) == min(heads, 16)
    assert dr.use_step_kernel(state.shape, d, 1, channel=True)
    want_o, want_s = dr.channel_step_arrays(q, k, v, alpha, beta, state,
                                            fresh, idle)
    got_o, got_s = jax.jit(dr.step_any)(q, k, v, alpha, beta, state, fresh,
                                        idle)
    close(got_o[:-1], want_o[:-1], 2e-4)        # values of order 10
    close(got_s, want_s, 2e-4)
    assert np.array_equal(np.asarray(got_s[-1]), np.asarray(state[-1]))
    # the fresh lane: what one token writes into zeros
    close(got_s[0], k[0][..., None] * (beta[0][..., None] * v[0])[:, None, :],
          2e-4)
    with pytest.raises(ValueError, match="unpacked"):
        dr.step_any(q, k, v, alpha, beta, state, packed=2)


def test_gated_rms_norm_gates_by_sigmoid_where_asked():
    rng = np.random.default_rng(0)
    o = jnp.asarray(rng.normal(size=(2, 5, 3, 16)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(2, 5, 3, 16)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, 16), jnp.float32)
    normed = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) * w
    close(F.gated_rms_norm(o, g, w, epsilon=1e-5,
                           activation="sigmoid").numpy(),
          normed * jax.nn.sigmoid(g))
    close(F.gated_rms_norm(o, g, w, epsilon=1e-5).numpy(),
          normed * jax.nn.silu(g))
