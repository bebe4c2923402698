"""State-space (Mamba-2 / SSD) sequence mixing with a carried state.

Reference: Dao & Gu 2024, "Transformers are SSMs" (the state-space dual
form), as the ``nemotron_h`` / ``mamba2`` model families use it. Per head
``h`` with state ``S`` of ``(P, N)``::

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t B_t^T
    y_t = S_t C_t + D_h * x_t

Three pieces, each taking and returning what a serving cache carries so a
sequence can be fed in pieces:

* ``causal_conv1d`` — the depthwise convolution in front of the scan, with
  the last ``K - 1`` inputs as its carried window (``gated_short_conv`` is
  LFM2's operator of the same family: the convolution gated on both sides,
  no activation, over whole sequences);
* ``ssd_chunk_scan`` — the chunked (SSD) form for a run of tokens that
  CONTINUES from a carried state: inside a chunk the recurrence is a masked
  matrix product, between chunks a short scan over per-chunk states;
* ``ssd_state_update`` — the recurrence itself for one token (decode).

A position with ``dt == 0`` leaves the state as it was (``exp(0) = 1``, the
input term vanishes), which is how callers pad. The state is kept in
float32 whatever the activations are.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import dispatch
from ...core.tensor import Tensor, as_tensor

__all__ = ["causal_conv1d", "gated_short_conv", "ssd_chunk_scan",
           "ssd_state_update", "gated_group_rms_norm"]


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


# ------------------------------------------------------------ array level
def conv_arrays(x, weight, bias, window):
    """``x`` (B, T, C), ``weight`` (C, K), ``bias`` (C,), ``window``
    (B, K-1, C): the inputs before ``x``. Returns ``(silu(conv + bias),
    the last K-1 inputs)``."""
    k = weight.shape[1]
    t = x.shape[1]
    full = jnp.concatenate([window.astype(x.dtype), x], axis=1)
    acc = bias.astype(jnp.float32)[None, None, :]
    for j in range(k):
        acc = acc + (full[:, j:j + t, :].astype(jnp.float32)
                     * weight[:, j].astype(jnp.float32)[None, None, :])
    return jax.nn.silu(acc).astype(x.dtype), full[:, -(k - 1):, :]


def gated_conv_arrays(bcz, taps):
    """``bcz`` (B, T, 3C): the in-projection's ``[B | C | z]``; ``taps``
    (C, K). ``C * conv(B * z)`` with ``conv[t] = sum_j taps[:, j] *
    (B * z)[t - (K - 1) + j]``, zeros left of the first token, accumulated
    in float32."""
    f32 = jnp.float32
    b, c, z = jnp.split(bcz, 3, axis=-1)
    k, t = taps.shape[1], bcz.shape[1]
    g = jnp.pad(b.astype(f32) * z.astype(f32), ((0, 0), (k - 1, 0), (0, 0)))
    acc = sum(g[:, j:j + t, :] * taps[:, j].astype(f32)[None, None, :]
              for j in range(k))
    return (c.astype(f32) * acc).astype(bcz.dtype)


def scan_arrays(x, dt, a, b, c, d, state, chunk_size):
    """``x`` (B, T, H, P); ``dt`` (B, T, H) after softplus; ``a`` (H,)
    negative; ``b`` / ``c`` (B, T, G, N), head ``h`` reading group
    ``h // (H / G)``; ``d`` (H,); ``state`` (B, H, P, N) float32. Returns
    ``(y (B, T, H, P) in x's dtype, the state after the last token)``."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    q = min(chunk_size, t)
    pad = -t % q
    if pad:     # dt = 0 rows at the end: the state passes through them
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    per = h // g
    f32 = jnp.float32
    dt = dt.astype(f32)
    xs = x.reshape(bsz, nc, q, g, per, p)
    # dt-weighted input, in the activations' dtype for the matrix unit
    xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype).reshape(
        bsz, nc, q, g, per, p)
    bs = b.reshape(bsz, nc, q, g, n)
    cs = c.reshape(bsz, nc, q, g, n)
    la = (dt * a.astype(f32)[None, None, :]).reshape(bsz, nc, q, g, per)
    cum = jnp.cumsum(la, axis=2)                       # log decay to t
    # inside a chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cs, bs,
                    preferred_element_type=f32)
    seg = cum[:, :, :, None] - cum[:, :, None, :]      # (b, c, q, s, g, per)
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    mix = (cb.transpose(0, 1, 3, 4, 2)[..., None] * decay).astype(x.dtype)
    y = jnp.einsum("bcqsgr,bcsgrp->bcqgrp", mix, xdt,
                   preferred_element_type=f32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:, :, :] - cum)       # (b, c, q, g, per)
    own = jnp.einsum("bcqgrp,bcqgn->bcgrpn",
                     (xdt.astype(f32) * to_end[..., None]).astype(x.dtype),
                     bs, preferred_element_type=f32)
    total = jnp.exp(cum[:, :, -1, :, :])               # (b, c, g, per)

    def carry(s, inp):
        own_c, total_c = inp
        return s * total_c[..., None, None] + own_c, s

    s0 = state.astype(f32).reshape(bsz, g, per, p, n)
    last, before = jax.lax.scan(
        carry, s0, (own.transpose(1, 0, 2, 3, 4, 5),
                    total.transpose(1, 0, 2, 3)))
    before = before.transpose(1, 0, 2, 3, 4, 5)        # state entering c
    # what the carried state adds: exp(cum_t) * (S_in C_t)
    y = y + jnp.einsum("bcgrpn,bcqgn->bcqgrp", before, cs.astype(f32),
                       preferred_element_type=f32) \
        * jnp.exp(cum)[..., None]
    y = y + xs.astype(f32) * d.astype(f32).reshape(g, per)[
        None, None, None, :, :, None]
    y = y.reshape(bsz, nc * q, h, p)[:, :t]
    return y.astype(x.dtype), last.reshape(bsz, h, p, n)


def step_arrays(x, dt, a, b, c, d, state):
    """One token: ``x`` (B, H, P), ``dt`` (B, H), ``b`` / ``c`` (B, G, N),
    ``state`` (B, H, P, N) float32."""
    bsz, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    per = h // g
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32)[None, :])
    xf = x.astype(f32)
    bh = jnp.repeat(b.astype(f32), per, axis=1)        # (B, H, N)
    ch = jnp.repeat(c.astype(f32), per, axis=1)
    new = (state.astype(f32) * decay[..., None, None]
           + (xf * dt[..., None])[..., :, None] * bh[:, :, None, :])
    y = jnp.sum(new * ch[:, :, None, :], axis=-1) \
        + xf * d.astype(f32)[None, :, None]
    return y.astype(x.dtype), new


def gated_norm_arrays(y, z, weight, groups, epsilon):
    """``RMSNorm_groups(y * silu(z)) * weight``: gate first, then normalise
    over each of ``groups`` equal slices of the last axis."""
    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    shape = v.shape
    v = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + epsilon)
    return (v.reshape(shape) * weight.astype(f32)).astype(y.dtype)


# ----------------------------------------------------------- tensor level
def causal_conv1d(x, weight, bias, window=None, name=None):
    """Causal depthwise 1-D convolution followed by SiLU, with a carried
    window. ``x`` (B, T, C), ``weight`` (C, K), ``bias`` (C,); ``window``
    (B, K-1, C) holds the K-1 inputs before ``x`` (zeros when omitted: the
    start of a sequence). Returns ``(y (B, T, C), new window)``."""
    x, weight, bias = _t(x), _t(weight), _t(bias)
    if window is None:
        window = Tensor(jnp.zeros(
            (x.shape[0], weight.shape[1] - 1, x.shape[2]), x._data.dtype))
    return dispatch.call("causal_conv1d", conv_arrays,
                         [x, weight, bias, _t(window)])


def gated_short_conv(bcz, taps, name=None):
    """LFM2's gated short convolution over whole sequences (training, no
    carried window): ``bcz`` (B, T, 3C) is the in-projection's output
    ``[B | C | z]``, ``taps`` (C, K) the depthwise causal filter (no bias).
    Returns ``C * conv(B * z)`` (B, T, C); differentiable in both."""
    return dispatch.call("gated_short_conv", gated_conv_arrays,
                         [_t(bcz), _t(taps)])


def ssd_chunk_scan(x, dt, a, b, c, d, state=None, chunk_size=128,
                   name=None):
    """Mamba-2 selective scan in its chunked (SSD) form over T tokens that
    continue from ``state``. ``x`` (B, T, H, P), ``dt`` (B, T, H) positive
    (after softplus; 0 marks padding), ``a`` (H,) negative, ``b`` / ``c``
    (B, T, G, N), ``d`` (H,), ``state`` (B, H, P, N) float32 (zeros when
    omitted). Returns ``(y (B, T, H, P), state after the last token)``."""
    x = _t(x)
    if state is None:
        state = Tensor(jnp.zeros((x.shape[0], x.shape[2], x.shape[3],
                                  _t(b).shape[3]), jnp.float32))

    def f(xa, dta, aa, ba, ca, da, sa, **_attrs):
        return scan_arrays(xa, dta, aa, ba, ca, da, sa, chunk_size)

    return dispatch.call(
        "ssd_chunk_scan", f,
        [x, _t(dt), _t(a), _t(b), _t(c), _t(d), _t(state)],
        attrs={"chunk_size": int(chunk_size)})


def ssd_state_update(x, dt, a, b, c, d, state, name=None):
    """The Mamba-2 recurrence for ONE token (decode): ``x`` (B, H, P),
    ``dt`` (B, H), ``b`` / ``c`` (B, G, N), ``state`` (B, H, P, N) float32.
    Returns ``(y (B, H, P), new state)``."""
    return dispatch.call(
        "ssd_state_update", step_arrays,
        [_t(x), _t(dt), _t(a), _t(b), _t(c), _t(d), _t(state)])


def gated_group_rms_norm(y, z, weight, groups=1, epsilon=1e-5, name=None):
    """``RMSNorm(y * silu(z)) * weight`` with the mean square taken over
    each of ``groups`` equal slices of the last axis (Mamba-2's gated
    norm, gate before norm)."""
    def f(ya, za, wa, **_attrs):
        return gated_norm_arrays(ya, za, wa, groups, epsilon)

    return dispatch.call("gated_group_rms_norm", f,
                         [_t(y), _t(z), _t(weight)],
                         attrs={"groups": int(groups),
                                "epsilon": float(epsilon)})
