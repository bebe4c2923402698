"""Common functionals: linear, dropout, embedding, pad, interpolate, etc.

Reference: python/paddle/nn/functional/common.py, input.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import dispatch, residuals
from ...core.generator import next_key
from ...core.tensor import Tensor, as_tensor


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b; W is (in, out) per the reference layout
    (python/paddle/nn/functional/common.py linear)."""
    inputs = [_t(x), _t(weight)]
    if bias is not None:
        inputs.append(_t(bias))

    def f(a, w, b=None):
        y = jnp.matmul(a, w.astype(a.dtype))
        if b is not None:
            y = y + b.astype(a.dtype)
        # inside a rematerialised block the result gets the name under which
        # the block's policy may keep it (``models/_remat.py``); anywhere
        # else it gets none, and the program is the one it was
        return residuals.keep(y, residuals.LINEAR_OUT) \
            if residuals.is_open() else y
    return dispatch.call("linear", f, inputs)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    """Zero elements with probability p at train time, rescaling survivors
    (reference dropout)."""
    x = _t(x)
    if not training or p == 0:
        if mode == "downscale_in_infer" and not training:
            return dispatch.call("dropout_scale", lambda a: a * (1 - p), [x])
        return x
    if p == 1:
        return dispatch.call("dropout", lambda a: jnp.zeros_like(a), [x])
    key = next_key()

    def f(a):
        shape = list(a.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1 - p, tuple(shape))
        y = jnp.where(keep, a, 0.0)
        if mode == "upscale_in_train":
            y = y / (1 - p)
        return y
    return dispatch.call("dropout", f, [x])


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    """Channel-wise dropout over NCHW feature maps (reference dropout2d)."""
    ax = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    """Channel-wise dropout over NCDHW feature maps (reference dropout3d)."""
    ax = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=ax, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    """SELU-preserving dropout: dropped units take the negative saturation
    value (reference alpha_dropout)."""
    x = _t(x)
    if not training or p == 0:
        return x
    key = next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(a):
        keep = jax.random.bernoulli(key, 1 - p, a.shape)
        coef_a = ((1 - p) * (1 + p * alpha_p ** 2)) ** -0.5
        coef_b = -coef_a * p * alpha_p
        return coef_a * jnp.where(keep, a, alpha_p) + coef_b
    return dispatch.call("alpha_dropout", f, [x])


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Row gather from ``weight`` by integer ids, optional padding_idx zero-
    grad (reference embedding)."""
    x, w = _t(x), _t(weight)

    def f(ids, table):
        out = jnp.take(table, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out
    return dispatch.call("embedding", f, [x, w],
                         differentiable_mask=[False, True])


def embedding_bag(x, weight, mode="sum", padding_idx=None, name=None):
    """Pooled row gather: ids ``(..., L)`` x table ``(V, H)`` ->
    ``(..., H)``, reduced over the bag dim ``L`` (reference
    embedding_bag; the DLRM multi-hot lookup shape).

    ``padding_idx`` rows contribute zero to the pool; ``mode="mean"``
    divides by the count of non-padding ids per bag (a bag of only
    padding ids pools to zero). The op traces as ``embedding_bag`` so
    the planner prices it and the spmd rule marks the output
    reduce-pending over a vocab-sharded table's axes (see
    ``distributed/spmd/rules.py:embedding_bag_rule``).
    """
    if mode not in ("sum", "mean"):
        raise ValueError(f"embedding_bag: mode must be sum|mean, "
                         f"got {mode!r}")
    x, w = _t(x), _t(weight)

    def f(ids, table):
        ids32 = ids.astype(jnp.int32)
        rows = jnp.take(table, ids32, axis=0)
        if padding_idx is not None:
            keep = (ids32 != padding_idx)[..., None]
            rows = jnp.where(keep, rows, 0.0)
            denom = jnp.maximum(
                jnp.sum(keep, axis=-2).astype(rows.dtype), 1.0)
        else:
            denom = jnp.asarray(float(ids32.shape[-1]), rows.dtype)
        pooled = jnp.sum(rows, axis=-2)
        if mode == "mean":
            pooled = pooled / denom
        return pooled
    return dispatch.call("embedding_bag", f, [x, w],
                         differentiable_mask=[False, True])


def one_hot(x, num_classes, name=None):
    return dispatch.call(
        "one_hot",
        lambda a: jax.nn.one_hot(a.astype(jnp.int32), num_classes,
                                 dtype=jnp.float32),
        [_t(x)], differentiable_mask=[False])


_PAD_MODES = {"constant": "constant", "reflect": "reflect",
              "replicate": "edge", "circular": "wrap"}


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    x = _t(x)
    if isinstance(pad, Tensor):
        pad = [int(v) for v in pad.numpy().tolist()]
    pad = list(pad)

    def f(a):
        nd = a.ndim
        if len(pad) == 2 * nd:
            cfg = [(pad[2 * i], pad[2 * i + 1]) for i in range(nd)]
        else:
            # paddle semantics: pad applies to spatial dims, ordered last-first
            nspatial = len(pad) // 2
            cfg = [(0, 0)] * nd
            channel_last = data_format.endswith("C") and data_format != "NC"
            spatial_start = 1 if channel_last else 2
            for i in range(nspatial):
                dim = spatial_start + (nspatial - 1 - i)
                cfg[dim] = (pad[2 * i], pad[2 * i + 1])
        if mode == "constant":
            return jnp.pad(a, cfg, constant_values=value)
        return jnp.pad(a, cfg, mode=_PAD_MODES[mode])
    return dispatch.call("pad", f, [x])


def zeropad2d(x, padding, data_format="NCHW", name=None):
    """Zero-pad the two spatial dims of NCHW input (reference zeropad2d)."""
    return pad(x, padding, mode="constant", value=0.0, data_format=data_format)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    """Resize spatial dims by nearest/bilinear/bicubic/area/trilinear
    (reference interpolate)."""
    x = _t(x)
    channel_last = data_format in ("NHWC", "NWC", "NDHWC")
    nd = x.ndim - 2
    spatial = x.shape[1:-1] if channel_last else x.shape[2:]
    if size is not None:
        if isinstance(size, Tensor):
            size = [int(v) for v in size.numpy().tolist()]
        out_size = [int(s) for s in (size if isinstance(size, (list, tuple))
                                     else [size] * nd)]
    else:
        sf = (scale_factor if isinstance(scale_factor, (list, tuple))
              else [scale_factor] * nd)
        out_size = [int(spatial[i] * float(sf[i])) for i in range(nd)]

    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def f(a):
        if channel_last:
            shape = (a.shape[0],) + tuple(out_size) + (a.shape[-1],)
        else:
            shape = a.shape[:2] + tuple(out_size)
        if method == "nearest":
            return jax.image.resize(a, shape, method="nearest")
        if align_corners:
            # jax.image.resize has no align_corners; emulate with explicit
            # coordinate map (reference interpolate align_corners=True).
            y = a
            axes = list(range(1, 1 + nd)) if channel_last else list(range(2, 2 + nd))
            for i, ax in enumerate(axes):
                in_sz, out_sz = y.shape[ax], out_size[i]
                if in_sz == out_sz:
                    continue
                pos = (jnp.arange(out_sz) * (in_sz - 1) / max(out_sz - 1, 1))
                lo = jnp.floor(pos).astype(jnp.int32)
                hi = jnp.minimum(lo + 1, in_sz - 1)
                w = (pos - lo).astype(a.dtype)
                y_lo = jnp.take(y, lo, axis=ax)
                y_hi = jnp.take(y, hi, axis=ax)
                bshape = [1] * y.ndim
                bshape[ax] = out_sz
                w = w.reshape(bshape)
                y = y_lo * (1 - w) + y_hi * w
            return y
        return jax.image.resize(a, shape, method=method)
    return dispatch.call("interpolate", f, [x])


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    """Alias of interpolate (reference upsample)."""
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference: python/paddle/nn/functional/common.py unfold)."""
    x = _t(x)
    k = kernel_sizes if isinstance(kernel_sizes, (list, tuple)) else [kernel_sizes] * 2
    s = strides if isinstance(strides, (list, tuple)) else [strides] * 2
    d = dilations if isinstance(dilations, (list, tuple)) else [dilations] * 2
    p = paddings if isinstance(paddings, (list, tuple)) else [paddings] * 2
    if len(p) == 2:
        p = [p[0], p[1], p[0], p[1]]

    def f(a):
        n, c, h, w = a.shape
        a = jnp.pad(a, [(0, 0), (0, 0), (p[0], p[2]), (p[1], p[3])])
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=tuple(k), window_strides=tuple(s),
            padding=[(0, 0), (0, 0)], rhs_dilation=tuple(d),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # patches: (N, C*kh*kw, out_h, out_w) -> (N, C*kh*kw, L)
        return patches.reshape(n, patches.shape[1], -1)
    return dispatch.call("unfold", f, [x])


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """Combine sliding local blocks back into a spatial tensor — inverse of
    unfold (reference fold)."""
    x = _t(x)
    out = output_sizes if isinstance(output_sizes, (list, tuple)) else [output_sizes] * 2
    k = kernel_sizes if isinstance(kernel_sizes, (list, tuple)) else [kernel_sizes] * 2
    s = strides if isinstance(strides, (list, tuple)) else [strides] * 2
    d = dilations if isinstance(dilations, (list, tuple)) else [dilations] * 2
    p = paddings if isinstance(paddings, (list, tuple)) else [paddings] * 2
    if len(p) == 2:
        p = [p[0], p[1], p[0], p[1]]

    def f(a):
        n, ckk, L = a.shape
        c = ckk // (k[0] * k[1])
        ph = out[0] + p[0] + p[2]
        pw = out[1] + p[1] + p[3]
        oh = (ph - d[0] * (k[0] - 1) - 1) // s[0] + 1
        ow = (pw - d[1] * (k[1] - 1) - 1) // s[1] + 1
        a = a.reshape(n, c, k[0], k[1], oh, ow)
        result = jnp.zeros((n, c, ph, pw), dtype=a.dtype)
        for i in range(k[0]):
            for j in range(k[1]):
                hi = i * d[0]
                wj = j * d[1]
                result = result.at[:, :, hi:hi + oh * s[0]:s[0],
                                   wj:wj + ow * s[1]:s[1]].add(a[:, :, i, j])
        return result[:, :, p[0]:ph - p[2], p[1]:pw - p[3]]
    return dispatch.call("fold", f, [x])


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    """Rearrange (C*r^2, H, W) -> (C, H*r, W*r) for sub-pixel conv (reference
    pixel_shuffle)."""
    x = _t(x)
    r = upscale_factor

    def f(a):
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 3, 1, 2))
        n, c, h, w = a.shape
        y = a.reshape(n, c // (r * r), r, r, h, w)
        y = jnp.transpose(y, (0, 1, 4, 2, 5, 3))
        y = y.reshape(n, c // (r * r), h * r, w * r)
        if data_format == "NHWC":
            y = jnp.transpose(y, (0, 2, 3, 1))
        return y
    return dispatch.call("pixel_shuffle", f, [x])


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    """Inverse of pixel_shuffle (reference pixel_unshuffle)."""
    x = _t(x)
    r = downscale_factor

    def f(a):
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 3, 1, 2))
        n, c, h, w = a.shape
        y = a.reshape(n, c, h // r, r, w // r, r)
        y = jnp.transpose(y, (0, 1, 3, 5, 2, 4))
        y = y.reshape(n, c * r * r, h // r, w // r)
        if data_format == "NHWC":
            y = jnp.transpose(y, (0, 2, 3, 1))
        return y
    return dispatch.call("pixel_unshuffle", f, [x])


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    """Interleave channel groups, ShuffleNet-style (reference channel_shuffle).
    """
    x = _t(x)

    def f(a):
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 3, 1, 2))
        n, c, h, w = a.shape
        y = a.reshape(n, groups, c // groups, h, w)
        y = jnp.swapaxes(y, 1, 2).reshape(n, c, h, w)
        if data_format == "NHWC":
            y = jnp.transpose(y, (0, 2, 3, 1))
        return y
    return dispatch.call("channel_shuffle", f, [x])


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    """Dot product of L2-normalized inputs along ``axis`` (reference
    cosine_similarity)."""
    def f(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.maximum(
            jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis), eps)
        return num / den
    return dispatch.call("cosine_similarity", f, [_t(x1), _t(x2)])


def bilinear(x1, x2, weight, bias=None, name=None):
    """Bilinear form x1^T W x2 + b per output channel (reference bilinear)."""
    inputs = [_t(x1), _t(x2), _t(weight)]
    if bias is not None:
        inputs.append(_t(bias))

    def f(a, b, w, *bb):
        y = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bb:
            y = y + bb[0]
        return y
    return dispatch.call("bilinear", f, inputs)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    """Blend one-hot labels toward uniform (or prior_dist) by epsilon
    (reference label_smooth)."""
    label = _t(label)
    inputs = [label]
    if prior_dist is not None:
        inputs.append(_t(prior_dist))

    def f(lab, *pd):
        c = lab.shape[-1]
        if pd:
            return (1 - epsilon) * lab + epsilon * pd[0]
        return (1 - epsilon) * lab + epsilon / c
    return dispatch.call("label_smooth", f, inputs)


__all__ = [
    "linear", "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    "embedding", "embedding_bag", "one_hot", "pad", "zeropad2d",
    "interpolate", "upsample",
    "unfold", "fold", "pixel_shuffle", "pixel_unshuffle", "channel_shuffle",
    "cosine_similarity", "bilinear", "label_smooth",
]


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    """[B] lengths -> [B, maxlen] 0/1 mask (reference ops.yaml
    sequence_mask). maxlen=None derives it from the (concrete) lengths
    BEFORE tracing — under capture, pass an explicit maxlen."""
    import jax as _jax

    from ...core import dispatch as _dispatch
    t = lengths if isinstance(lengths, Tensor) else as_tensor(lengths)
    if maxlen is None:
        if isinstance(t._data, _jax.core.Tracer):
            raise ValueError(
                "sequence_mask(maxlen=None) needs concrete lengths; pass "
                "an explicit maxlen under jit/to_static (shapes must be "
                "static)")
        # tpulint: disable=TPU103 — maxlen becomes an output SHAPE; guarded by the Tracer check above
        maxlen = int(jnp.max(t._data))

    def f(l):
        return (jnp.arange(maxlen)[None, :] < l[..., None]).astype(dtype)
    return _dispatch.call("sequence_mask", f, [t])

__all__ += ['sequence_mask']
