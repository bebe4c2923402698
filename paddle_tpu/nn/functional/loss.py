"""Loss functionals.

Reference: python/paddle/nn/functional/loss.py (cross_entropy at its heart is
phi softmax_with_cross_entropy). Labels are non-differentiable inputs; the
dispatcher routes float0 cotangents around them automatically.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core import dispatch
from ...core.tensor import Tensor, as_tensor


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def _reduce(val, reduction, weight_sum=None):
    if reduction == "none":
        return val
    if reduction == "sum":
        return jnp.sum(val)
    if weight_sum is not None:
        return jnp.sum(val) / weight_sum
    return jnp.mean(val)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """softmax+CE in one fused lowering (reference: loss.py cross_entropy →
    _C_ops.cross_entropy_with_softmax)."""
    input, label = _t(input), _t(label)
    inputs = [input, label]
    has_w = weight is not None
    if has_w:
        inputs.append(_t(weight))

    def f(logits, lab, *w):
        ax = axis if axis >= 0 else logits.ndim + axis
        c = logits.shape[ax]
        hard = not (soft_label or (lab.ndim == logits.ndim
                                   and lab.shape[ax] == c
                                   and jnp.issubdtype(lab.dtype,
                                                      jnp.floating)))
        if use_softmax and hard:
            # streaming formulation: nll = lse - logits[label]. Never
            # materializes an f32 (N, V) log-prob tensor — the f32 cast
            # fuses into the reductions, the big buffer stays in the
            # input dtype (bf16 under AMP). Cuts the GPT-class lm-head
            # loss from ~5 HBM passes of f32 to ~3 passes of bf16.
            m = jax.lax.stop_gradient(
                jnp.max(logits, axis=ax, keepdims=True))
            shifted = (logits - m).astype(jnp.float32)
            sumexp = jnp.sum(jnp.exp(shifted), axis=ax)
            lse = jnp.log(sumexp) + jnp.squeeze(m.astype(jnp.float32), ax)
            lab_i = lab.astype(jnp.int32)
            if lab_i.ndim == logits.ndim and lab_i.shape[ax] == 1:
                lab_i = jnp.squeeze(lab_i, axis=ax)
            valid = lab_i != ignore_index
            safe = jnp.where(valid, lab_i, 0)
            picked = jnp.squeeze(jnp.take_along_axis(
                logits, jnp.expand_dims(safe, ax), axis=ax), ax)
            nll = lse - picked.astype(jnp.float32)
            if label_smoothing > 0:
                # mean_logp = mean(logits) - lse
                smooth = lse - jnp.mean(logits.astype(jnp.float32), axis=ax)
                nll = (1 - label_smoothing) * nll + label_smoothing * smooth
            return _hard_label_reduce(nll, valid, w, has_w, safe, reduction)
        if use_softmax:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=ax)
        else:
            logp = jnp.log(jnp.clip(logits.astype(jnp.float32), 1e-15, 1.0))
        if not hard:
            soft = lab.astype(jnp.float32)
            if label_smoothing > 0:
                soft = soft * (1 - label_smoothing) + label_smoothing / c
            loss = -jnp.sum(soft * logp, axis=ax)
            if has_w:
                wvec = w[0].astype(jnp.float32)
                loss = loss * jnp.sum(soft * wvec.reshape(
                    [1] * ax + [c] + [1] * (logits.ndim - ax - 1)), axis=ax)
            return _reduce(loss, reduction)
        lab_i = lab.astype(jnp.int32)
        if lab_i.ndim == logits.ndim and lab_i.shape[ax] == 1:
            lab_i = jnp.squeeze(lab_i, axis=ax)
        valid = lab_i != ignore_index
        safe = jnp.where(valid, lab_i, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, ax),
                                     axis=ax)
        nll = -jnp.squeeze(picked, axis=ax)
        if label_smoothing > 0:
            smooth = -jnp.mean(logp, axis=ax)
            nll = (1 - label_smoothing) * nll + label_smoothing * smooth
        return _hard_label_reduce(nll, valid, w, has_w, safe, reduction)
    return dispatch.call("cross_entropy", f, inputs,
                         differentiable_mask=[True, soft_label] + [False] * has_w)


def _hard_label_reduce(nll, valid, w, has_w, safe, reduction):
    """Shared ignore_index/weight epilogue of both hard-label CE paths."""
    if has_w:
        wv = jnp.take(w[0].astype(jnp.float32), safe)
        nll = nll * wv
        nll = jnp.where(valid, nll, 0.0)
        if reduction == "mean":
            return jnp.sum(nll) / jnp.maximum(
                jnp.sum(jnp.where(valid, wv, 0.0)), 1e-12)
        return _reduce(nll, reduction)
    nll = jnp.where(valid, nll, 0.0)
    if reduction == "mean":
        return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)
    return _reduce(nll, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    """Fused softmax + cross entropy on logits (reference
    softmax_with_cross_entropy)."""
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    from .activation import softmax as _softmax
    loss = dispatch.call("unsqueeze", lambda a: jnp.expand_dims(a, axis), [loss])
    if return_softmax:
        return loss, _softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    """Negative log likelihood over log-probabilities with hard labels
    (reference nll_loss)."""
    input, label = _t(input), _t(label)
    inputs = [input, label]
    has_w = weight is not None
    if has_w:
        inputs.append(_t(weight))

    def f(logp, lab, *w):
        lab_i = lab.astype(jnp.int32)
        valid = lab_i != ignore_index
        safe = jnp.where(valid, lab_i, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, 1), axis=1)
        nll = -jnp.squeeze(picked, axis=1)
        wv = (jnp.take(w[0], safe) if has_w
              else jnp.ones_like(nll))
        nll = jnp.where(valid, nll * wv, 0.0)
        if reduction == "mean":
            return jnp.sum(nll) / jnp.maximum(
                jnp.sum(jnp.where(valid, wv, 0.0)), 1e-12)
        return _reduce(nll, reduction)
    return dispatch.call("nll_loss", f, inputs,
                         differentiable_mask=[True, False] + [False] * has_w)


def mse_loss(input, label, reduction="mean", name=None):
    """Mean squared error (reference mse_loss)."""
    return dispatch.call(
        "mse_loss",
        lambda a, b: _reduce((a - b.astype(a.dtype)) ** 2, reduction),
        [_t(input), _t(label)])


def l1_loss(input, label, reduction="mean", name=None):
    """Mean absolute error (reference l1_loss)."""
    return dispatch.call(
        "l1_loss",
        lambda a, b: _reduce(jnp.abs(a - b.astype(a.dtype)), reduction),
        [_t(input), _t(label)])


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    """Huber-style L1 smoothed below delta (reference smooth_l1_loss)."""
    def f(a, b):
        d = a - b.astype(a.dtype)
        ad = jnp.abs(d)
        val = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
        return _reduce(val, reduction)
    return dispatch.call("smooth_l1_loss", f, [_t(input), _t(label)])


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    """BCE over probabilities with optional weight (reference
    binary_cross_entropy)."""
    inputs = [_t(input), _t(label)]
    has_w = weight is not None
    if has_w:
        inputs.append(_t(weight))

    def f(p, y, *w):
        p = jnp.clip(p, 1e-12, 1 - 1e-7)
        val = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if has_w:
            val = val * w[0]
        return _reduce(val, reduction)
    return dispatch.call("binary_cross_entropy", f, inputs,
                         differentiable_mask=[True, True] + [False] * has_w)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    """Numerically stable BCE straight from logits (reference
    binary_cross_entropy_with_logits)."""
    inputs = [_t(logit), _t(label)]
    has_w = weight is not None
    has_pw = pos_weight is not None
    if has_w:
        inputs.append(_t(weight))
    if has_pw:
        inputs.append(_t(pos_weight))

    def f(x, y, *rest):
        y = y.astype(x.dtype)
        max_val = jnp.maximum(-x, 0)
        if has_pw:
            pw = rest[-1]
            log_weight = (pw - 1) * y + 1
            loss = (1 - y) * x + log_weight * (
                jnp.log(jnp.exp(-max_val) + jnp.exp(-x - max_val)) + max_val)
        else:
            loss = (1 - y) * x + max_val + jnp.log(
                jnp.exp(-max_val) + jnp.exp(-x - max_val))
        if has_w:
            loss = loss * rest[0]
        return _reduce(loss, reduction)
    return dispatch.call("bce_with_logits", f, inputs,
                         differentiable_mask=[True, True]
                         + [False] * (has_w + has_pw))


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    """KL divergence sum(target * (log(target) - input)) with input log-probs
    (reference kl_div)."""
    def f(lp, t):
        if log_target:
            val = jnp.exp(t) * (t - lp)
        else:
            tt = jnp.clip(t, 1e-12, None)
            val = t * (jnp.log(tt) - lp)
            val = jnp.where(t > 0, val, 0.0)
        if reduction == "batchmean":
            return jnp.sum(val) / lp.shape[0]
        return _reduce(val, reduction)
    return dispatch.call("kl_div", f, [_t(input), _t(label)])


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    """max(0, -label*(x1-x2) + margin) (reference margin_ranking_loss)."""
    def f(a, b, y):
        return _reduce(jnp.maximum(0.0, -y * (a - b) + margin), reduction)
    return dispatch.call("margin_ranking_loss", f,
                         [_t(input), _t(other), _t(label)],
                         differentiable_mask=[True, True, False])


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    """Hinge on dissimilar pairs, identity on similar (reference
    hinge_embedding_loss)."""
    def f(a, y):
        val = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce(val, reduction)
    return dispatch.call("hinge_embedding_loss", f, [_t(input), _t(label)],
                         differentiable_mask=[True, False])


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    """1 - cos for similar pairs, relu(cos - margin) for dissimilar (reference
    cosine_embedding_loss)."""
    def f(a, b, y):
        cos = (jnp.sum(a * b, axis=-1)
               / jnp.maximum(jnp.linalg.norm(a, axis=-1)
                             * jnp.linalg.norm(b, axis=-1), 1e-12))
        val = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(val, reduction)
    return dispatch.call("cosine_embedding_loss", f,
                         [_t(input1), _t(input2), _t(label)],
                         differentiable_mask=[True, True, False])


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    """max(0, d(a,p) - d(a,n) + margin) over a p-norm metric (reference
    triplet_margin_loss)."""
    def f(a, pos, neg):
        def dist(u, v):
            return jnp.sum(jnp.abs(u - v + epsilon) ** p, axis=-1) ** (1.0 / p)
        d_ap = dist(a, pos)
        d_an = dist(a, neg)
        if swap:
            d_an = jnp.minimum(d_an, dist(pos, neg))
        return _reduce(jnp.maximum(0.0, d_ap - d_an + margin), reduction)
    return dispatch.call("triplet_margin_loss", f,
                         [_t(input), _t(positive), _t(negative)])


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    """Focal-modulated BCE with logits for class imbalance (reference
    sigmoid_focal_loss)."""
    inputs = [_t(logit), _t(label)]
    if normalizer is not None:
        inputs.append(_t(normalizer))

    def f(x, y, *n):
        p = jax.nn.sigmoid(x)
        ce = jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce(loss, reduction)
    return dispatch.call("sigmoid_focal_loss", f, inputs,
                         differentiable_mask=[True, False]
                         + [False] * (normalizer is not None))


def square_error_cost(input, label):
    """Elementwise (input - label)^2, unreduced (reference square_error_cost).
    """
    return dispatch.call("square_error_cost",
                         lambda a, b: (a - b) ** 2, [_t(input), _t(label)])


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via the standard forward algorithm in log space (reference:
    warpctc binding, python/paddle/nn/functional/loss.py ctc_loss).
    log_probs: (T, N, C) logits."""
    lp, lab = _t(log_probs), _t(labels)
    il, ll = _t(input_lengths), _t(label_lengths)

    def f(logits, labels_, in_len, lab_len):
        T, N, C = logits.shape
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        S = labels_.shape[1]
        ext_len = 2 * S + 1
        # Extended label sequence: blank, l1, blank, l2, ... blank
        ext = jnp.full((N, ext_len), blank, dtype=jnp.int32)
        ext = ext.at[:, 1::2].set(labels_.astype(jnp.int32))
        neg_inf = -1e30
        alpha0 = jnp.full((N, ext_len), neg_inf)
        alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
        alpha0 = alpha0.at[:, 1].set(
            jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0])

        allow_skip = jnp.concatenate([
            jnp.zeros((N, 2), bool),
            ext[:, 2:] != ext[:, :-2]], axis=1) & (jnp.arange(ext_len)[None, :] % 2 == 1)

        def step(alpha, t):
            shifted1 = jnp.concatenate(
                [jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
            shifted2 = jnp.concatenate(
                [jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
            shifted2 = jnp.where(allow_skip, shifted2, neg_inf)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, shifted1), shifted2)
            emit = jnp.take_along_axis(logp[t], ext, axis=1)
            new_alpha = merged + emit
            new_alpha = jnp.where(t < in_len[:, None], new_alpha, alpha)
            return new_alpha, None

        alpha, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
        last = 2 * lab_len.astype(jnp.int32)
        a_last = jnp.take_along_axis(alpha, last[:, None], axis=1)[:, 0]
        a_prev = jnp.take_along_axis(
            alpha, jnp.maximum(last - 1, 0)[:, None], axis=1)[:, 0]
        ll_total = jnp.logaddexp(a_last, a_prev)
        loss = -ll_total
        if norm_by_times:
            loss = loss / in_len.astype(loss.dtype)
        if reduction == "mean":
            # Reference semantics (loss.py:1977): mean(loss / label_lengths).
            return jnp.mean(loss / jnp.maximum(lab_len, 1).astype(loss.dtype))
        return _reduce(loss, reduction)
    return dispatch.call("ctc_loss", f, [lp, lab, il, ll],
                         differentiable_mask=[True, False, False, False])


__all__ = [
    "cross_entropy", "softmax_with_cross_entropy", "nll_loss", "mse_loss",
    "l1_loss", "smooth_l1_loss", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "kl_div", "margin_ranking_loss",
    "hinge_embedding_loss", "cosine_embedding_loss", "triplet_margin_loss",
    "sigmoid_focal_loss", "square_error_cost", "ctc_loss",
]


def log_loss(input, label, epsilon=1e-4, name=None):
    """Negative log likelihood for probabilities (reference ops.yaml
    log_loss)."""
    i, l = _t(input), _t(label)
    def f(p, y):
        return -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(
            1 - p + epsilon)
    return dispatch.call("log_loss", f, [i, l])


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    """True Huber loss (reference ops.yaml huber_loss):
    0.5*d^2 for |d|<delta else delta*(|d| - 0.5*delta). Note this is NOT
    smooth_l1 (which divides by delta)."""
    i, l = _t(input), _t(label)

    def f(a, b):
        d = a - b
        ad = jnp.abs(d)
        out = jnp.where(ad < delta, 0.5 * d * d,
                        delta * (ad - 0.5 * delta))
        if reduction == "mean":
            return jnp.mean(out)
        if reduction == "sum":
            return jnp.sum(out)
        return out
    return dispatch.call("huber_loss", f, [i, l])

__all__ += ['log_loss', 'huber_loss']


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss over a complete binary class tree.

    Default tree (no path_table): leaf for class c is node ``c + K - 1`` in a
    heap-indexed complete binary tree with K-1 internal nodes; walking to the
    root emits one sigmoid decision per internal node. With
    path_table/path_code the custom tree is used (reference
    python/paddle/nn/functional/loss.py hsigmoid_loss,
    phi/kernels/cpu/hsigmoid_loss_kernel.cc).
    """
    inp, lab = _t(input), _t(label)
    w = _t(weight)
    tensors = [inp, w, lab]
    diff_mask = [True, True, False]
    has_bias = bias is not None
    if has_bias:
        tensors.append(_t(bias))
        diff_mask.append(True)
    has_table = path_table is not None
    if has_table:
        tensors += [_t(path_table), _t(path_code)]
        diff_mask += [False, False]
    K = num_classes
    depth = max(int(np.ceil(np.log2(max(K, 2)))), 1)  # static: K is python

    def f(x, wt, lab_, *rest):
        bv = rest[0] if has_bias else None
        if has_table:
            nodes = rest[-2].astype(jnp.int64)
            codes = rest[-1].astype(jnp.float32)
            valid = (nodes >= 0).astype(jnp.float32)
            nodes = jnp.maximum(nodes, 0)
        else:
            # default complete binary tree: walk leaf -> root; the tree
            # depth is static so the walk unrolls to `depth` vectorized
            # steps — labels stay on device (the seed built these tables
            # with a host loop over label values, graph-breaking capture)
            i = lab_.reshape(-1).astype(jnp.int64) + (K - 1)
            nd, cd, vd = [], [], []
            for _ in range(depth):
                parent = (i - 1) // 2
                live = i > 0
                nd.append(jnp.where(live, parent, 0))
                cd.append(jnp.where(live & (i == 2 * parent + 1), 1.0, 0.0))
                vd.append(live.astype(jnp.float32))
                i = jnp.where(live, parent, 0)
            nodes = jnp.stack(nd, axis=1)
            codes = jnp.stack(cd, axis=1)
            valid = jnp.stack(vd, axis=1)
        wsel = wt[nodes]                      # (B, D, F)
        logits = jnp.einsum("bdf,bf->bd", wsel, x)
        if bv is not None:
            logits = logits + bv.reshape(-1)[nodes]
        # BCE with logits against the path code, masked by path validity
        per = (jnp.maximum(logits, 0) - logits * codes
               + jnp.log1p(jnp.exp(-jnp.abs(logits)))) * valid
        return per.sum(axis=1, keepdims=True)

    return dispatch.call("hsigmoid_loss", f, tensors,
                         differentiable_mask=diff_mask)


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None, name=None):
    """Levenshtein distance per batch row (reference
    python/paddle/nn/functional/loss.py edit_distance,
    phi/kernels/impl/edit_distance_kernel_impl.h). In-graph DP: the classic
    serial recurrence dp[c] = min(e[c], dp[c-1]+1) unrolls to
    dp[c] = c + min_{k<=c}(e[k]-k), a prefix-min (lax.cummin) — so each DP
    row is one vectorized step and the whole metric is a vmapped fori_loop
    XLA compiles into the caller's program (the seed version pulled the
    operands to the host and graph-broke to_static capture; tpulint TPU1xx).

    Returns (distance (B,1) float, sequence_num (1,) int).
    """
    it, lt = _t(input), _t(label)
    m_pad, n_pad = int(it.shape[1]), int(lt.shape[1])
    ign = tuple(sorted(set(ignored_tokens or ())))
    tensors = [it, lt]
    has_il, has_ll = input_length is not None, label_length is not None
    if has_il:
        tensors.append(_t(input_length))
    if has_ll:
        tensors.append(_t(label_length))

    def f(a, b, *rest):
        il = rest[0].reshape(-1) if has_il else jnp.full(
            (a.shape[0],), m_pad, jnp.int32)
        ll = rest[-1].reshape(-1) if has_ll else jnp.full(
            (b.shape[0],), n_pad, jnp.int32)

        def compact(seq, length, width):
            # drop ignored tokens in-graph: stable-sort valid entries to
            # the front, padding the tail with -1 (matches no real token)
            keep = jnp.arange(width)[None, :] < length[:, None].astype(
                jnp.int32)
            for tok in ign:
                keep &= seq != tok
            order = jnp.argsort(~keep, axis=1, stable=True)
            packed = jnp.where(jnp.take_along_axis(keep, order, axis=1),
                               jnp.take_along_axis(seq, order, axis=1), -1)
            return packed, keep.sum(axis=1)

        s1, m_eff = compact(a, il, m_pad)
        s2, n_eff = compact(b, ll, n_pad)

        def row_distance(x, y, m, n):
            cols = jnp.arange(n_pad + 1, dtype=jnp.int32)

            def step(r, carry):
                prev, best = carry
                cost = (x[r - 1] != y).astype(jnp.int32)
                # e[c] = min(delete, substitute); insert handled below
                e = jnp.minimum(prev[1:] + 1, prev[:-1] + cost)
                g = jnp.concatenate([jnp.full((1,), r, jnp.int32), e])
                dp = jax.lax.cummin(g - cols) + cols
                best = jnp.where(r == m, dp[n], best)
                return dp, best

            dp0 = cols
            best0 = jnp.where(m == 0, dp0[n], 0)
            _, best = jax.lax.fori_loop(1, m_pad + 1, step, (dp0, best0))
            return best

        dist = jax.vmap(row_distance)(s1, s2, m_eff, n_eff).astype(
            jnp.float32)
        if normalized:
            dist = dist / jnp.maximum(n_eff, 1).astype(jnp.float32)
        return dist.reshape(-1, 1), jnp.full((1,), a.shape[0], jnp.int32)

    return dispatch.call("edit_distance", f, tensors, multi_output=True,
                         differentiable_mask=[False] * len(tensors),
                         export_attrs={"normalized": normalized,
                                       "ignored_tokens": ign})


def ctc_align(input, input_length=None, blank=0, padding_value=0, name=None):
    """CTC greedy alignment: merge repeats then drop blanks
    (reference ctc_align op, phi/kernels/cpu/ctc_align_kernel.cc).
    input: (B, T) argmax token ids.

    Deliberately host-side: the output WIDTH is data-dependent (longest
    de-blanked row), which XLA's static shapes cannot express — a decode
    utility, never on the training path."""
    a = np.asarray(_t(input)._data)  # tpulint: disable=TPU104 — dynamic output shape forces host decode
    il = (np.asarray(_t(input_length)._data).ravel()  # tpulint: disable=TPU104 — same host decode path
          if input_length is not None else
          np.full(a.shape[0], a.shape[1], np.int64))
    rows, lens = [], []
    for i in range(a.shape[0]):
        seq = a[i, :il[i]]
        prev = None
        out = []
        for tkn in seq.tolist():  # tpulint: disable=TPU102 — host decode, see docstring
            if tkn != prev and tkn != blank:
                out.append(tkn)
            prev = tkn
        rows.append(out)
        lens.append(len(out))
    width = max(max(lens, default=0), 1)
    res = np.full((a.shape[0], width), padding_value, dtype=a.dtype)
    for i, r in enumerate(rows):
        res[i, :len(r)] = r
    return (Tensor(jnp.asarray(res)),
            Tensor(jnp.asarray(lens, dtype=jnp.int32)))


def rnnt_loss(logits, labels, logit_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    """RNN-Transducer loss (reference warprnnt op,
    phi/kernels/impl/warprnnt_kernel_impl.h; paddle.nn.functional.rnnt_loss).

    logits: (B, T, U+1, V) unnormalized; labels: (B, U) int. TPU-native: the
    alpha recursion runs as U+1 vectorized row updates (each a lax-style
    cumulative band update over T), fully differentiable by jax.vjp — no
    hand-written backward, no warp-rnnt CUDA.
    """
    lg, lb = _t(logits), _t(labels)
    tlt, ult = _t(logit_lengths), _t(label_lengths)

    def f_all(lp, lab_in, tl_in, ul_in):
        B, T, U1, V = lp.shape
        tl = tl_in.reshape(-1)
        ul = ul_in.reshape(-1)
        logp = jax.nn.log_softmax(lp, axis=-1)
        blank_lp = logp[..., blank]
        lab = lab_in.astype(jnp.int64)
        emit_lp = jnp.take_along_axis(
            logp[:, :, :U1 - 1, :], lab[:, None, :, None], axis=-1)[..., 0]
        if fastemit_lambda:
            # FastEmit (arXiv:2010.11148): scale the gradient through emit
            # terms by (1 + lambda) without changing the loss value — the
            # identity x + l*(x - stop_grad(x)) adds 0 forward, scales vjp
            emit_lp = emit_lp + fastemit_lambda * (
                emit_lp - jax.lax.stop_gradient(emit_lp))
        NEG = -1e30
        tmask = jnp.arange(T)[None, :] < tl[:, None]
        alpha0 = jnp.concatenate(
            [jnp.zeros((B, 1)), jnp.cumsum(blank_lp[:, :-1, 0], axis=1)],
            axis=1)
        alpha0 = jnp.where(tmask, alpha0, NEG)
        rows = [alpha0]
        for u in range(1, U1):
            start = rows[-1] + emit_lp[:, :, u - 1]
            bl_u = blank_lp[:, :, u]

            def t_step(carry, t, start=start, bl_u=bl_u):
                cur = jnp.logaddexp(start[:, t], carry + bl_u[:, t - 1])
                return cur, cur

            first = start[:, 0]
            _, rest = jax.lax.scan(t_step, first, jnp.arange(1, T))
            au = jnp.concatenate([first[:, None], rest.T], axis=1)
            au = jnp.where(tmask, au, NEG)
            rows.append(au)
        A = jnp.stack(rows, axis=2)                     # (B, T, U1)
        tb = tl - 1
        ub = ul
        binx = jnp.arange(B)
        ll = A[binx, tb, ub] + blank_lp[binx, tb, ub]
        loss = -ll
        if reduction == "mean":
            return jnp.mean(loss)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    return dispatch.call("rnnt_loss", f_all, [lg, lb, tlt, ult],
                         differentiable_mask=[True, False, False, False])


__all__ += ['hsigmoid_loss', 'edit_distance', 'ctc_align', 'rnnt_loss']


#: rows a chunk of ``fused_linear_cross_entropy``: the most of the
#: ``(rows, vocabulary)`` logits that is alive at once. Head + loss alone,
#: forward + backward on a v5e (``tools/head_loss_bench.py``, PR 42), at
#: 2048 / 4096 / 8192 rows a chunk: 17.45 / 18.61 / 16.91 ms for 8192 rows x
#: 1024 x 50 257 (8192 is one chunk, 0.82 GB of temporaries against 0.55 and
#: 0.24) and 22.87 / 23.18 / 24.64 ms for 16 384 rows x 2048 x 16 384: no
#: chunk is the faster one at both, so the default is no function of the
#: vocabulary
HEAD_LOSS_CHUNK_ROWS = 4096

_head_loss_logged = set()


def head_loss_plan(rows: int, chunk_rows: int, vocab: int, hidden: int,
                   dtype, by_rule: bool) -> dict:
    """What ``fused_linear_cross_entropy`` executes for ``rows`` rows (a
    device's, where the call is partitioned), fixed when it is traced:
    ``chunk`` rows a pass of the scan, ``chunks`` passes, and ``products``,
    how often a differentiated call multiplies by the vocabulary: 3 under
    the hand-written rule (logits, the rows' gradient, the table's), 4 where
    the chunk is rematerialised (``reduction="none"``)."""
    chunk = min(chunk_rows, rows)
    return {"rows": rows, "chunk": chunk, "chunks": -(-rows // chunk),
            "vocab": vocab, "hidden": hidden,
            "products": 3 if by_rule else 4, "dtype": str(jnp.dtype(dtype))}


def _stamp_head_loss_plan(plan):
    """The plan on the ``compile.trace`` entry of the program being traced
    (the start-up record; outside a trace, nothing), and once a signature on
    the logger ``paddle_tpu.head_loss`` at ``FLAGS_log_level`` 1."""
    from ...core import flags
    from ...observability import trace as _trace
    _trace.compile_note("head_loss_plan", plan)
    signature = tuple(plan.items())
    if flags.get_flag("log_level") >= 1 and signature not in _head_loss_logged:
        _head_loss_logged.add(signature)
        logging.getLogger("paddle_tpu.head_loss").info(
            "head_loss_plan: %s", plan)


def _chunked(xa, lab, chunk, ignore_index):
    """``(rows, H)``, ``(rows,)`` -> ``(chunks, chunk, H)``, ``(chunks,
    chunk)``; rows that complete the last chunk carry ``ignore_index``."""
    n, h = xa.shape
    pad = (-n) % chunk
    if pad:
        xa = jnp.concatenate([xa, jnp.zeros((pad, h), xa.dtype)], axis=0)
        lab = jnp.concatenate(
            [lab, jnp.full((pad,), ignore_index, lab.dtype)], axis=0)
    return xa.reshape(-1, chunk, h), lab.reshape(-1, chunk)


def _chunk_nll(x_c, l_c, wa, ba, transpose_y, ignore_index):
    """One chunk: its logits as the unfused matmul gives them, each row's
    loss (0 where the label is ``ignore_index``) with the statistics in
    float32, and what the gradient needs of them."""
    logits = (x_c @ wa.T) if transpose_y else (x_c @ wa)
    if ba is not None:
        logits = logits + ba
    wide = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(wide, axis=-1))
    lse = jnp.log(jnp.sum(jnp.exp(wide - m[:, None]), axis=-1)) + m
    l_i = l_c.astype(jnp.int32)
    valid = l_i != ignore_index
    safe = jnp.where(valid, l_i, 0)
    picked = jnp.squeeze(jnp.take_along_axis(
        logits, safe[:, None], axis=-1), -1).astype(jnp.float32)
    return jnp.where(valid, lse - picked, 0.0), valid, logits, lse, safe


def _head_loss_rows(xa, wa, ba, lab, transpose_y, ignore_index, chunk):
    """``reduction="none"``: the rows' losses, each chunk's logits
    rematerialised in the backward (``jax.checkpoint``)."""
    @jax.checkpoint
    def rows_nll(x_c, l_c):
        return _chunk_nll(x_c, l_c, wa, ba, transpose_y, ignore_index)[0]

    _, per_row = jax.lax.scan(
        lambda carry, xl: (carry, rows_nll(*xl)), None,
        _chunked(xa, lab, chunk, ignore_index))
    return per_row.reshape(-1)[:xa.shape[0]]


def _head_loss_sums(xa, wa, ba, lab, transpose_y, ignore_index, chunk):
    """``(sum of the rows' losses, rows counted)`` of ``x (rows, H)`` chunk
    by chunk, with a hand-written VJP: differentiated, the forward's scan
    takes each chunk's gradient while its logits are at hand, so a step
    multiplies by the vocabulary three times and keeps arrays of ``x``'s and
    the table's shape, never of (rows, vocabulary)."""
    n, h = xa.shape

    def zeros():        # inside each rule: a rule closes over no traced value
        return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)

    def loss_only(xa, wa, ba, lab):
        def body(sums, xl):
            nll, valid, *_ = _chunk_nll(*xl, wa, ba, transpose_y,
                                        ignore_index)
            return (sums[0] + jnp.sum(nll),
                    sums[1] + jnp.sum(valid, dtype=jnp.int32)), None

        return jax.lax.scan(body, zeros(),
                            _chunked(xa, lab, chunk, ignore_index))[0]

    def with_gradients(xa, wa, ba, lab):
        classes = jnp.arange(wa.shape[0 if transpose_y else 1],
                             dtype=jnp.int32)
        over_rows = (((0,), (0,)), ((), ()))    # contract the chunk's rows

        def body(carry, xl):
            total, count, dw, db = carry
            nll, valid, logits, lse, safe = _chunk_nll(
                *xl, wa, ba, transpose_y, ignore_index)
            # d(sum of losses) / d(logits): softmax - onehot on the counted
            # rows, rounded to the logits' dtype as the unfused path's cast
            # rounds its cotangent
            d = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
            d = jnp.where(valid[:, None],
                          d - (classes == safe[:, None]), 0.0)
            d = d.astype(logits.dtype)
            dx_c = (d @ wa) if transpose_y else (d @ wa.T)
            dw = dw + jax.lax.dot_general(
                *((d, xl[0]) if transpose_y else (xl[0], d)), over_rows,
                preferred_element_type=jnp.float32)
            if ba is not None:
                db = db + jnp.sum(d, axis=0, dtype=jnp.float32)
            return (total + jnp.sum(nll),
                    count + jnp.sum(valid, dtype=jnp.int32), dw,
                    db), dx_c.astype(xa.dtype)

        (total, count, dw, db), dx = jax.lax.scan(
            body, zeros() + (jnp.zeros(wa.shape, jnp.float32),
                             None if ba is None
                             else jnp.zeros(ba.shape, jnp.float32)),
            _chunked(xa, lab, chunk, ignore_index))
        return (total, count), (dx.reshape(-1, h)[:n], dw, db)

    def scaled(kept, cts):
        dx, dw, db = kept
        g = cts[0]                  # the count's cotangent is a float0
        return ((g * dx).astype(xa.dtype), (g * dw).astype(wa.dtype),
                None if db is None else (g * db).astype(ba.dtype), None)

    sums = jax.custom_vjp(loss_only)
    sums.defvjp(with_gradients, scaled)
    return sums(xa, wa, ba, lab)


@functools.lru_cache(maxsize=64)
def _head_loss_program(transpose_y, ignore_index, reduction, chunk_rows,
                       mesh, axes):
    """``(x, table, labels[, bias]) -> rows' losses`` ("none") or ``(sums,
    counts)``, one entry a data shard: ``local`` over a device's rows,
    inside a ``shard_map`` over ``axes`` of ``mesh`` where there are any
    (jitted there, so that an eager call runs one program too)."""

    def local(xa, wa, lab, *b):
        lead = xa.shape[:-1]
        x2, l1 = xa.reshape(-1, xa.shape[-1]), lab.reshape(-1)
        args = (x2, wa, b[0] if b else None, l1, transpose_y, ignore_index,
                min(chunk_rows, x2.shape[0]))
        if reduction == "none":
            return _head_loss_rows(*args).reshape(lead)
        total, count = _head_loss_sums(*args)
        return total[None], count[None]

    if not axes:
        return local
    from jax.sharding import PartitionSpec as P
    from ...distributed.shard_map_compat import shard_map

    def head_loss_shards(xa, wa, lab, *b):
        return shard_map(local, mesh,
                         in_specs=(P(axes), P(), P(axes)) + (P(),) * len(b),
                         out_specs=P(axes), axis_names=axes)(xa, wa, lab, *b)

    return jax.jit(head_loss_shards)


def fused_linear_cross_entropy(x, weight, label, bias=None,
                               transpose_y=False, ignore_index=-100,
                               reduction="mean",
                               chunk_rows=HEAD_LOSS_CHUNK_ROWS):
    """Cross entropy of ``x @ weight (+ bias)`` against hard ``label``
    WITHOUT materializing the ``(rows, vocabulary)`` logits, in any dtype.

    TPU-native fusion of the LM-head matmul with the loss (the reference
    computes them as two ops, ``matmul`` then ``cross_entropy_with_softmax``,
    which sends the logits through HBM twice forward and again backward,
    in float32 for the loss). The rows run ``chunk_rows`` at a time under
    ``jax.lax.scan``; only a chunk's logits and their gradient are ever
    alive. ``reduction`` "mean" and "sum" have a hand-written VJP: a
    differentiated call takes each chunk's gradient in the forward's scan
    while its logits are at hand (``softmax - onehot`` on the counted rows,
    its product with the table: the rows' gradient; its product with the
    chunk's rows, summed in float32 over the chunks: the table's; its column
    sums: the bias's), keeps those, and the backward scales them by the
    incoming cotangent. That is THREE products with the vocabulary a step,
    the unfused path's count. A call that is not differentiated runs the
    loss alone. ``reduction="none"`` (a cotangent a row) rematerialises each
    chunk's logits in the backward instead, four products. The logits are
    what the unfused ``matmul`` gives (bf16 under O1 autocast, out of
    float32 accumulation); max, logsumexp, the picked logit and the loss
    are float32.

    ``x`` is ``(..., H)``, ``weight`` ``(H, V)`` (or ``(V, H)`` with
    ``transpose_y=True`` for embedding-tied heads), ``label`` ``x``'s
    leading shape; rows whose label is ``ignore_index`` count for nothing;
    "mean" averages over the others; "none" returns ``label``'s shape.
    Under a mesh whose data axes (``dp``, ``sharding``) divide ``x``'s
    first dimension the scan runs inside a ``shard_map`` over them: a device
    chunks its OWN rows (the chunk axis is never the partitioned one), and
    the table's gradient is reduced over the data axes once, after the scan.

    ``chunk_rows``: 4096 rows of a 50 257-wide vocabulary are 412 MB of bf16
    logits; ``head_loss_plan`` (stamped on the traced program's
    ``compile.trace`` entry) says what a call was built with.
    """
    x, weight, label = _t(x), _t(weight), _t(label)
    inputs = [x, weight, label]
    has_b = bias is not None
    if has_b:
        inputs.append(_t(bias))

    def f(xa, wa, lab, *b):
        lead, h = xa.shape[:-1], xa.shape[-1]
        n = math.prod(lead)
        if n == 0:      # e.g. seq_len==1 -> empty shifted labels
            if reduction == "none":
                return jnp.zeros(lead, jnp.float32)
            return jnp.asarray(0.0, jnp.float32)
        from ...distributed import mesh as mesh_mod
        mesh, axes = mesh_mod.data_axes_dividing(lead[0])
        shards = math.prod(mesh.shape[a] for a in axes) if axes else 1
        _stamp_head_loss_plan(head_loss_plan(
            n // shards, chunk_rows, wa.shape[0 if transpose_y else 1], h,
            xa.dtype, reduction != "none"))
        out = _head_loss_program(
            transpose_y, ignore_index, reduction, chunk_rows, mesh, axes)(
                xa, wa, lab, *b)
        if reduction == "none":
            return out
        total, count = jnp.sum(out[0]), jnp.sum(out[1])
        if reduction == "sum":
            return total
        return total / jnp.maximum(count, 1)

    return dispatch.call("fused_linear_cross_entropy", f, inputs,
                         differentiable_mask=[True, True, False]
                         + [True] * has_b)


__all__ += ['fused_linear_cross_entropy']
