"""Olmo-Hybrid decoder (``model_type`` ``olmo_hybrid``; Ai2's Olmo-Hybrid-7B
is the published instance).

A POST-norm residual block a layer, the norm on each sublayer's OUTPUT as
Olmo 2 / Olmo 3 place it: ``h = x + RMSNorm(Mixer_l(x))``, ``y = h +
RMSNorm(MLP(h))``, ``MLP`` a SwiGLU of ``intermediate_size``, no bias; after
the last block a final RMSNorm and an untied head. ``layer_types[l]`` says
which mixer (published: ``L L L F`` x 8):

* ``linear_attention``: gated delta-rule linear attention (Yang, Kautz and
  Hatamizadeh, arXiv:2412.06464; ``nn.functional.delta_rule``).
  ``linear_num_value_heads`` heads of key width ``linear_key_head_dim`` and
  value width ``linear_value_head_dim``. ``q' , k', v = SiLU(conv(W_q x |
  W_k x | W_v x))``, ``conv`` a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps, no bias; a head: ``q = q' / |q'| /
  sqrt(d_k)``, ``k = k' / |k'|``; ``beta = 2 sigmoid(W_b x)`` (the 2 is
  ``linear_allow_neg_eigval``; without it ``sigmoid`` alone), ``alpha =
  exp(-exp(A_log) softplus(W_a x + dt_bias))``; the state ``S`` (d_k x
  d_v, float32) ``S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t
  S_{t-1}^T k_t))^T``, ``o_t = S_t^T q_t``; ``y = W_o concat_h(RMSNorm(o_h) w
  * SiLU((W_g x)_h))``: the norm first, then the gate.
* ``full_attention``: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over the
  WHOLE projection (Olmo 2 / 3's q/k norm, not a head's), causal attention
  of ``num_attention_heads`` query and ``num_key_value_heads`` K/V heads,
  scale ``head_dim ** -0.5``, NO positional encoding
  (``rope_parameters.rope_theta`` null: the recurrent layers carry order).

What this file refuses by name: a ``rope_theta`` that is a number (a rotary
variant of the full layers is not built), ``attention_bias``, tied
embeddings.

``forward(ids)`` runs a whole sequence (tests); ``paged_adapter()`` is what
``inference.PagedEngine`` serves the model through: a full layer pages its
K/V, a linear layer keeps per slot its convolution window and its state,
whatever the context. The state is kept PACKED, ``(H / p, d_k, p * d_v)``
(``nn.functional.delta_rule``): whole lane tiles, so the decode step moves
the bytes the state has and no padding.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn, ops
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.functional import delta_rule as _rule
from ..nn.initializer import Assign, Constant, Normal, Uniform
from ..nn.parameter import ParamAttr

__all__ = ["OlmoHybridConfig", "OlmoHybridForCausalLM", "OlmoHybridModel",
           "GatedDeltaNet", "olmo_hybrid_tiny"]

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass
class OlmoHybridConfig:
    """The published keys under their published names."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    #: per layer ``linear_attention`` or ``full_attention`` (None: the
    #: published ``L L L F`` pattern over ``num_hidden_layers``)
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": None})
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    #: tokens a sub-chunk of the chunked rule holds
    chunk_size: int = 64
    max_seq_len: int = 4096

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(FULL if (i + 1) % 4 == 0 else LINEAR
                                     for i in range(n))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != n:
            raise ValueError("layer_types names one kind a layer of "
                             "num_hidden_layers")
        bad = set(self.layer_types) - {LINEAR, FULL}
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if (self.rope_parameters or {}).get("rope_theta") is not None:
            raise ValueError(
                "rope_parameters.rope_theta is a number: a rotary variant of "
                "the full-attention layers is not built (the published "
                "config has null: no positional encoding)")
        if self.attention_bias:
            raise ValueError("attention_bias is not built")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings is not built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("head_dim is hidden_size / num_attention_heads")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("linear_num_key_heads != linear_num_value_heads "
                             "(grouped keys) is not built")

    # what the engine and the rest of the zoo call these
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def heads_packed(self) -> int:
        """Heads side by side in a row of the packed state."""
        return _rule.heads_packed(self.linear_num_value_heads,
                                  self.linear_value_head_dim)


def olmo_hybrid_tiny(**kw) -> OlmoHybridConfig:
    """Two periods ``L L L F`` as the benchmark's cut has them; 6 heads (no
    multiple of a sublane tile, as the published 30 are none), a state of
    8 x 64 a head packed two to a row."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 96)
    kw.setdefault("intermediate_size", 160)
    kw.setdefault("num_hidden_layers", 8)
    kw.setdefault("num_attention_heads", 6)
    kw.setdefault("num_key_value_heads", 6)
    kw.setdefault("linear_num_key_heads", 6)
    kw.setdefault("linear_num_value_heads", 6)
    kw.setdefault("linear_key_head_dim", 8)
    kw.setdefault("linear_value_head_dim", 64)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("max_seq_len", 128)
    return OlmoHybridConfig(**kw)


def _linear(in_f, out_f, std):
    return nn.Linear(in_f, out_f, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def _time_constants(cfg: OlmoHybridConfig):
    """``A`` uniform in [1, 16] and ``dt`` log-uniform in [0.001, 0.1]
    stored through the inverse of softplus (the gated-delta-net reference
    initialisation, Mamba-2's)."""
    h = cfg.linear_num_value_heads
    rng = np.random.default_rng(h)
    a_log = np.log(rng.uniform(1.0, 16.0, h))
    dt = np.exp(rng.uniform(math.log(0.001), math.log(0.1), h))
    return (a_log.astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


class GatedDeltaNet(nn.Layer):
    """The linear-attention mixer. ``project`` (row-wise), ``scan`` (along
    each sequence, from and to a carried ``{"conv", "s"}``), ``finish``
    (row-wise): a caller whose rows are not all one batch of sequences
    (serving, a chunk with the decode batch aboard) runs the three itself,
    ``scan`` once a group of rows."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        hid, std = cfg.hidden_size, cfg.initializer_range
        h = cfg.linear_num_value_heads
        self.q_proj = _linear(hid, cfg.key_dim, std)
        self.k_proj = _linear(hid, cfg.key_dim, std)
        self.v_proj = _linear(hid, cfg.value_dim, std)
        self.a_proj = _linear(hid, h, std)
        self.b_proj = _linear(hid, h, std)
        self.g_proj = _linear(hid, cfg.value_dim, std)
        self.o_proj = _linear(cfg.value_dim, hid, std)
        # one depthwise convolution over [q | k | v]'s columns: PyTorch's
        # Conv1d default at fan-in K, no bias
        bound = 1.0 / math.sqrt(cfg.linear_conv_kernel_dim)
        self.conv_weight = self.create_parameter(
            [cfg.conv_dim, cfg.linear_conv_kernel_dim],
            attr=ParamAttr(initializer=Uniform(-bound, bound)))
        a_log, dt_bias = _time_constants(cfg)
        self.A_log = self.create_parameter(
            [h], dtype="float32", default_initializer=Assign(a_log))
        self.dt_bias = self.create_parameter(
            [h], dtype="float32", default_initializer=Assign(dt_bias))
        self.o_norm_weight = self.create_parameter(
            [cfg.linear_value_head_dim], dtype="float32",
            default_initializer=Constant(1.0))

    def zero_state(self, bsz: int, dtype):
        cfg = self.cfg
        p = cfg.heads_packed
        return {"conv": jnp.zeros((bsz, cfg.linear_conv_kernel_dim - 1,
                                   cfg.conv_dim), dtype),
                "s": jnp.zeros((bsz, cfg.linear_num_value_heads // p,
                                cfg.linear_key_head_dim,
                                p * cfg.linear_value_head_dim), jnp.float32)}

    def forward(self, x, state=None, valid=None):
        """``x`` (B, T, hidden); ``state`` ``{"conv", "s"}`` arrays to
        continue from (zeros when omitted). Returns the mixer's output, and
        the new state when one was given."""
        qkv, alpha_log, beta, gate = self.project(x)
        given = state is not None
        if not given:
            state = self.zero_state(x.shape[0], qkv._data.dtype)
        o, new = self.scan(state, qkv._data, alpha_log._data, beta._data,
                           None if valid is None else valid._data)
        out = self.finish(Tensor(o), gate)
        return (out, new) if given else out

    def project(self, x):
        """``[q' | k' | v]`` before the convolution, the log of ``alpha``
        and ``beta`` (float32, (B, T, H)), and the gate, of every row."""
        cfg = self.cfg
        with jax.named_scope("attn.linear.proj"):
            qkv = ops.concat([self.q_proj(x), self.k_proj(x),
                              self.v_proj(x)], axis=-1)
            dt = F.softplus(self.a_proj(x).astype("float32") + self.dt_bias)
            alpha_log = -ops.exp(self.A_log) * dt
            beta = F.sigmoid(self.b_proj(x).astype("float32"))
            if cfg.linear_allow_neg_eigval:
                beta = beta * 2.0
            return qkv, alpha_log, beta, self.g_proj(x)

    def scan(self, state, qkv, alpha_log, beta, valid=None, fresh=None,
             idle=None):
        """Arrays in, arrays out: convolution and rule along the sequences
        of ``qkv`` (B, T, conv_dim) from ``state``; ``o`` (B, T, H, d_v)
        float32 and the new state. ``fresh`` / ``idle`` (B,) bool are the
        serving cache's flags (``recur(..., masks=True)``): a fresh lane
        starts from zeros, an idle one gets its state back as it was."""
        cfg = self.cfg
        bsz, t = qkv.shape[0], qkv.shape[1]
        h, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        p = cfg.heads_packed

        def lanes(flag, like):
            return flag.reshape((-1,) + (1,) * (like.ndim - 1))

        if fresh is not None and idle is not None:
            # a sentinel lane (start -1) reads as both: it is kept, not zeroed
            fresh = fresh & ~idle
        with jax.named_scope("attn.linear.conv"):
            window = state["conv"]
            if fresh is not None:
                window = jnp.where(lanes(fresh, window), 0, window)
            if valid is not None:
                qkv = qkv * valid[..., None].astype(qkv.dtype)
            qkv, new_window = _rule.conv_arrays(
                qkv, self.conv_weight._data, window)
            if idle is not None:
                new_window = jnp.where(lanes(idle, window), state["conv"],
                                       new_window)
        with jax.named_scope("attn.linear.rule"):
            f32 = jnp.float32
            q = qkv[..., :cfg.key_dim].astype(f32).reshape(bsz, t, h, dk)
            k = qkv[..., cfg.key_dim:2 * cfg.key_dim].astype(f32).reshape(
                bsz, t, h, dk)
            v = qkv[..., 2 * cfg.key_dim:].reshape(bsz, t, h, dv)
            q = q * jax.lax.rsqrt(
                jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True)
                                  + 1e-6)
            if t == 1:      # decode: the recurrence itself, one visit
                o, s = _rule.step_any(
                    q[:, 0], k[:, 0], v[:, 0], jnp.exp(alpha_log[:, 0]),
                    beta[:, 0], state["s"], fresh, idle, packed=p)
                o = o[:, None]
            else:
                s0 = _rule.unpack_state(state["s"], p)
                if fresh is not None:
                    s0 = jnp.where(lanes(fresh, s0), 0.0, s0)
                o, s = _rule.chunk_arrays(q, k, v, alpha_log, beta, s0,
                                          valid, cfg.chunk_size)
                # an idle lane has no valid row, and rows that are not
                # valid leave the state as it was: no ``where`` over it
                s = _rule.pack_state(s, p)
        return o, {"conv": new_window.astype(state["conv"].dtype), "s": s}

    def finish(self, o, gate):
        """Norm, gate and ``W_o`` of every row: ``o`` (B, T, H, d_v)."""
        cfg = self.cfg
        bsz, t = o.shape[0], o.shape[1]
        h, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        with jax.named_scope("attn.linear.norm"):
            y = F.gated_rms_norm(o, ops.reshape(gate, [bsz, t, h, dv]),
                                 self.o_norm_weight,
                                 epsilon=cfg.rms_norm_eps)
        with jax.named_scope("attn.linear.proj"):
            return self.o_proj(ops.reshape(y, [bsz, t, h * dv]))


class OlmoHybridAttention(nn.Layer):
    """Causal attention, softmax scale ``head_dim ** -0.5``, no bias, q and k
    RMS-normed over the whole projection, no positional encoding."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        std, hd = cfg.initializer_range, cfg.head_dim
        nq, nkv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
        self.q_proj = _linear(cfg.hidden_size, nq, std)
        self.k_proj = _linear(cfg.hidden_size, nkv, std)
        self.v_proj = _linear(cfg.hidden_size, nkv, std)
        self.o_proj = _linear(nq, cfg.hidden_size, std)
        self.q_norm = nn.RMSNorm(nq, epsilon=cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(nkv, epsilon=cfg.rms_norm_eps)

    def qkv(self, x):
        cfg = self.cfg
        b, t = x.shape[0], x.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        return (ops.reshape(self.q_norm(self.q_proj(x)), [b, t, nh, hd]),
                ops.reshape(self.k_norm(self.k_proj(x)), [b, t, nkv, hd]),
                ops.reshape(self.v_proj(x), [b, t, nkv, hd]))

    def forward(self, x):
        cfg = self.cfg
        b, t = x.shape[0], x.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        q, k, v = self.qkv(x)
        rep = nh // nkv
        if rep > 1:
            k = ops.reshape(ops.tile(ops.unsqueeze(k, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
            v = ops.reshape(ops.tile(ops.unsqueeze(v, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(ops.reshape(out, [b, t, nh * hd]))


class OlmoHybridMLP(nn.Layer):
    """``down(silu(gate u) * up u)``."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        std = cfg.initializer_range
        self.gate_proj = _linear(cfg.hidden_size, cfg.intermediate_size, std)
        self.up_proj = _linear(cfg.hidden_size, cfg.intermediate_size, std)
        self.down_proj = _linear(cfg.intermediate_size, cfg.hidden_size, std)

    def forward(self, u):
        return self.down_proj(F.swiglu(self.gate_proj(u), self.up_proj(u)))


_SCOPES = {LINEAR: "attn.linear", FULL: "attn.full"}

#: K/V heads a page holds are padded to a multiple of this (a bfloat16
#: sublane tile, two float32 ones)
_HEAD_TILE = 16


class OlmoHybridBlock(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.mixer = (GatedDeltaNet(cfg) if kind == LINEAR
                      else OlmoHybridAttention(cfg))
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = OlmoHybridMLP(cfg)
        self.post_feedforward_layernorm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    @property
    def scope(self) -> str:
        return _SCOPES[self.kind]

    def feed_forward(self, x):
        with jax.named_scope("mlp"):
            return x + self.post_feedforward_layernorm(self.mlp(x))

    def forward(self, x):
        with jax.named_scope(self.scope):
            x = x + self.post_attention_layernorm(self.mixer(x))
        return self.feed_forward(x)


class OlmoHybridModel(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList(
            [OlmoHybridBlock(cfg, kind) for kind in cfg.layer_types])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class OlmoHybridForCausalLM(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = OlmoHybridModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                               cfg.initializer_range)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def paged_adapter(self):
        """What ``inference.PagedEngine`` serves this model through."""
        return _OlmoHybridPaged(self)


class _OlmoHybridPaged:
    """``PagedEngine`` adapter: per layer the cache state the engine
    carries, and the per-chunk forward over that state."""

    def __init__(self, model: OlmoHybridForCausalLM):
        self.model = model
        self.cfg = cfg = model.cfg
        # the pages hold whole sublane tiles of K/V heads: 30 heads of
        # bfloat16 are laid out in 32 on the chip whatever the shape says,
        # and a pool declared so is one the decode kernel reads in place
        # (``ops.pallas.paged_attention.supports``); the two pad heads are
        # zeros, q's too, and their output rows are dropped
        nkv = cfg.num_key_value_heads
        self.pad_heads = (-nkv % _HEAD_TILE
                          if nkv == cfg.num_attention_heads else 0)
        self.num_kv_heads = nkv + self.pad_heads
        self.head_dim = cfg.head_dim

    def cache_layout(self, dtype):
        """``("paged_kv",)`` for a full layer; for a linear layer
        ``("slot_state", ...)``: the convolution's window in the model's
        dtype and the packed float32 state."""
        cfg = self.cfg
        p = cfg.heads_packed
        linear = ("slot_state", {
            "conv": ((cfg.linear_conv_kernel_dim - 1, cfg.conv_dim), dtype),
            "s": ((cfg.linear_num_value_heads // p, cfg.linear_key_head_dim,
                   p * cfg.linear_value_head_dim), jnp.float32)})
        return [linear if kind == LINEAR else ("paged_kv",)
                for kind in cfg.layer_types]

    def forward_chunk(self, tokens, cache, logits_t: int = 1):
        model, cfg = self.model, self.cfg
        bsz, t = tokens.shape
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        valid = Tensor(cache.valid)
        with jax.named_scope("embed"):
            x = model.model.embed_tokens(Tensor(tokens))
        for li, blk in enumerate(model.model.layers):
            with jax.named_scope(blk.scope):
                if blk.kind == FULL:
                    q, k, v = blk.mixer.qkv(x)
                    if self.pad_heads:
                        q, k, v = (Tensor(jnp.pad(
                            a._data, ((0, 0), (0, 0), (0, self.pad_heads),
                                      (0, 0)))) for a in (q, k, v))
                    out = cache.attend(li, q, k, v)
                    out = blk.mixer.o_proj(ops.reshape(
                        Tensor(out._data[:, :, :nh]), [bsz, t, nh * hd]))
                else:
                    # the weights meet every row once; the recurrence runs
                    # along each group's own sequences, and masks its own
                    # fresh and idle lanes (one visit of the state)
                    qkv, alpha_log, beta, gate = blk.mixer.project(x)

                    def run(state, qkv, alpha_log, beta, valid, fresh, idle,
                            mixer=blk.mixer):
                        o, new = mixer.scan(
                            state, qkv._data, alpha_log._data, beta._data,
                            valid._data, fresh, idle)
                        return Tensor(o), new
                    out = blk.mixer.finish(
                        cache.recur(li, run, qkv, alpha_log, beta, valid,
                                    masks=True), gate)
                x = x + blk.post_attention_layernorm(out)
            x = blk.feed_forward(x)
        x = model.model.norm(x)
        last = cache.head_rows(x, logits_t)
        with jax.named_scope("lm_head"):
            return model.lm_head(last)
