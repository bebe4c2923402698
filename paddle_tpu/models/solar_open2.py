"""Solar Open 2 decoder (``model_type`` ``solar_open2``; Upstage's
Solar-Open2-250B is the published instance).

A pre-norm residual block a layer, ``h = x + Mixer_l(RMSNorm(x))``, ``y = h
+ MoE(RMSNorm(h))``; EVERY layer's feed-forward is the expert layer
(``first_k_dense_replace`` 0); after the last block a final RMSNorm and an
untied head. ``gqa_layers`` names the layers whose mixer is attention
(published: 0, 4, 8, ...: ``G K K K`` x 12); the others are Kimi delta
attention:

* **KDA** (Kimi Linear, arXiv:2510.26692; ``linear_attn_config``): the delta
  rule with a decay a key CHANNEL. ``q~, k~, v = SiLU(conv4(W_q x)),
  SiLU(conv4(W_k x)), SiLU(conv4(W_v x))``, three causal depthwise
  convolutions of ``short_conv_kernel_size`` taps, no bias; a head: ``q =
  q~ / |q~| / sqrt(d)``, ``k = k~ / |k~|``; ``g = -exp(A_log[h]) *
  softplus((x W_fa) W_fb + dt_bias)`` (``num_heads x head_dim`` numbers a
  token, through a rank-``head_dim`` pair: ``kda_use_full_proj`` false),
  ``alpha = exp(g)``; ``beta = 2 sigmoid(W_b x)`` (the 2 is
  ``kda_allow_neg_eigval``); the state ``S`` (d x d, float32) ``S_t = (I -
  beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
  S_t^T q_t`` (``nn.functional.delta_rule``, the per-channel forms); ``y =
  W_o concat_h(RMSNorm(o_h) w * sigmoid(((x W_ga) W_gb)_h))``: the norm
  first, then the gate.
* **gated GQA** (``use_gqa_gate``, ``use_rope`` false): ``num_attention_
  heads`` query and ``num_key_value_heads`` K/V heads of ``head_dim``,
  causal, scale ``head_dim ** -0.5``, NO positional encoding, no q/k norm;
  the attention's output times ``sigmoid(W_g x)`` elementwise before
  ``W_o``.
* the expert layer: ``nn.SwiGLUMoE``, a float32 sigmoid router over
  ``n_routed_experts``, ``num_experts_per_tok`` SwiGLU experts of
  ``moe_intermediate_size`` weighted by their renormalised scores times
  ``routed_scaling_factor``, plus ``n_shared_experts`` shared ones.
  ``intermediate_size`` builds no layer.

What this file refuses by name: ``use_rope`` true, ``kda_use_full_proj``
true, ``use_gqa_gate`` false, a linear head count other than
``num_attention_heads`` (grouped keys), tied embeddings.

``forward(ids)`` runs a whole sequence (tests); ``paged_adapter()`` is what
``inference.PagedEngine`` serves the model through: a GQA layer pages its
K/V, a KDA layer keeps per slot its three convolution windows and its
state (``heads x d x d`` float32, whole lane tiles at the published 128: no
packing), and every layer keeps an expert-load counter beside them.
``Engine.fit`` is not asked to train the KDA layer.
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

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM", "SolarOpen2Model",
           "KimiDeltaAttention", "solar_open2_tiny"]

#: the three convolutions of a KDA layer, in the order of their windows
_CONVS = ("q", "k", "v")


@dataclass
class SolarOpen2Config:
    """The published keys under their published names."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: the layers whose mixer is gated GQA (None: every fourth from 0, the
    #: published list, over ``num_hidden_layers``)
    gqa_layers: Optional[Tuple[int, ...]] = None
    use_gqa_gate: bool = True
    use_rope: bool = False
    linear_attn_config: dict = field(default_factory=lambda: {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None})
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    # feed-forward: every layer is the expert layer
    intermediate_size: int = 10240
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1280
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    #: ``(lo, hi)``: the routed experts this chip holds (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    #: tokens a sub-chunk of the chunked rule holds
    chunk_size: int = 64
    max_seq_len: int = 8192

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.gqa_layers is None:
            self.gqa_layers = tuple(range(0, n, 4))
        self.gqa_layers = tuple(int(i) for i in self.gqa_layers)
        if any(not 0 <= i < n for i in self.gqa_layers):
            raise ValueError("gqa_layers names a layer outside "
                             "num_hidden_layers")
        for key, built in (("use_rope", False), ("kda_use_full_proj", False),
                           ("use_gqa_gate", True),
                           ("tie_word_embeddings", False),
                           ("first_k_dense_replace", 0)):
            if getattr(self, key) != built:
                raise ValueError(f"{key} = {getattr(self, key)!r} is not "
                                 f"built (the published config has "
                                 f"{built!r})")
        lin = self.linear_attn_config
        if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
            raise ValueError("linear_attn_config.num_kv_heads other than "
                             "num_heads (grouped keys) is not built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(self.experts_held)

    # what the engine and the rest of the zoo call these
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def linear_heads(self) -> int:
        return self.linear_attn_config["num_heads"]

    @property
    def linear_head_dim(self) -> int:
        return self.linear_attn_config["head_dim"]

    @property
    def linear_dim(self) -> int:
        """Columns of each of q, k and v in a KDA layer."""
        return self.linear_heads * self.linear_head_dim

    @property
    def conv_taps(self) -> int:
        return self.linear_attn_config["short_conv_kernel_size"]


def solar_open2_tiny(**kw) -> SolarOpen2Config:
    """One period ``G K K K`` as the benchmark's cut has it; 4 linear heads
    of 16 x 16, 4 query heads over 2 K/V heads, 16 experts of which a token
    picks 4."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 4)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("linear_attn_config", {
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
        "num_kv_heads": None})
    kw.setdefault("n_routed_experts", 16)
    kw.setdefault("num_experts_per_tok", 4)
    kw.setdefault("moe_intermediate_size", 48)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("max_seq_len", 128)
    return SolarOpen2Config(**kw)


def _linear(in_f, out_f, std):
    return nn.Linear(in_f, out_f, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def _time_constants(cfg: SolarOpen2Config):
    """``A`` uniform in [1, 16] a HEAD and ``dt`` log-uniform in [0.001,
    0.1] a CHANNEL stored through the inverse of softplus (the Kimi Linear
    reference initialisation, Mamba-2's ranges)."""
    h, d = cfg.linear_heads, cfg.linear_dim
    rng = np.random.default_rng(h)
    a_log = np.log(rng.uniform(1.0, 16.0, h))
    dt = np.exp(rng.uniform(math.log(0.001), math.log(0.1), d))
    return (a_log.astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


class KimiDeltaAttention(nn.Layer):
    """The KDA mixer. ``project`` (row-wise), ``scan`` (along each sequence,
    from and to a carried ``{"conv_q", "conv_k", "conv_v", "s"}``),
    ``finish`` (row-wise): a caller whose rows are not all one batch of
    sequences (serving, a chunk with the decode batch aboard) runs the three
    itself, ``scan`` once a group of rows."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        hid, std = cfg.hidden_size, cfg.initializer_range
        h, d, wide = cfg.linear_heads, cfg.linear_head_dim, cfg.linear_dim
        self.q_proj = _linear(hid, wide, std)
        self.k_proj = _linear(hid, wide, std)
        self.v_proj = _linear(hid, wide, std)
        # the decay and the output gate through a rank-``head_dim`` pair
        self.f_a_proj = _linear(hid, d, std)
        self.f_b_proj = _linear(d, wide, std)
        self.g_a_proj = _linear(hid, d, std)
        self.g_b_proj = _linear(d, wide, std)
        self.b_proj = _linear(hid, h, std)
        self.o_proj = _linear(wide, hid, std)
        # three depthwise convolutions: PyTorch's Conv1d default at fan-in
        # K, no bias
        bound = 1.0 / math.sqrt(cfg.conv_taps)
        for name in _CONVS:
            setattr(self, f"{name}_conv_weight", self.create_parameter(
                [wide, cfg.conv_taps],
                attr=ParamAttr(initializer=Uniform(-bound, bound))))
        a_log, dt_bias = _time_constants(cfg)
        self.A_log = self.create_parameter(
            [h], dtype="float32", default_initializer=Assign(a_log))
        self.dt_bias = self.create_parameter(
            [wide], dtype="float32", default_initializer=Assign(dt_bias))
        self.o_norm_weight = self.create_parameter(
            [d], dtype="float32", default_initializer=Constant(1.0))

    def state_layout(self, dtype) -> dict:
        """``{name: (shape a slot, dtype)}`` of what a sequence carries."""
        cfg = self.cfg
        window = ((cfg.conv_taps - 1, cfg.linear_dim), dtype)
        return {**{f"conv_{name}": window for name in _CONVS},
                "s": ((cfg.linear_heads, cfg.linear_head_dim,
                       cfg.linear_head_dim), jnp.float32)}

    def zero_state(self, bsz: int, dtype):
        return {name: jnp.zeros((bsz,) + shape, dt)
                for name, (shape, dt) in self.state_layout(dtype).items()}

    def forward(self, x, state=None, valid=None):
        """``x`` (B, T, hidden); ``state`` arrays to continue from (zeros
        when omitted). Returns the mixer's output, and the new state when
        one was given."""
        q, k, v, alpha_log, beta, gate = self.project(x)
        given = state is not None
        if not given:
            state = self.zero_state(x.shape[0], q._data.dtype)
        o, new = self.scan(state, q._data, k._data, v._data,
                           alpha_log._data, beta._data,
                           None if valid is None else valid._data)
        out = self.finish(Tensor(o), gate)
        return (out, new) if given else out

    def project(self, x):
        """``q~``, ``k~``, ``v`` before their convolutions, the log of
        ``alpha`` (float32, (B, T, H, d)) and ``beta`` (float32, (B, T,
        H)), and the gate, of every row."""
        cfg = self.cfg
        with jax.named_scope("attn.linear.proj"):
            dt = F.softplus(
                self.f_b_proj(self.f_a_proj(x)).astype("float32")
                + self.dt_bias)
            alpha_log = -ops.unsqueeze(ops.exp(self.A_log), -1) * ops.reshape(
                dt, [x.shape[0], x.shape[1], cfg.linear_heads,
                     cfg.linear_head_dim])
            beta = F.sigmoid(self.b_proj(x).astype("float32"))
            if cfg.kda_allow_neg_eigval:
                beta = beta * 2.0
            return (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                    alpha_log, beta, self.g_b_proj(self.g_a_proj(x)))

    def scan(self, state, q, k, v, alpha_log, beta, valid=None, fresh=None,
             idle=None):
        """Arrays in, arrays out: the convolutions and the rule along the
        sequences of ``q`` / ``k`` / ``v`` (B, T, H * d) from ``state``; ``o``
        (B, T, H, d) float32 and the new state. ``fresh`` / ``idle`` (B,)
        bool are the serving cache's flags (``recur(..., masks=True)``): a
        fresh lane starts from zeros, an idle one gets its state back as it
        was."""
        cfg = self.cfg
        bsz, t = q.shape[0], q.shape[1]
        h, d = cfg.linear_heads, cfg.linear_head_dim

        def lanes(flag, like):
            return flag.reshape((-1,) + (1,) * (like.ndim - 1))

        if fresh is not None and idle is not None:
            # a sentinel lane (start -1) reads as both: it is kept, not zeroed
            fresh = fresh & ~idle
        def conv(name, rows):
            """One convolution along the sequences from its carried
            window: the rows after it and the window it leaves."""
            kept = state[f"conv_{name}"]
            window = kept
            if fresh is not None:
                window = jnp.where(lanes(fresh, window), 0, window)
            if valid is not None:
                rows = rows * valid[..., None].astype(rows.dtype)
            rows, last = _rule.conv_arrays(
                rows, getattr(self, f"{name}_conv_weight")._data, window)
            if idle is not None:
                last = jnp.where(lanes(idle, kept), kept, last)
            return rows, last.astype(kept.dtype)

        new = {}
        with jax.named_scope("attn.linear.conv"):
            q, new["conv_q"] = conv("q", q)
            k, new["conv_k"] = conv("k", k)
            v, new["conv_v"] = conv("v", v)
        with jax.named_scope("attn.linear.rule"):
            f32 = jnp.float32
            q = q.astype(f32).reshape(bsz, t, h, d)
            k = k.astype(f32).reshape(bsz, t, h, d)
            v = v.reshape(bsz, t, h, d)
            q = q * jax.lax.rsqrt(
                jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * d ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True)
                                  + 1e-6)
            if t == 1:      # decode: the recurrence itself, one visit
                o, s = _rule.step_any(
                    q[:, 0], k[:, 0], v[:, 0], jnp.exp(alpha_log[:, 0]),
                    beta[:, 0], state["s"], fresh, idle)
                o = o[:, None]
            else:
                s0 = state["s"]
                if fresh is not None:
                    s0 = jnp.where(lanes(fresh, s0), 0.0, s0)
                # an idle lane has no valid row, and rows that are not
                # valid leave the state as it was: no ``where`` over it
                o, s = _rule.channel_chunk_arrays(
                    q, k, v, alpha_log, beta, s0, valid, cfg.chunk_size)
            new["s"] = s
        return o, new

    def finish(self, o, gate):
        """Norm, gate and ``W_o`` of every row: ``o`` (B, T, H, d)."""
        cfg = self.cfg
        bsz, t = o.shape[0], o.shape[1]
        h, d = cfg.linear_heads, cfg.linear_head_dim
        with jax.named_scope("attn.linear.norm"):
            y = F.gated_rms_norm(o, ops.reshape(gate, [bsz, t, h, d]),
                                 self.o_norm_weight,
                                 epsilon=cfg.rms_norm_eps,
                                 activation="sigmoid")
        with jax.named_scope("attn.linear.proj"):
            return self.o_proj(ops.reshape(y, [bsz, t, h * d]))


class SolarOpen2Attention(nn.Layer):
    """Grouped-query causal attention, scale ``head_dim ** -0.5``, no bias,
    no positions, no q/k norm; the output gated by ``sigmoid(W_g x)``."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        std, hd = cfg.initializer_range, cfg.head_dim
        nq, nkv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
        self.q_proj = _linear(cfg.hidden_size, nq, std)
        self.k_proj = _linear(cfg.hidden_size, nkv, std)
        self.v_proj = _linear(cfg.hidden_size, nkv, std)
        self.g_proj = _linear(cfg.hidden_size, nq, std)
        self.o_proj = _linear(nq, cfg.hidden_size, std)

    def qkv(self, x):
        cfg = self.cfg
        b, t = x.shape[0], x.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        return (ops.reshape(self.q_proj(x), [b, t, nh, hd]),
                ops.reshape(self.k_proj(x), [b, t, nkv, hd]),
                ops.reshape(self.v_proj(x), [b, t, nkv, hd]))

    def gate_and_project(self, x, attended):
        """``(attended * sigmoid(W_g x)) W_o``: ``attended`` (B, T, heads,
        head_dim)."""
        b, t = x.shape[0], x.shape[1]
        with jax.named_scope("attn.full.gate"):
            gated = ops.reshape(attended, [b, t, -1]) * F.sigmoid(
                self.g_proj(x))
        return self.o_proj(gated)

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
        return self.gate_and_project(
            x, F.scaled_dot_product_attention(q, k, v, is_causal=True))


class SolarOpen2Block(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config, full: bool):
        super().__init__()
        self.full = full
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.mixer = (SolarOpen2Attention(cfg) if full
                      else KimiDeltaAttention(cfg))
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = nn.SwiGLUMoE(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.moe_intermediate_size * cfg.n_shared_experts,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            experts_held=cfg.experts_held,
            routed_scale=cfg.routed_scaling_factor,
            norm_topk=cfg.norm_topk_prob, init_std=cfg.initializer_range)

    @property
    def scope(self) -> str:
        return "attn.full" if self.full else "attn.linear"

    def forward(self, x):
        with jax.named_scope(self.scope):
            x = x + self.mixer(self.input_layernorm(x))
        with jax.named_scope("moe"):
            return x + self.mlp(self.post_attention_layernorm(x))


class SolarOpen2Model(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList(
            [SolarOpen2Block(cfg, i in cfg.gqa_layers)
             for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class SolarOpen2ForCausalLM(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        self.model = SolarOpen2Model(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                               cfg.initializer_range)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def paged_adapter(self):
        """What ``inference.PagedEngine`` serves this model through."""
        return _SolarOpen2Paged(self)


class _SolarOpen2Paged:
    """``PagedEngine`` adapter: per layer the cache states the engine
    carries, and the per-chunk forward over them."""

    def __init__(self, model: SolarOpen2ForCausalLM):
        self.model = model
        self.cfg = cfg = model.cfg
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim

    def cache_layout(self, dtype):
        """A layer's mixer state, ``("paged_kv",)`` for a GQA layer and
        ``("slot_state", ...)`` for a KDA layer (three convolution windows
        in the model's dtype and the float32 state), and beside it the
        expert-load counter every layer keeps."""
        cfg = self.cfg
        held = cfg.experts_held[1] - cfg.experts_held[0]
        counter = ("accumulator", (held + 2,), jnp.int32)
        return [
            ((("paged_kv",) if blk.full
              else ("slot_state", blk.mixer.state_layout(dtype))), counter)
            for blk in self.model.model.layers]

    def forward_chunk(self, tokens, cache, logits_t: int = 1):
        model = self.model
        valid = Tensor(cache.valid)
        with jax.named_scope("embed"):
            x = model.model.embed_tokens(Tensor(tokens))
        for li, blk in enumerate(model.model.layers):
            with jax.named_scope(blk.scope):
                u = blk.input_layernorm(x)
                if blk.full:
                    out = blk.mixer.gate_and_project(
                        u, cache.attend(li, *blk.mixer.qkv(u)))
                else:
                    # the weights meet every row once; the recurrence runs
                    # along each group's own sequences, and masks its own
                    # fresh and idle lanes (one visit of the state)
                    *rows, gate = blk.mixer.project(u)

                    def run(state, q, k, v, alpha_log, beta, valid, fresh,
                            idle, mixer=blk.mixer):
                        o, new = mixer.scan(
                            state, q._data, k._data, v._data,
                            alpha_log._data, beta._data, valid._data, fresh,
                            idle)
                        return Tensor(o), new
                    out = blk.mixer.finish(
                        cache.recur(li, run, *rows, valid, masks=True), gate)
                x = x + out
            with jax.named_scope("moe"):
                out, load = blk.mlp(blk.post_attention_layernorm(x),
                                    valid=valid, with_load=True)
                cache.accumulate(li, load._data)
                x = x + out
        x = model.model.norm(x)
        last = cache.head_rows(x, logits_t)
        with jax.named_scope("lm_head"):
            return model.lm_head(last)
