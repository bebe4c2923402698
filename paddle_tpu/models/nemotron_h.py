"""Nemotron-H hybrid decoder (``model_type`` ``nemotron_h``; NVIDIA
Nemotron-3-Super-120B-A12B is the published instance).

Every block is ONE mixer behind one RMSNorm and one residual add,
``x <- x + mixer_i(RMSNorm_i(x))``; ``hybrid_override_pattern`` says which
mixer each block has: ``M`` a Mamba-2 (SSD) layer, ``*`` grouped-query
attention WITHOUT any positional encoding (the family's attention layers
apply none; ``rope_theta`` is carried in the published config and unread),
``E`` a latent mixture-of-experts layer (``nn.LatentMoE``). After the last
block ``norm_f`` and an untied head. The multi-token-prediction module of
the published checkpoint is a draft head beside the served logits and is
not built here.

``forward(ids)`` runs a whole sequence (tests, trainers);
``paged_adapter()`` is what ``inference.PagedEngine`` serves the model
through: per layer it declares the cache state the engine must carry
(paged K/V for ``*``, a per-slot convolution window and SSM state for
``M``, an expert-load accumulator for ``E``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn, ops
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Assign, Constant, Normal
from ..nn.parameter import ParamAttr

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "NemotronHModel",
           "Mamba2Mixer", "NemotronHAttention", "nemotron_h_tiny"]


@dataclass
class NemotronHConfig:
    """The published keys under their published names."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    hybrid_override_pattern: str = "MEM*E"
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0        # published, unread: no rotary here
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # latent mixture of experts
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    #: ``(lo, hi)``: the routed experts this chip holds (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    max_seq_len: int = 4096

    def __post_init__(self):
        bad = set(self.hybrid_override_pattern) - set("ME*")
        if bad or not self.hybrid_override_pattern:
            raise ValueError(f"hybrid_override_pattern takes M, E and *; "
                             f"got {self.hybrid_override_pattern!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must be a multiple of n_groups")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(self.experts_held)

    # what the engine and the rest of the zoo call these
    @property
    def num_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def nemotron_h_tiny(**kw) -> NemotronHConfig:
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("hybrid_override_pattern", "MEM*E")
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("mamba_num_heads", 8)
    kw.setdefault("mamba_head_dim", 8)
    kw.setdefault("n_groups", 2)
    kw.setdefault("ssm_state_size", 16)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("n_routed_experts", 16)
    kw.setdefault("num_experts_per_tok", 4)
    kw.setdefault("moe_latent_size", 32)
    kw.setdefault("moe_intermediate_size", 48)
    kw.setdefault("moe_shared_expert_intermediate_size", 96)
    kw.setdefault("max_seq_len", 128)
    return NemotronHConfig(**kw)


def _linear(in_f, out_f, std):
    return nn.Linear(in_f, out_f, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def _mamba_time_constants(cfg: NemotronHConfig):
    """The Mamba-2 reference initialisation: ``A`` uniform in [1, 16],
    ``dt`` log-uniform in [time_step_min, time_step_max] floored at
    time_step_floor and stored through the inverse of softplus."""
    rng = np.random.default_rng(cfg.mamba_num_heads)
    a_log = np.log(rng.uniform(1.0, 16.0, cfg.mamba_num_heads))
    dt = np.exp(rng.uniform(math.log(cfg.time_step_min),
                            math.log(cfg.time_step_max),
                            cfg.mamba_num_heads))
    dt = np.maximum(dt, cfg.time_step_floor)
    return (a_log.astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


class Mamba2Mixer(nn.Layer):
    """``[z | xBC | dt] = W_in u``; ``xBC <- silu(conv(xBC))``; the SSD
    recurrence over ``x, B, C`` with ``dt <- softplus(dt + dt_bias)`` and
    ``A = -exp(A_log)``; gated group norm; ``W_out``. ``state`` is the
    pair ``(conv window (B, K-1, conv_dim), ssm state (B, H, P, N) f32)``
    the mixer continues from and hands back."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        d_in, h = cfg.mamba_inner, cfg.mamba_num_heads
        std = cfg.initializer_range
        self.in_proj = _linear(cfg.hidden_size, d_in + cfg.conv_dim + h, std)
        self.conv_weight = self.create_parameter(
            [cfg.conv_dim, cfg.conv_kernel],
            attr=ParamAttr(initializer=Normal(0.0, std)))
        self.conv_bias = self.create_parameter(
            [cfg.conv_dim], default_initializer=Constant(0.0))
        a_log, dt_bias = _mamba_time_constants(cfg)
        self.A_log = self.create_parameter(
            [h], dtype="float32", default_initializer=Assign(a_log))
        self.dt_bias = self.create_parameter(
            [h], dtype="float32", default_initializer=Assign(dt_bias))
        self.D = self.create_parameter(
            [h], dtype="float32", default_initializer=Constant(1.0))
        self.norm_weight = self.create_parameter(
            [d_in], dtype="float32", default_initializer=Constant(1.0))
        self.out_proj = _linear(d_in, cfg.hidden_size, std)

    def forward(self, u, state=None, valid=None):
        """``u`` (B, T, hidden). ``valid`` (B, T) bool marks real rows:
        an invalid row feeds the convolution zeros and leaves the SSM
        state as it was (left padding of a first chunk). Returns the
        mixer's output, and the new ``(window, ssm)`` when ``state`` is
        given. It is ``project`` (row-wise), ``scan`` (along each
        sequence), ``finish`` (row-wise): a caller whose rows are not all
        one batch of sequences (serving, a chunk with the decode batch
        aboard) runs the three itself, ``scan`` once a group of rows."""
        z, xbc, dt = self.project(u)
        y, new = self.scan(xbc, dt, state, valid)
        out = self.finish(y, z)
        return out if state is None else (out, new)

    def project(self, u):
        """``z`` (the gate), ``xBC`` (the convolution's input) and ``dt``
        (float32, after its softplus) of every row of ``u``."""
        cfg = self.cfg
        z, xbc, dt = ops.split(
            self.in_proj(u),
            [cfg.mamba_inner, cfg.conv_dim, cfg.mamba_num_heads], axis=-1)
        return z, xbc, F.softplus(dt.astype("float32") + self.dt_bias)

    def scan(self, xbc, dt, state=None, valid=None):
        """Convolution and SSD recurrence along the sequences of ``xbc``
        (B, T, conv_dim) / ``dt`` (B, T, H) from ``state``: ``y`` (B, T,
        inner) and the new ``(window, ssm)``."""
        cfg = self.cfg
        bsz, t = xbc.shape[0], xbc.shape[1]
        d_in, h, p = cfg.mamba_inner, cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n = cfg.n_groups, cfg.ssm_state_size
        if valid is not None:
            xbc = xbc * valid.astype(xbc.dtype).unsqueeze(-1)
            dt = dt * valid.astype("float32").unsqueeze(-1)
        window, ssm = state if state is not None else (None, None)
        xbc, window = F.causal_conv1d(xbc, self.conv_weight, self.conv_bias,
                                      window)
        x, b, c = ops.split(xbc, [d_in, g * n, g * n], axis=-1)
        a = -ops.exp(self.A_log)
        if t == 1 and ssm is not None:      # decode: the recurrence itself
            y, ssm = F.ssd_state_update(
                x.reshape([bsz, h, p]), dt.reshape([bsz, h]), a,
                b.reshape([bsz, g, n]), c.reshape([bsz, g, n]), self.D, ssm)
        else:
            y, ssm = F.ssd_chunk_scan(
                x.reshape([bsz, t, h, p]), dt, a,
                b.reshape([bsz, t, g, n]), c.reshape([bsz, t, g, n]),
                self.D, ssm, chunk_size=cfg.chunk_size)
        return y.reshape([bsz, t, d_in]), (window, ssm)

    def finish(self, y, z):
        """Gated group norm and ``W_out`` of every row."""
        y = F.gated_group_rms_norm(
            y, z, self.norm_weight, groups=self.cfg.n_groups,
            epsilon=self.cfg.layer_norm_epsilon)
        return self.out_proj(y)


class NemotronHAttention(nn.Layer):
    """Causal grouped-query attention, softmax scale ``head_dim ** -0.5``,
    no bias, no positional encoding."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        std = cfg.initializer_range
        nq = cfg.num_attention_heads * cfg.head_dim
        nkv = cfg.num_key_value_heads * cfg.head_dim
        self.q_proj = _linear(cfg.hidden_size, nq, std)
        self.k_proj = _linear(cfg.hidden_size, nkv, std)
        self.v_proj = _linear(cfg.hidden_size, nkv, std)
        self.o_proj = _linear(nq, cfg.hidden_size, std)

    def qkv(self, u):
        cfg = self.cfg
        b, t = u.shape[0], u.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        return (ops.reshape(self.q_proj(u), [b, t, nh, hd]),
                ops.reshape(self.k_proj(u), [b, t, nkv, hd]),
                ops.reshape(self.v_proj(u), [b, t, nkv, hd]))

    def forward(self, u):
        cfg = self.cfg
        b, t = u.shape[0], u.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        q, k, v = self.qkv(u)
        rep = nh // nkv
        if rep > 1:
            k = ops.reshape(ops.tile(ops.unsqueeze(k, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
            v = ops.reshape(ops.tile(ops.unsqueeze(v, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(ops.reshape(out, [b, t, nh * hd]))


_SCOPES = {"M": "mamba", "*": "attn", "E": "moe"}


class NemotronHBlock(nn.Layer):
    def __init__(self, cfg: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(cfg.hidden_size,
                               epsilon=cfg.layer_norm_epsilon)
        if kind == "M":
            self.mixer = Mamba2Mixer(cfg)
        elif kind == "*":
            self.mixer = NemotronHAttention(cfg)
        else:
            self.mixer = nn.LatentMoE(
                cfg.hidden_size, cfg.moe_latent_size,
                cfg.moe_intermediate_size,
                cfg.moe_shared_expert_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                routed_scale=cfg.routed_scaling_factor,
                norm_topk=cfg.norm_topk_prob,
                init_std=cfg.initializer_range)

    def forward(self, x):
        with jax.named_scope(_SCOPES[self.kind]):
            return x + self.mixer(self.norm(x))


class NemotronHModel(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList(
            [NemotronHBlock(cfg, kind)
             for kind in cfg.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x)
        return self.norm_f(x)


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.model = NemotronHModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                               cfg.initializer_range)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def paged_adapter(self):
        """What ``inference.PagedEngine`` serves this model through."""
        return _NemotronHPaged(self)


class _NemotronHPaged:
    """``PagedEngine`` adapter: per layer the cache state the engine
    carries, and the per-chunk forward over that state."""

    def __init__(self, model: NemotronHForCausalLM):
        self.model = model
        self.cfg = cfg = model.cfg
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim

    def cache_layout(self, dtype):
        """One entry a layer: ``("paged_kv",)``, ``("slot_state", {name:
        (shape after the slot axis, dtype)})`` or ``("accumulator", shape,
        dtype)``. ``dtype`` is the model's compute dtype."""
        cfg = self.cfg
        held = cfg.experts_held[1] - cfg.experts_held[0]
        kinds = {
            "*": ("paged_kv",),
            "M": ("slot_state", {
                "conv": ((cfg.conv_kernel - 1, cfg.conv_dim), dtype),
                "ssm": ((cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.ssm_state_size), jnp.float32)}),
            "E": ("accumulator", (held + 2,), jnp.int32)}
        return [kinds[k] for k in cfg.hybrid_override_pattern]

    def forward_chunk(self, tokens, cache, logits_t: int = 1):
        model = self.model
        bsz, t = tokens.shape
        nh, hd = self.cfg.num_attention_heads, self.cfg.head_dim
        valid = Tensor(cache.valid)
        with jax.named_scope("embed"):
            x = model.model.embed_tokens(Tensor(tokens))
        for li, blk in enumerate(model.model.layers):
            with jax.named_scope(_SCOPES[blk.kind]):
                u = blk.norm(x)
                if blk.kind == "*":
                    q, k, v = blk.mixer.qkv(u)
                    out = blk.mixer.o_proj(ops.reshape(
                        cache.attend(li, q, k, v), [bsz, t, nh * hd]))
                elif blk.kind == "M":
                    # the weights meet every row once; the recurrence runs
                    # along each group's own sequences
                    z, xbc, dt = blk.mixer.project(u)

                    def run(state, xbc, dt, valid, mixer=blk.mixer):
                        y, (window, ssm) = mixer.scan(
                            xbc, dt, (Tensor(state["conv"]),
                                      Tensor(state["ssm"])), valid)
                        return y, {"conv": window._data, "ssm": ssm._data}
                    out = blk.mixer.finish(
                        cache.recur(li, run, xbc, dt, valid), z)
                else:
                    out, load = blk.mixer(u, valid=valid, with_load=True)
                    cache.accumulate(li, load._data)
                x = x + out
        x = model.model.norm_f(x)
        last = cache.head_rows(x, logits_t)
        with jax.named_scope("lm_head"):
            return model.lm_head(last)
