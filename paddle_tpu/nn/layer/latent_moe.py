"""Latent mixture-of-experts layer that is told which experts it holds.

The dropless serving-side expert layer (``distributed.fleet.MoELayer`` is
the GShard layer of the reference API: softmax gate, capacity, per-expert
sublayers). Nemotron-3's form: a float32 sigmoid router over ALL
``num_experts`` on the full-width input, the routed experts in a narrower
latent between a down- and an up-projection, one shared expert at full
width beside them. The layer holds experts ``[lo, hi)`` as two stacked
parameters and computes only their part of the routed sum, which is what
expert parallelism asks of a chip; the exchange that would bring the other
chips' parts is not here.
"""
from __future__ import annotations

import jax

from ...core.tensor import Tensor
from .. import functional as F
from ..functional import experts as _experts
from ..initializer import Constant, Normal
from ..parameter import ParamAttr
from .common import Linear
from .layers import Layer

__all__ = ["LatentMoE"]


def _linear(in_f, out_f, std):
    return Linear(in_f, out_f, bias_attr=False,
                  weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def relu2(x):
    h = F.relu(x)
    return h * h


class LatentMoE(Layer):
    """``up(sum_k w_k W2_e relu(W1_e down(u))^2) + V2 relu(V1 u)^2``.

    ``experts_held = (lo, hi)``: the routed experts whose weights live
    here, ``w1`` ``[hi - lo, latent, width]`` and ``w2`` ``[hi - lo, width,
    latent]``. The router stays ``num_experts`` wide and picks ``top_k``.
    ``forward(u, valid=None)`` returns the layer's output;
    ``forward(..., with_load=True)`` also the int32 load vector of
    ``functional.experts.load_arrays`` (tokens per held expert, pairs
    landed here, pairs selected)."""

    def __init__(self, hidden_size: int, latent_size: int,
                 expert_width: int, shared_width: int, num_experts: int,
                 top_k: int, experts_held=None, routed_scale: float = 1.0,
                 norm_topk: bool = True, init_std: float = 0.02):
        super().__init__()
        lo, hi = experts_held or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"experts_held {experts_held!r} outside "
                             f"[0, {num_experts}]")
        self.num_experts, self.top_k = num_experts, top_k
        self.experts_held = (lo, hi)
        self.routed_scale, self.norm_topk = routed_scale, norm_topk
        normal = ParamAttr(initializer=Normal(0.0, init_std))
        self.gate_weight = self.create_parameter(
            [hidden_size, num_experts], attr=normal)
        self.e_score_correction_bias = self.create_parameter(
            [num_experts], dtype="float32",
            default_initializer=Constant(0.0))
        self.latent_down = _linear(hidden_size, latent_size, init_std)
        self.latent_up = _linear(latent_size, hidden_size, init_std)
        self.w1 = self.create_parameter(
            [hi - lo, latent_size, expert_width], attr=normal)
        self.w2 = self.create_parameter(
            [hi - lo, expert_width, latent_size], attr=normal)
        self.shared_up = _linear(hidden_size, shared_width, init_std)
        self.shared_down = _linear(shared_width, hidden_size, init_std)

    def forward(self, u, valid=None, with_load: bool = False):
        lo, hi = self.experts_held
        shape = u.shape
        flat = u.reshape([-1, shape[-1]])
        rows = None if valid is None else valid.reshape([-1])
        with jax.named_scope("moe.router"):
            idx, w = F.sigmoid_topk_route(
                flat, self.gate_weight, self.e_score_correction_bias,
                self.top_k, scale=self.routed_scale,
                normalize=self.norm_topk)
        with jax.named_scope("moe.experts"):
            routed = self.latent_up(F.held_experts_relu2(
                self.latent_down(flat), idx, w, self.w1, self.w2, lo=lo,
                valid=rows, num_experts=self.gate_weight.shape[1]))
        with jax.named_scope("moe.shared"):
            shared = self.shared_down(relu2(self.shared_up(flat)))
        out = (routed + shared).reshape(shape)
        if not with_load:
            return out
        load = _experts.load_arrays(
            idx._data, lo, hi - lo, None if rows is None else rows._data)
        return out, Tensor(load)
