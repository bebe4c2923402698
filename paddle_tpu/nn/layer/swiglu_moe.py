"""Mixture of gated experts, with or without a shared expert, told which
experts it holds; the gate's activation and the route are arguments, and the
routing may be handed in.

The DeepSeek-V3 form that K-EXAONE's sparse layers take is the default: a
float32 sigmoid router over ALL ``num_experts``
(``functional.sigmoid_topk_route``), routed experts at full width, each
``down(silu(gate x) * up x)``, and one shared expert of the same form beside
them that every token passes through. Like ``LatentMoE`` the layer holds
experts ``[lo, hi)`` as stacked parameters and computes only their part of
the routed sum, which is what expert parallelism asks of a chip; the
exchange that would bring the other chips' parts is not here. LFM2-MoE's
sparse layers are the same layer with no shared expert (``shared_width =
0``) and their own renormalisation epsilon. SmallThinker's are the layer
with ``activation="relu"`` (ReGLU experts), ``route="softmax"`` (a softmax
over the chosen logits, ``functional.softmax_topk_route``; no correction
bias exists) and the routing made by the BLOCK from the attention's input
(``layer.route(x)``) and handed to ``forward(u, routing=...)``. The layer is
served by the paged engine and trained by ``Engine.fit``; the functional
picks the form of the expert product from the call's rows.
"""
from __future__ import annotations

import jax

from ...core.tensor import Tensor
from .. import functional as F
from ..functional import experts as _experts
from ..initializer import Constant, Normal
from ..parameter import ParamAttr
from .latent_moe import _linear
from .layers import Layer

__all__ = ["SwiGLUMoE"]


ROUTES = ("sigmoid", "softmax")


class SwiGLUMoE(Layer):
    """``sum_k w_k D_e (act(G_e u) * U_e u) + D_s (act(G_s u) * U_s u)``,
    ``activation`` ``silu`` (SwiGLU) or ``relu`` (ReGLU).

    ``experts_held = (lo, hi)``: the routed experts whose weights live
    here, ``w_gate`` / ``w_up`` ``[hi - lo, hidden, width]`` and ``w_down``
    ``[hi - lo, width, hidden]``. The router stays ``num_experts`` wide and
    picks ``top_k``. ``forward(u, valid=None)`` returns the layer's output;
    ``forward(..., with_load=True)`` also the int32 load vector of
    ``functional.experts.load_arrays`` (tokens per held expert, pairs
    landed here, pairs selected). ``shared_width = 0`` builds no shared
    expert; ``norm_eps`` is what the renormalisation of the chosen scores
    adds to their sum. ``route``: ``sigmoid`` (scores, correction bias,
    renormalisation and scale as above) or ``softmax`` (the ``top_k``
    largest logits, a softmax over them; ``routed_scale``, ``norm_topk``,
    ``norm_eps`` and the bias do not enter). ``route(x)`` is the routing
    ``(idx, w)`` of the rows of ``x``; ``forward(u, routing=(idx, w))``
    takes one made elsewhere in the block instead of routing from ``u``."""

    def __init__(self, hidden_size: int, expert_width: int,
                 shared_width: int, num_experts: int, top_k: int,
                 experts_held=None, routed_scale: float = 1.0,
                 norm_topk: bool = True, init_std: float = 0.02,
                 norm_eps: float = 1e-20, activation: str = "silu",
                 route: str = "sigmoid"):
        super().__init__()
        lo, hi = experts_held or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"experts_held {experts_held!r} outside "
                             f"[0, {num_experts}]")
        if activation not in _experts.GATE_ACTIVATIONS or route not in ROUTES:
            raise ValueError(
                f"activation {activation!r} / route {route!r}: one of "
                f"{sorted(_experts.GATE_ACTIVATIONS)} / {ROUTES}")
        self.activation, self.route_kind = activation, route
        self.num_experts, self.top_k = num_experts, top_k
        self.experts_held = (lo, hi)
        self.routed_scale, self.norm_topk = routed_scale, norm_topk
        self.norm_eps, self.shared_width = norm_eps, shared_width
        normal = ParamAttr(initializer=Normal(0.0, init_std))
        self.gate_weight = self.create_parameter(
            [hidden_size, num_experts], attr=normal)
        if route == "sigmoid":
            self.e_score_correction_bias = self.create_parameter(
                [num_experts], dtype="float32",
                default_initializer=Constant(0.0))
        self.w_gate = self.create_parameter(
            [hi - lo, hidden_size, expert_width], attr=normal)
        self.w_up = self.create_parameter(
            [hi - lo, hidden_size, expert_width], attr=normal)
        self.w_down = self.create_parameter(
            [hi - lo, expert_width, hidden_size], attr=normal)
        if shared_width:
            self.shared_gate = _linear(hidden_size, shared_width, init_std)
            self.shared_up = _linear(hidden_size, shared_width, init_std)
            self.shared_down = _linear(shared_width, hidden_size, init_std)

    def shared(self, flat):
        """The shared expert: what every chip computes alike."""
        gate, up = self.shared_gate(flat), self.shared_up(flat)
        if self.activation == "silu":
            return self.shared_down(F.swiglu(gate, up))
        return self.shared_down(F.relu(gate) * up)

    def route(self, x):
        """``(idx, w)``, each (rows of ``x``, ``top_k``): the experts every
        row chooses among ALL ``num_experts`` and their weights."""
        flat = x.reshape([-1, x.shape[-1]])
        with jax.named_scope("moe.router"):
            if self.route_kind == "softmax":
                return F.softmax_topk_route(flat, self.gate_weight,
                                            self.top_k)
            return F.sigmoid_topk_route(
                flat, self.gate_weight, self.e_score_correction_bias,
                self.top_k, scale=self.routed_scale,
                normalize=self.norm_topk, norm_eps=self.norm_eps)

    def forward(self, u, valid=None, with_load: bool = False, routing=None):
        lo, hi = self.experts_held
        shape = u.shape
        flat = u.reshape([-1, shape[-1]])
        rows = None if valid is None else valid.reshape([-1])
        idx, w = self.route(flat) if routing is None else routing
        with jax.named_scope("moe.experts"):
            routed = F.held_experts_swiglu(
                flat, idx, w, self.w_gate, self.w_up, self.w_down, lo=lo,
                valid=rows, activation=self.activation,
                num_experts=self.gate_weight.shape[1])
        if self.shared_width:
            with jax.named_scope("moe.shared"):
                routed = routed + self.shared(flat)
        out = routed.reshape(shape)
        if not with_load:
            return out
        load = _experts.load_arrays(
            idx._data, lo, hi - lo, None if rows is None else rows._data)
        return out, Tensor(load)
