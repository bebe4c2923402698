"""Mixture-of-Experts with expert parallelism.

Capability parity with the reference MoE stack (reference:
python/paddle/incubate/distributed/models/moe/moe_layer.py:263 ``MoELayer``
routing tokens with NCCL alltoall through per-rank expert sublayers;
gates in .../moe/gate/: NaiveGate, GShardGate top-2 with capacity).
TPU-native redesign (GShard-style): routing is expressed as dispatch /
combine one-hot einsums over global arrays —

* ``TopKGate`` produces dispatch mask [N, E, C] + combine weights + the
  load-balancing aux loss;
* expert weights are STACKED along a leading expert dim sharded over the
  expert-parallel mesh axis (``ep_axis``), so the dispatch einsum
  (tokens sharded on batch × experts sharded on E) makes XLA insert the
  all-to-all on ICI — no hand-written NCCL alltoall, and the routing is
  differentiable end-to-end by construction.

This is the layer of the reference API (softmax gate, capacity factor,
dropped tokens, per-expert sublayers). The dropless serving-side layer that
holds a share of the experts as stacked weights is ``nn.LatentMoE``
(``nn/layer/latent_moe.py``).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core import dispatch
from ...core.tensor import Tensor
from ...nn.layer.layers import Layer
from ...nn.parameter import ParamAttr
from ...observability import metrics as _metrics
from .. import mesh as mesh_mod

_m_expert_tokens = _metrics.counter(
    "paddle_tpu_moe_expert_tokens_total",
    "Tokens routed per expert: by eager MoE dispatch (within capacity), "
    "and by a serving engine's compiled programs (read from their device "
    "counters when health() or the metrics dump asks).",
    labelnames=("expert",))
_m_load_imbalance = _metrics.gauge(
    "paddle_tpu_moe_load_imbalance",
    "max/mean tokens-per-expert of the latest eager MoE dispatch, or of "
    "a serving engine's worst expert layer since it started "
    "(1.0 = perfectly balanced).")
_m_routed_pairs = _metrics.counter(
    "paddle_tpu_moe_routed_pairs_total",
    "(token, expert) pairs a serving engine's routers selected "
    "(kind=selected) and those that landed on experts held here "
    "(kind=held).", labelnames=("kind",))
_m_pair_strides = _metrics.counter(
    "paddle_tpu_moe_pair_strides_total",
    "Strides of the sorted pair buffer a trained model's routed layers "
    "walked (kind=walked: ceil(landed pairs / stride) a layer a step, at "
    "the epoch's mean load a step) and the strides of the whole buffer "
    "(kind=buffer: what every step walked before the layer stopped at its "
    "landed pairs). walked / buffer is the share of the buffer's row work "
    "that is still done.", labelnames=("kind",))


def stamp_expert_load(tokens_per_expert, first_expert: int, pairs_held,
                      pairs_selected, imbalance):
    """Export host-side expert-load counts read from a serving engine's
    device counters (``PagedEngine.expert_load``)."""
    if not _metrics.enabled():
        return
    for e, c in enumerate(tokens_per_expert):
        if c > 0:
            _m_expert_tokens.inc(float(c), expert=first_expert + e)
    _m_routed_pairs.inc(float(pairs_held), kind="held")
    _m_routed_pairs.inc(float(pairs_selected), kind="selected")
    if imbalance is not None:
        _m_load_imbalance.set(imbalance)


def stamp_pair_strides(landed, selected, stride: int, pairs: int):
    """Export how far the grouped expert product walked its pair buffer
    over an epoch: ``landed`` / ``selected`` the epoch's pairs a routed
    layer (host arrays, ``moe.expert_load``'s last two columns), ``stride``
    / ``pairs`` the static stride and buffer of one call
    (``nn.functional.experts.pair_walk``). Returns ``(walked, buffer)``."""
    steps = np.asarray(selected) // pairs
    a_step = -(-np.asarray(landed) // np.maximum(steps, 1))
    walked = int(np.sum(steps * -(-a_step // stride)))
    whole = int(np.sum(steps) * -(-pairs // stride))
    if _metrics.enabled():
        _m_pair_strides.inc(float(walked), kind="walked")
        _m_pair_strides.inc(float(whole), kind="buffer")
    return walked, whole


def _stamp_expert_load(dispatch_mask: Tensor):
    """Per-expert token counts + load-imbalance gauge from the dispatch
    mask [N, E, C] — the per-rank expert-load-balance signal the MoE
    scaling rung is judged on.  Only stamps eager dispatches: inside a
    traced program the mask is abstract and a host read would either
    fail or silently bake a constant, so telemetry stays out."""
    if not _metrics.enabled():
        return
    data = dispatch_mask._data
    if isinstance(data, jax.core.Tracer):
        return
    counts = np.asarray(jnp.sum(data, axis=(0, 2)))  # tpulint: disable=TPU104 — telemetry-by-design: eager-only (tracer-guarded), metrics-gated host read
    for e, c in enumerate(counts):
        if c > 0:  # tpulint: disable=TPU105 — counts is host numpy here (eager telemetry path)
            _m_expert_tokens.inc(float(c), expert=e)  # tpulint: disable=TPU103 — same eager telemetry path
    mean = float(counts.mean())  # tpulint: disable=TPU103 — same eager telemetry path
    if mean > 0:
        _m_load_imbalance.set(float(counts.max()) / mean)  # tpulint: disable=TPU103 — same eager telemetry path


def _ep_axes(ep_axis: Optional[str], num_experts: int):
    mesh = mesh_mod.get_mesh()
    if (ep_axis and ep_axis in mesh.axis_names
            and int(mesh.shape[ep_axis]) > 1
            and num_experts % int(mesh.shape[ep_axis]) == 0):
        return mesh, (ep_axis,)
    return mesh, ()


class TopKGate(Layer):
    """Top-k gating with capacity (reference moe/gate/gshard_gate.py
    GShardGate; top-1 == NaiveGate+capacity). Returns, for tokens [N, H]:
    combine [N, E, C] (soft weights), dispatch [N, E, C] (0/1), aux loss.
    """

    def __init__(self, d_model: int, num_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.25):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        from ...nn.initializer import Normal
        self.weight = self.create_parameter(
            [d_model, num_experts],
            attr=ParamAttr(initializer=Normal(0.0, 0.02)))

    def _routing(self, logits):
        """logits [N, E] -> (combine [N,E,C], dispatch [N,E,C], aux)."""
        n, e = logits.shape
        k = self.top_k
        capacity = max(int(self.capacity_factor * n * k / e), 1)
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

        # iterative top-k with per-expert positions via cumsum (GShard)
        remaining = gates
        combine = jnp.zeros((n, e, capacity), jnp.float32)
        dispatch = jnp.zeros((n, e, capacity), bool)
        fill = jnp.zeros((e,), jnp.int32)      # tokens already in expert
        aux_me = jnp.mean(gates, axis=0)       # mean prob per expert
        aux_ce = jnp.zeros((e,), jnp.float32)  # fraction routed per expert
        for _ in range(k):
            idx = jnp.argmax(remaining, axis=-1)              # [N]
            onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
            pos = jnp.cumsum(onehot, axis=0) - 1.0            # [N, E]
            pos = pos + fill[None, :].astype(jnp.float32)
            in_cap = (pos < capacity) & (onehot > 0)
            pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
            cslot = jax.nn.one_hot(pos_c, capacity, dtype=jnp.float32)
            mask = in_cap[..., None] * cslot                  # [N, E, C]
            w = jnp.take_along_axis(gates, idx[:, None],
                                    axis=1)                   # [N, 1]
            combine = combine + mask * w[:, :, None]
            dispatch = dispatch | (mask > 0)
            aux_ce = aux_ce + jnp.mean(onehot, axis=0)
            fill = fill + jnp.sum(onehot, axis=0).astype(jnp.int32)
            remaining = remaining * (1.0 - onehot)
        aux = jnp.sum(aux_me * aux_ce) * e / k
        return combine, dispatch.astype(jnp.float32), aux

    def forward(self, x: Tensor):
        """GShard top-k gating: token logits -> (combine weights
        [N, E, C], dispatch mask [N, E, C], load-balance aux loss) —
        the registered ``moe_gate`` op."""
        def f(xa, wa):
            logits = xa.reshape(-1, xa.shape[-1]) @ wa
            return self._routing(logits)
        return dispatch.call("moe_gate", f, [x, self.weight])


class _ExpertMLP(Layer):
    """Default expert: 2-layer GELU MLP (reference ExpertLayer)."""

    def __init__(self, d_model: int, d_hidden: int):
        super().__init__()
        from ...nn import Linear
        self.fc1 = Linear(d_model, d_hidden)
        self.fc2 = Linear(d_hidden, d_model)

    def forward(self, x):
        from ...nn import functional as F
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class MoELayer(Layer):
    """MoE layer with expert parallelism (reference moe_layer.py:263).

    ``experts`` — a list of identical-structure expert Layers (stacked for
    SPMD execution), or None to build ``num_experts`` default MLP experts.
    ``ep_axis`` — mesh axis the expert dim is sharded over ('mp' default).
    The load-balancing aux loss of the latest forward is ``self.l_aux``
    (add it to the training loss, reference contract).
    """

    def __init__(self, d_model: int, num_experts: int,
                 experts: Optional[Sequence[Layer]] = None,
                 d_hidden: Optional[int] = None, top_k: int = 2,
                 capacity_factor: float = 1.25, gate: Optional[Layer] = None,
                 ep_axis: str = "mp"):
        super().__init__()
        self.num_experts = num_experts
        self.ep_axis = ep_axis
        self.gate = gate or TopKGate(d_model, num_experts, top_k,
                                     capacity_factor)
        if experts is None:
            from ...nn import LayerList
            experts = LayerList([
                _ExpertMLP(d_model, d_hidden or 4 * d_model)
                for _ in range(num_experts)])
        else:
            from ...nn import LayerList
            experts = experts if isinstance(experts, LayerList) \
                else LayerList(list(experts))
        if len(experts) != num_experts:
            raise ValueError(f"{len(experts)} experts != num_experts="
                             f"{num_experts}")
        self.experts = experts
        # ALL params (frozen included) are stacked/swapped — a frozen
        # per-expert constant must still be each expert's own value
        t0 = list(experts[0].parameters())
        for ex in experts:
            ps = list(ex.parameters())
            if [tuple(p.shape) for p in ps] != [tuple(p.shape) for p in t0]:
                raise ValueError("experts must be identical in structure "
                                 "for stacked SPMD execution")
        self.l_aux: Optional[Tensor] = None

    def forward(self, x: Tensor) -> Tensor:
        """Dispatch/expert/combine as ONE ``moe_layer`` op: the GShard
        einsum pair around the vmapped stacked experts; the aux loss
        lands on ``self.l_aux``."""
        combine, dispatch_mask, aux = self.gate(x)
        self.l_aux = aux
        _stamp_expert_load(dispatch_mask)

        template = self.experts[0]
        tmpl_params = list(template.parameters())
        all_params: List[Tensor] = []
        for ex in self.experts:
            all_params.extend(ex.parameters())
        n_p = len(tmpl_params)
        mesh, axes = _ep_axes(self.ep_axis, self.num_experts)

        def f(xa, ca, da, *flat):
            shape = xa.shape
            h = shape[-1]
            tokens = xa.reshape(-1, h)
            e = self.num_experts
            # stack expert params on a leading E dim sharded over ep
            stacked = []
            for j in range(n_p):
                s = jnp.stack([flat[i * n_p + j] for i in range(e)])
                if axes:
                    s = jax.lax.with_sharding_constraint(
                        s, NamedSharding(mesh, P(*axes)))
                stacked.append(s)
            # dispatch: [N,E,C] x [N,H] -> [E,C,H]
            ein = jnp.einsum("nec,nh->ech", da, tokens.astype(jnp.float32))
            if axes:
                ein = jax.lax.with_sharding_constraint(
                    ein, NamedSharding(mesh, P(*axes)))
            ein = ein.astype(tokens.dtype)

            def run_expert(pvals, xe):
                originals = [p._data for p in tmpl_params]
                for p, a in zip(tmpl_params, pvals):
                    p._data = a
                try:
                    # the template's own dispatches are INTERNAL to this
                    # lowering: without the quiet scope they'd leak into
                    # an enclosing program_guard as dead nested records
                    with dispatch.quiet_scope():
                        return template(
                            Tensor(xe, stop_gradient=False))._data
                finally:
                    for p, o in zip(tmpl_params, originals):
                        p._data = o

            eout = jax.vmap(run_expert)(stacked, ein)        # [E, C, H]
            # combine: [N,E,C] x [E,C,H] -> [N,H]
            y = jnp.einsum("nec,ech->nh", ca,
                           eout.astype(jnp.float32)).astype(tokens.dtype)
            return y.reshape(shape)

        return dispatch.call("moe_layer", f,
                             [x, combine, dispatch_mask, *all_params])


# the registry is the op surface of record (verifier TPU700): the MoE
# ops dispatch from the layer forwards, which close over the routing
# hyperparameters — the forwards ARE the lowerings. The planner prices
# both through its explicit PENALTY_OPS table, never silently.
from ...ops import registry as _op_registry  # noqa: E402

_op_registry.register("moe_gate", "nn_common",
                      tags=("moe",))(TopKGate.forward)
_op_registry.register("moe_layer", "nn_common",
                      tags=("moe",))(MoELayer.forward)
