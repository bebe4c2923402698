"""Per-op sharding-propagation rules.

Capability parity with the reference's ``phi/infermeta/spmd_rules/``
(~30 hand-written rules: matmul, flash_attention, layer_norm, rms_norm,
fused_rope, elementwise, reduction, reshape, …). Each rule maps the
*input* PartitionSpecs of one op to its *output* specs plus the input
constraints the op needs — the propagation pass
(:mod:`.propagate`) threads these through a whole program so one mesh
declaration shards every op, and GSPMD picks the collectives.

Conventions
-----------
* A spec is a tuple with one entry per tensor dim: ``None`` (replicated
  / unknown), an axis name, or a tuple of axis names. ``normalize``
  produces it from ``jax.sharding.PartitionSpec`` / ``None``.
* Rule signature (mirrors ``OpDef.cost_fn``)::

      rule(input_specs, input_shapes, attrs, output_shapes) -> SpmdResult

  Shapes are int tuples; attrs the op's semantic attr dict (many
  lowerings close over their parameters instead — rules therefore lean
  on shapes, which the IR always has).
* Rules are HEURISTIC guidance, not correctness constraints: any spec
  is legal (the partitioner reshards), so a rule's job is to keep data
  where it already is and surface the natural output placement.
* The meet rule (`meet`): merging two candidate specs for one value is
  per-dim — equal entries keep; a ``None`` yields to the sharded side;
  two *different* sharded entries replicate that dim (conflict, counted
  in ``paddle_tpu_spmd_conflicts_total``). One axis name may shard only
  one dim of a value; later repeats are dropped (`dedupe`).
* **Partial (reduce-pending) placement**: a value whose producer
  contracted a sharded dim (row-parallel matmul, einsum over a sharded
  contraction) is *partial* over those mesh axes — each shard holds a
  partial sum and an all-reduce over the axes is pending. Partiality is
  a per-VALUE property (not per-dim), carried as a sorted tuple of axis
  names in ``SpmdResult.out_partial`` and merged with `meet_partial`:
  equal keeps; the intersection survives a disagreement (an axis one
  side believes already reduced cannot be un-reduced). The planner's
  cost model charges the pending all-reduce; GSPMD still owns emitting
  it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ...observability import metrics as _metrics

__all__ = ["SpmdResult", "Partial", "normalize", "meet", "meet_partial",
           "dedupe", "to_pspec", "attach_spmd_rules", "rule_for",
           "SPMD_RULES", "CATEGORY_RULES", "rule_class_of"]

_m_conflicts = _metrics.counter(
    "paddle_tpu_spmd_conflicts_total",
    "Sharding-propagation meet conflicts: two inputs proposed different "
    "mesh axes for the same tensor dim (the dim was replicated).")


# --------------------------------------------------------------------------
# Spec algebra
# --------------------------------------------------------------------------
def normalize(spec, rank: int) -> tuple:
    """PartitionSpec / tuple / None -> canonical tuple of length ``rank``."""
    if spec is None:
        return (None,) * rank
    entries = list(spec)
    entries = entries[:rank] + [None] * (rank - len(entries))
    out = []
    for e in entries:
        if e is None or e == ():
            out.append(None)
        elif isinstance(e, (list, tuple)):
            out.append(tuple(e) if len(e) > 1 else (e[0] if e else None))
        else:
            out.append(e)
    return tuple(out)


def _axes(entry) -> tuple:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def dedupe(spec: Sequence) -> tuple:
    """Drop repeated axis uses (an axis may shard only one dim)."""
    seen = set()
    out = []
    for e in spec:
        kept = tuple(a for a in _axes(e) if a not in seen)
        seen.update(kept)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(kept)
    return tuple(out)


def meet(a: Sequence, b: Sequence) -> tuple:
    """Merge two equal-rank candidate specs (see module docstring)."""
    out = []
    for ea, eb in zip(a, b):
        if ea == eb:
            out.append(ea)
        elif ea is None:
            out.append(eb)
        elif eb is None:
            out.append(ea)
        else:  # genuine disagreement -> replicate the dim
            if _metrics.enabled():
                _m_conflicts.inc()
            out.append(None)
    return dedupe(out)


def to_pspec(spec: Sequence):
    """Canonical tuple -> jax PartitionSpec."""
    from jax.sharding import PartitionSpec as P
    return P(*spec)


def is_trivial(spec) -> bool:
    return spec is None or all(e is None for e in spec)


@dataclass(frozen=True)
class Partial:
    """Reduce-pending placement marker: the value is a partial sum over
    ``axes`` — each shard along those mesh axes holds an addend and an
    all-reduce is pending. Surfaced by rules whose op contracts a
    sharded dim (einsum/matmul); the planner's scorer charges the wire
    bytes, the partitioner emits the actual collective."""

    axes: tuple

    def __iter__(self):
        return iter(self.axes)


def normalize_partial(p) -> tuple:
    """Partial / axis tuple / axis name / None -> sorted axis tuple."""
    if p is None:
        return ()
    if isinstance(p, Partial):
        p = p.axes
    elif hasattr(p, "reduce_type"):
        # the OTHER Partial — distributed.auto_parallel's DistTensor
        # Placement. It names a reduce op, not mesh axes; silently
        # iterating it would produce garbage axis tuples
        raise TypeError(
            "got a distributed.Partial Placement; the spmd spec "
            "algebra wants spmd.rules.Partial(axes) / an axis tuple")
    if isinstance(p, str):
        p = (p,)
    return tuple(sorted(set(p)))


def meet_partial(a, b) -> tuple:
    """Merge two reduce-pending proposals for one value: equal keeps;
    otherwise only the axes BOTH sides still consider pending survive
    (an axis one side already reduced over cannot be un-reduced)."""
    return tuple(sorted(set(normalize_partial(a))
                        & set(normalize_partial(b))))


@dataclass
class SpmdResult:
    """One rule application: resolved input constraints + output specs.

    ``in_specs[i] is None`` means "no constraint — leave input i as the
    propagator found it"; otherwise the propagator may re-annotate the
    input at the op boundary (the offline ``shard_program`` pass does;
    the online trace scope only annotates outputs).

    ``out_partial[i]`` is the sorted tuple of mesh axes output i is
    reduce-pending over (empty = fully reduced / not partial). Rules
    that contract a sharded dim (matmul/einsum) surface the pending
    all-reduce here so the planner can score it; the propagator does
    NOT insert a constraint for it — the partitioner owns the
    collective.
    """

    out_specs: List[tuple]
    in_specs: List[Optional[tuple]] = field(default_factory=list)
    out_partial: List[tuple] = field(default_factory=list)


# --------------------------------------------------------------------------
# Shape-walk helpers (lowerings close over axis args, so rules infer
# the dim mapping from shapes)
# --------------------------------------------------------------------------
def _numel(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _align_dims(in_shape, out_shape) -> List[Optional[int]]:
    """out-dim -> in-dim map by a greedy size walk: equal-size runs map
    1:1, size-1 dims skip, anything ambiguous maps to None. Serves
    squeeze/unsqueeze/getitem/keepdim-reductions."""
    mapping: List[Optional[int]] = [None] * len(out_shape)
    i = 0
    for o, od in enumerate(out_shape):
        while i < len(in_shape) and in_shape[i] == 1 and od != 1:
            i += 1
        if i < len(in_shape) and in_shape[i] == od:
            mapping[o] = i
            i += 1
        elif od == 1:
            continue
        else:  # partial slice / merged dims: stop aligning this dim
            i += 1
    return mapping


def _carry(in_spec, in_shape, out_shape) -> tuple:
    """Carry a spec through a dim-preserving shape change via
    `_align_dims`."""
    m = _align_dims(in_shape, out_shape)
    return dedupe(tuple(in_spec[i] if i is not None else None for i in m))


def _reshape_map(in_shape, out_shape, in_spec) -> tuple:
    """Propagate through reshape by factor chunks: between chunk
    boundaries where cumulative products agree, a 1:1 dim keeps its
    entry; a split dim hands its axes to the chunk's FIRST (major)
    output dim; merged dims hand the FIRST input dim's axes over."""
    if _numel(in_shape) != _numel(out_shape):
        return (None,) * len(out_shape)
    out = [None] * len(out_shape)
    i = j = 0
    while i < len(in_shape) and j < len(out_shape):
        i2, j2 = i + 1, j + 1
        pi, pj = int(in_shape[i]), int(out_shape[j])
        while pi != pj:
            if pi < pj:
                if i2 >= len(in_shape):
                    return tuple(out)
                pi *= int(in_shape[i2])
                i2 += 1
            else:
                if j2 >= len(out_shape):
                    return tuple(out)
                pj *= int(out_shape[j2])
                j2 += 1
        # chunk [i, i2) -> [j, j2)
        if i2 - i == 1 and j2 - j == 1:
            out[j] = in_spec[i]
        else:
            # split/merge chunk: the first input dim's axes go to the
            # chunk's major output dim (divisibility is the
            # partitioner's problem — it pads uneven shards)
            axes = _axes(in_spec[i])
            if axes:
                out[j] = axes if len(axes) > 1 else axes[0]
        i, j = i2, j2
    return dedupe(tuple(out))


# --------------------------------------------------------------------------
# Rule classes
# --------------------------------------------------------------------------
def elementwise_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Broadcast-aligned merge: each output dim takes the meet of every
    input dim broadcast onto it (right-aligned)."""
    out_shape = out_shapes[0] if out_shapes else ()
    r = len(out_shape)
    cand = (None,) * r
    for spec, shape in zip(in_specs, in_shapes):
        off = r - len(shape)
        lifted = [None] * r
        for d, e in enumerate(spec):
            od = d + off
            if 0 <= od < r and int(shape[d]) == int(out_shape[od]) \
                    and int(shape[d]) != 1:
                lifted[od] = e
        cand = meet(cand, tuple(lifted))
    outs = [cand if tuple(s) == tuple(out_shape)
            else _carry(cand, out_shape, s) for s in out_shapes]
    # inputs aligned back down from the merged spec
    resolved = []
    for spec, shape in zip(in_specs, in_shapes):
        off = r - len(shape)
        resolved.append(dedupe(tuple(
            cand[d + off] if int(shape[d]) == int(out_shape[d + off])
            and int(shape[d]) != 1 else None
            for d in range(len(shape)))))
    return SpmdResult(out_specs=outs, in_specs=resolved)


def matmul_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """(…, m, k) @ (…, k, n) — batch dims merge; m from x, n from y;
    a shared contracting-axis sharding stays internal (the partitioner
    emits the reduce). Orientation (transpose_x/y) is recovered from
    shapes since the lowering closes over the flags."""
    if len(in_specs) < 2 or len(in_shapes[0]) < 1 or len(in_shapes[1]) < 1:
        return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])
    a_shape, b_shape = in_shapes[0], in_shapes[1]
    a_spec, b_spec = in_specs[0], in_specs[1]
    out_shape = out_shapes[0]
    if len(out_shape) < 1:
        return SpmdResult(out_specs=[()])
    m = out_shape[-2] if len(out_shape) >= 2 else 1
    n = out_shape[-1]
    # locate m among a's (last two) dims, n among b's
    def _pick(shape, spec, want, prefer_last):
        if len(shape) == 1:
            # a 1-D operand IS the contraction (matvec/vecmat): its
            # only dim never supplies m or n, even when the extents
            # coincide
            return None
        d_last, d_prev = int(shape[-1]), int(shape[-2])
        if prefer_last:  # n: standard layout has it last
            if d_last == int(want):
                return spec[-1]
            if d_prev == int(want):
                return spec[-2]
        else:            # m: standard layout has it second-to-last
            if d_prev == int(want):
                return spec[-2]
            if d_last == int(want):
                return spec[-1]
        return None
    m_entry = _pick(a_shape, a_spec, m, prefer_last=False)
    n_entry = _pick(b_shape, b_spec, n, prefer_last=True)
    # contracted dim: whichever of each operand's trailing dims did NOT
    # supply m/n is k — a sharded k makes the output reduce-pending
    # (Partial) over those axes
    partial = set()
    for shape, spec, picked, prefer_last in (
            (a_shape, a_spec, m_entry, False),
            (b_shape, b_spec, n_entry, True)):
        if len(shape) == 1:
            # 1-D operand: its whole extent is contracted
            partial.update(_axes(spec[0]))
            continue
        # the trailing dim not picked as m/n is the contraction
        if prefer_last:
            k_entry = spec[-2] if int(shape[-1]) == int(n) \
                and picked == spec[-1] else spec[-1]
        else:
            k_entry = spec[-1] if int(shape[-2]) == int(m) \
                and picked == spec[-2] else spec[-2]
        partial.update(_axes(k_entry))
    batch = list((None,) * (len(out_shape) - 2))
    # batch dims: right-aligned merge of the operands' batch prefixes
    for spec, shape in ((a_spec, a_shape), (b_spec, b_shape)):
        bdims = len(shape) - 2
        off = len(batch) - bdims
        if bdims > 0 and off >= 0:
            lifted = [None] * len(batch)
            for d in range(bdims):
                if int(shape[d]) == int(out_shape[off + d]):
                    lifted[off + d] = spec[d]
            batch = list(meet(tuple(batch), tuple(lifted)))
    out = tuple(batch) + ((m_entry,) if len(out_shape) >= 2 else ()) \
        + (n_entry,)
    out = dedupe(out[:len(out_shape)])
    if len(in_specs) > 2:  # bias rides the n dim
        bias_spec = dedupe((out[-1],)) if len(in_shapes[2]) == 1 \
            else (None,) * len(in_shapes[2])
        resolved = [None, None, bias_spec] + [None] * (len(in_specs) - 3)
    else:
        resolved = [None] * len(in_specs)
    # a contracted sharded axis is reduce-pending even when the output
    # also uses it for a kept dim (col-split W consuming a
    # contraction-sharded x: the partitioner reduce-scatters — the
    # collective is real either way)
    pend = tuple(sorted(partial))
    return SpmdResult(out_specs=[out if tuple(s) == tuple(out_shape)
                                 else (None,) * len(s)
                                 for s in out_shapes],
                      in_specs=resolved,
                      out_partial=[pend if tuple(s) == tuple(out_shape)
                                   else () for s in out_shapes])


def parse_einsum_equation(equation: str, n_operands: int,
                          in_shapes=None):
    """``"nec,nh->ech"`` -> (input terms, output term) as label lists,
    or None when the equation cannot be resolved statically (ellipsis,
    operand/term mismatch). Implicit output (no ``->``) follows the
    einsum convention: labels appearing exactly once, alphabetical."""
    eq = equation.replace(" ", "")
    if "." in eq:          # ellipsis: rank-dependent, punt to heuristics
        return None
    if "->" in eq:
        lhs, rhs = eq.split("->", 1)
    else:
        lhs, rhs = eq, None
    terms = lhs.split(",")
    if len(terms) != n_operands:
        return None
    if in_shapes is not None:
        for t, s in zip(terms, in_shapes):
            if len(t) != len(s):
                return None
    if rhs is None:
        counts: Dict[str, int] = {}
        for t in terms:
            for c in t:
                counts[c] = counts.get(c, 0) + 1
        rhs = "".join(sorted(c for c, n in counts.items() if n == 1))
    return [list(t) for t in terms], list(rhs)


def einsum_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """General einsum propagation from the ``equation`` attr: each
    label's placement is the meet of every operand dim carrying it;
    output dims read the label map; labels contracted away (absent from
    the output) whose dims were sharded make the output **Partial**
    over those axes — the MoE dispatch/combine einsums
    (``nec,nh->ech`` / ``nec,ech->nh``) and megatron-style sharded
    contractions all resolve without replicating. Inputs are
    constrained back to the merged label map. Falls back to the old
    batch-style heuristic when no equation is recorded (pre-round-16
    traces) or the equation is rank-dynamic (ellipsis)."""
    eq = (attrs or {}).get("equation")
    parsed = parse_einsum_equation(eq, len(in_specs), in_shapes) \
        if isinstance(eq, str) else None
    if parsed is None:
        if (len(in_specs) == 2 and out_shapes
                and len(in_shapes[0]) == len(in_shapes[1])
                == len(out_shapes[0])):
            return elementwise_rule(in_specs, in_shapes, attrs,
                                    out_shapes)
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    terms, out_term = parsed
    # label -> merged placement entry (meet over every occurrence)
    label: Dict[str, object] = {}
    for term, spec in zip(terms, in_specs):
        for c, e in zip(term, spec):
            label[c] = meet((label[c],), (e,))[0] if c in label else e
    out_shape = out_shapes[0] if out_shapes else ()
    if len(out_term) != len(out_shape):
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    out = dedupe(tuple(label.get(c) for c in out_term))
    # contracted labels with sharded dims -> reduce-pending axes (kept
    # even when an output dim reuses the axis: the reduce-scatter is
    # still a real collective)
    pend = set()
    for c, e in label.items():
        if c not in out_term:
            pend.update(_axes(e))
    pend_t = tuple(sorted(pend))
    resolved = [dedupe(tuple(label.get(c) for c in term))
                for term in terms]
    return SpmdResult(
        out_specs=[out if tuple(s) == tuple(out_shape)
                   else (None,) * len(s) for s in out_shapes],
        in_specs=resolved,
        out_partial=[pend_t if tuple(s) == tuple(out_shape) else ()
                     for s in out_shapes])


def conv_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """NCHW x (Cout, Cin/g, kh, kw): batch from x dim0, out-channels
    from w dim0, spatial replicated (halo exchange is the partitioner's
    call)."""
    out = list((None,) * len(out_shapes[0]))
    if in_specs and in_shapes and len(in_shapes[0]) >= 1:
        out[0] = in_specs[0][0]
    if len(in_specs) > 1 and len(in_shapes[1]) >= 1 and len(out) >= 2:
        out[1] = in_specs[1][0]
    out = dedupe(tuple(out))
    return SpmdResult(out_specs=[out if len(s) == len(out)
                                 else (None,) * len(s)
                                 for s in out_shapes])


def attention_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """q/k/v (B, S, H, D): the output rides q's placement (batch over
    data, heads over tp); k/v are constrained to q's layout on the dims
    whose sizes match (kv seq length may differ)."""
    if not in_specs:
        return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])
    q_spec, q_shape = in_specs[0], in_shapes[0]
    outs = []
    for s in out_shapes:
        outs.append(q_spec if tuple(s) == tuple(q_shape)
                    else _carry(q_spec, q_shape, s))
    resolved: List[Optional[tuple]] = [None]
    for spec, shape in zip(in_specs[1:], in_shapes[1:]):
        if len(shape) == len(q_shape):
            resolved.append(dedupe(tuple(
                q_spec[d] if int(shape[d]) == int(q_shape[d]) else None
                for d in range(len(shape)))))
        else:
            resolved.append(None)
    return SpmdResult(out_specs=outs, in_specs=resolved)


def norm_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """batch/group/instance norm: the activation's spec passes
    through; scale/bias/stats stay replicated."""
    x_spec = in_specs[0] if in_specs else ()
    x_shape = in_shapes[0] if in_shapes else ()
    outs = [x_spec if tuple(s) == tuple(x_shape)
            else _carry(x_spec, x_shape, s) for s in out_shapes]
    resolved = [None] + [normalize(None, len(s)) for s in in_shapes[1:]]
    return SpmdResult(out_specs=outs, in_specs=resolved)


def layer_norm_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """layer/rms norm: statistics reduce over the LAST (feature) dim —
    a sharding there forces a gather, so the rule constrains the input
    feature dim replicated and carries only the leading dims' placement
    through. Scale/bias stay replicated."""
    x_spec = in_specs[0] if in_specs else ()
    x_shape = in_shapes[0] if in_shapes else ()
    pinned = tuple(x_spec[:-1]) + (None,) if x_spec else x_spec
    outs = [pinned if tuple(s) == tuple(x_shape)
            else _carry(pinned, x_shape, s) for s in out_shapes]
    resolved = [pinned if x_spec and x_spec[-1] is not None else None]
    resolved += [normalize(None, len(s)) for s in in_shapes[1:]]
    return SpmdResult(out_specs=outs, in_specs=resolved)


def rope_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Rotary embedding: elementwise over q/k with broadcast cos/sin —
    every output keeps its corresponding input's placement."""
    outs = []
    for i, s in enumerate(out_shapes):
        if i < len(in_specs) and tuple(in_shapes[i]) == tuple(s):
            outs.append(in_specs[i])
        elif in_specs and tuple(in_shapes[0]) == tuple(s):
            outs.append(in_specs[0])
        else:
            outs.append((None,) * len(s))
    return SpmdResult(out_specs=outs)


def reduction_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Reduced dims disappear (or become 1 under keepdim) and lose their
    axes; kept dims carry through — recovered by the size walk."""
    if not in_specs:
        return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])
    x_spec, x_shape = in_specs[0], in_shapes[0]
    return SpmdResult(out_specs=[_carry(x_spec, x_shape, s)
                                 for s in out_shapes])


def reshape_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    x_spec, x_shape = in_specs[0], in_shapes[0]
    return SpmdResult(out_specs=[_reshape_map(x_shape, s, x_spec)
                                 for s in out_shapes])


def transpose_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Permutation recovered from attrs['perm'] when present, else from
    unique dim sizes; ambiguous (repeated-size) dims replicate."""
    x_spec, x_shape = in_specs[0], in_shapes[0]
    out_shape = out_shapes[0]
    perm = (attrs or {}).get("perm")
    if perm is not None and len(perm) == len(out_shape):
        out = tuple(x_spec[int(p)] for p in perm)
        return SpmdResult(out_specs=[dedupe(out)])
    sizes = list(x_shape)
    out = []
    for od in out_shape:
        matches = [i for i, s in enumerate(sizes) if s == od]
        if len(matches) == 1:
            out.append(x_spec[matches[0]])
        else:
            out.append(None)
    return SpmdResult(out_specs=[dedupe(tuple(out))]
                      + [(None,) * len(s) for s in out_shapes[1:]])


def concat_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Meet of the inputs; the concatenated dim (size grew) replicates."""
    out_shape = out_shapes[0]
    cand = (None,) * len(out_shape)
    for spec, shape in zip(in_specs, in_shapes):
        if len(shape) != len(out_shape):
            continue
        lifted = tuple(
            spec[d] if int(shape[d]) == int(out_shape[d]) else None
            for d in range(len(shape)))
        cand = meet(cand, lifted)
    return SpmdResult(out_specs=[cand])


def split_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Each chunk keeps the input placement; the split dim keeps its
    axis only when every chunk still divides it cleanly (heuristic:
    keep — the partitioner pads otherwise)."""
    x_spec, x_shape = in_specs[0], in_shapes[0]
    outs = []
    for s in out_shapes:
        if len(s) == len(x_shape):
            # every dim — including the split one — keeps its axes (the
            # documented "heuristic: keep"; the partitioner pads a chunk
            # that no longer divides evenly)
            outs.append(dedupe(tuple(x_spec[:len(s)])))
        else:
            outs.append(_carry(x_spec, x_shape, s))
    return SpmdResult(out_specs=outs)


def stack_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """New leading stack dim replicates; the rest is the meet of the
    inputs shifted right."""
    out_shape = out_shapes[0]
    cand = (None,) * len(out_shape)
    for spec, shape in zip(in_specs, in_shapes):
        if len(shape) != len(out_shape) - 1:
            continue
        cand = meet(cand, (None,) + tuple(spec))
    return SpmdResult(out_specs=[cand])


def embedding_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """ids(…) x table(V, H) -> out(…, H): ids dims keep their placement,
    the feature dim takes the table's; a vocab-sharded table contributes
    a partial sum the partitioner reduces."""
    if len(in_specs) < 2:
        return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])
    ids_spec, table_spec = in_specs[0], in_specs[1]
    out_shape = out_shapes[0]
    n_ids = len(in_shapes[0])
    out = list((None,) * len(out_shape))
    for d in range(min(n_ids, len(out_shape) - 1)):
        out[d] = ids_spec[d]
    if len(out_shape) >= 1 and len(table_spec) >= 2:
        out[-1] = table_spec[-1]
    out = dedupe(tuple(out))
    # vocab-sharded table: each shard contributes masked rows — the
    # lookup's output is reduce-pending over the vocab axes
    used = {ax for e in out for ax in _axes(e)}
    pend = tuple(sorted(set(_axes(table_spec[0])) - used)) \
        if len(table_spec) >= 2 else ()
    return SpmdResult(out_specs=[out],
                      out_partial=[pend] + [()] * (len(out_shapes) - 1))


def embedding_bag_rule(in_specs, in_shapes, attrs,
                       out_shapes) -> SpmdResult:
    """ids(…, L) x table(V, H) -> out(…, H): like ``embedding_rule``
    but the pooled bag dim L disappears. Batch dims keep the ids'
    placement, the feature dim takes the table's; a vocab-sharded table
    pools only its resident rows per shard, so the output is
    reduce-pending over the vocab axes (the sharded-embedding lookup's
    single deduped exchange IS that pending reduce)."""
    if len(in_specs) < 2:
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    ids_spec, table_spec = in_specs[0], in_specs[1]
    out_shape = out_shapes[0]
    out = list((None,) * len(out_shape))
    # ids dims minus the pooled last one carry to the output's lead dims
    for d in range(min(len(in_shapes[0]) - 1, len(out_shape) - 1)):
        out[d] = ids_spec[d]
    if len(out_shape) >= 1 and len(table_spec) >= 2:
        out[-1] = table_spec[-1]
    out = dedupe(tuple(out))
    used = {ax for e in out for ax in _axes(e)}
    pend = tuple(sorted(set(_axes(table_spec[0])) - used)) \
        if len(table_spec) >= 2 else ()
    return SpmdResult(out_specs=[out],
                      out_partial=[pend] + [()] * (len(out_shapes) - 1))


def scatter_add_rule(in_specs, in_shapes, attrs,
                     out_shapes) -> SpmdResult:
    """dest(V, …) + index(N) + updates(N, …) -> out(V, …): row
    accumulation keeps the DESTINATION's placement — a vocab-sharded
    dest accepts only its resident rows per shard (the sharded-embedding
    backward's table-grad scatter). Trailing dims meet with the updates'
    so a feature-dim disagreement replicates instead of mis-sharding."""
    if not in_specs:
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    dest_spec = tuple(in_specs[0])
    out = dest_spec
    if (len(in_specs) >= 3 and len(in_specs[2]) == len(dest_spec)
            and len(dest_spec) >= 1):
        upd_spec = tuple(in_specs[2])
        out = (dest_spec[0],) + meet(dest_spec[1:], upd_spec[1:])
    out = dedupe(out)
    outs = [out if tuple(s) == tuple(in_shapes[0])
            else _carry(out, in_shapes[0], s) for s in out_shapes]
    return SpmdResult(out_specs=outs)


def gather_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Value-dependent addressing: output dims that still match the
    source carry through, gathered dims replicate."""
    if not in_specs:
        return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])
    x_spec, x_shape = in_specs[0], in_shapes[0]
    return SpmdResult(out_specs=[_carry(x_spec, x_shape, s)
                                 for s in out_shapes])


def softmax_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    x_spec, x_shape = in_specs[0], in_shapes[0]
    outs = [x_spec if tuple(s) == tuple(x_shape)
            else _carry(x_spec, x_shape, s) for s in out_shapes]
    return SpmdResult(out_specs=outs)


def cross_entropy_rule(in_specs, in_shapes, attrs,
                       out_shapes) -> SpmdResult:
    """logits(N, C) + labels(N) -> loss: batch dims carry, the class
    dim and any reduced output replicate. ``fused_linear_cross_entropy``
    has hidden rows (..., H) first and labels (...): their leading dims are
    the batch dims, H is contracted inside the op like a class dim, a
    per-row loss carries the rows' axes and "mean" / "sum" replicate; the
    op cuts its own rows over the data axes (a ``shard_map`` over ``dp`` /
    ``sharding`` on the first dim), which is the placement this rule
    propagates."""
    if not in_specs:
        return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])
    lg_spec, lg_shape = in_specs[0], in_shapes[0]
    outs = []
    for s in out_shapes:
        if not s:
            outs.append(())
        else:
            outs.append(_carry(lg_spec[:-1] + (None,), lg_shape, s))
    return SpmdResult(out_specs=outs)


def getitem_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Basic indexing: full dims carry their axes, sliced/dropped dims
    replicate (size walk)."""
    x_spec, x_shape = in_specs[0], in_shapes[0]
    return SpmdResult(out_specs=[_carry(x_spec, x_shape, s)
                                 for s in out_shapes])


def pooling_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """N/C dims carry; pooled spatial dims replicate."""
    x_spec, x_shape = in_specs[0], in_shapes[0]
    out = list((None,) * len(out_shapes[0]))
    for d in range(min(2, len(out), len(x_spec))):
        if d < len(x_shape) and int(x_shape[d]) == int(out_shapes[0][d]):
            out[d] = x_spec[d]
    return SpmdResult(out_specs=[dedupe(tuple(out))]
                      + [(None,) * len(s) for s in out_shapes[1:]])


def creation_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Freshly created values are replicated until a consumer shards
    them."""
    return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])


def scan_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """cumsum/cumprod-style: shape-preserving, spec passes through."""
    x_spec, x_shape = in_specs[0], in_shapes[0]
    outs = [x_spec if tuple(s) == tuple(x_shape)
            else _carry(x_spec, x_shape, s) for s in out_shapes]
    return SpmdResult(out_specs=outs)


def broadcast_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """expand/broadcast_to/tile: right-aligned dims whose size is
    unchanged carry their axes; expanded/tiled dims replicate."""
    x_spec, x_shape = in_specs[0], in_shapes[0]
    out_shape = out_shapes[0]
    off = len(out_shape) - len(x_shape)
    out = [None] * len(out_shape)
    for d in range(len(x_shape)):
        if off + d >= 0 and int(x_shape[d]) == int(out_shape[off + d]) \
                and int(x_shape[d]) != 1:
            out[off + d] = x_spec[d]
    return SpmdResult(out_specs=[dedupe(tuple(out))]
                      + [(None,) * len(s) for s in out_shapes[1:]])


def pad_rule(in_specs, in_shapes, attrs, out_shapes) -> SpmdResult:
    """Padded dims replicate (the partitioner would have to reshard a
    grown dim anyway); untouched dims carry."""
    x_spec, x_shape = in_specs[0], in_shapes[0]
    out_shape = out_shapes[0]
    if len(out_shape) != len(x_shape):
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    out = tuple(x_spec[d] if int(x_shape[d]) == int(out_shape[d]) else None
                for d in range(len(x_shape)))
    return SpmdResult(out_specs=[dedupe(out)]
                      + [(None,) * len(s) for s in out_shapes[1:]])


def fused_residual_norm_rule(in_specs, in_shapes, attrs,
                             out_shapes) -> SpmdResult:
    """(x, residual[, w][, b]) -> (normed, summed): both outputs carry
    the meet of x and residual; norm params stay replicated."""
    if len(in_specs) < 2:
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    cand = meet(in_specs[0], in_specs[1]) \
        if len(in_shapes[0]) == len(in_shapes[1]) else in_specs[0]
    x_shape = in_shapes[0]
    outs = [cand if tuple(s) == tuple(x_shape)
            else _carry(cand, x_shape, s) for s in out_shapes]
    resolved = [None, None] + [normalize(None, len(s))
                               for s in in_shapes[2:]]
    return SpmdResult(out_specs=outs, in_specs=resolved)


def fused_norm_linear_rule(in_specs, in_shapes, attrs,
                           out_shapes) -> SpmdResult:
    """(x(…, K), W(K, N)[, bias][, norm params]) -> (…, N): batch dims
    ride x, the feature dim rides W's output axis (a TP column split
    propagates); the contracting dim stays internal."""
    if len(in_specs) < 2 or not out_shapes:
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    x_spec, w_spec = in_specs[0], in_specs[1]
    out_shape = out_shapes[0]
    out = list(x_spec[:len(out_shape) - 1]) \
        + [None] * (len(out_shape) - len(x_spec))
    out = out[:len(out_shape) - 1]
    out.append(w_spec[-1] if len(w_spec) >= 2 else None)
    out = dedupe(tuple(out))
    resolved: List[Optional[tuple]] = [None, None]
    for spec, shape in zip(in_specs[2:], in_shapes[2:]):
        # 1-D bias rides the output feature axis; norm params replicate
        if len(shape) == 1 and int(shape[0]) == int(out_shape[-1]):
            resolved.append(dedupe((out[-1],)))
        else:
            resolved.append(normalize(None, len(shape)))
    return SpmdResult(out_specs=[out if tuple(s) == tuple(out_shape)
                                 else (None,) * len(s)
                                 for s in out_shapes],
                      in_specs=resolved)


def fused_rope_proj_rule(in_specs, in_shapes, attrs,
                         out_shapes) -> SpmdResult:
    """(x(B, S, K), W(K, H*D)[, bias]) -> (B, S, H, D): batch/seq ride
    x; a feature-split W shards the heads axis (head_dim is the minor
    factor of the reshape, so the axis lands on dim 2)."""
    if len(in_specs) < 2 or not out_shapes or len(out_shapes[0]) != 4:
        return SpmdResult(out_specs=[(None,) * len(s)
                                     for s in out_shapes])
    x_spec, w_spec = in_specs[0], in_specs[1]
    out = (x_spec[0] if len(x_spec) > 0 else None,
           x_spec[1] if len(x_spec) > 1 else None,
           w_spec[-1] if len(w_spec) >= 2 else None, None)
    out = dedupe(out)
    return SpmdResult(out_specs=[out]
                      + [(None,) * len(s) for s in out_shapes[1:]])


def unconstrained_rule(in_specs, in_shapes, attrs,
                       out_shapes) -> SpmdResult:
    """A real (counted) rule that imposes nothing — for ops whose
    sharding the partitioner must own entirely (decompositions, host
    boundaries)."""
    return SpmdResult(out_specs=[(None,) * len(s) for s in out_shapes])


# --------------------------------------------------------------------------
# Name / category tables (mirrors costmodel.COST_MODELS layout)
# --------------------------------------------------------------------------
#: op name -> rule. The closed vocabulary the coverage audit pivots on.
SPMD_RULES: Dict[str, Callable] = {}


def _fill_rules():
    for name in ("matmul", "mm", "bmm", "addmm", "linear", "fc",
                 "matmul_v2", "inner", "outer", "mv"):
        SPMD_RULES[name] = matmul_rule
    SPMD_RULES["einsum"] = einsum_rule
    for name in ("conv2d", "conv1d", "conv3d", "conv2d_transpose",
                 "conv1d_transpose", "conv3d_transpose",
                 "depthwise_conv2d"):
        SPMD_RULES[name] = conv_rule
    for name in ("flash_attention", "scaled_dot_product_attention",
                 "block_multihead_attention", "paged_attention",
                 "flash_attn_unpadded", "ring_flash_attention",
                 "memory_efficient_attention"):
        SPMD_RULES[name] = attention_rule
    for name in ("layer_norm", "rms_norm", "fused_layer_norm",
                 "fused_rms_norm"):
        SPMD_RULES[name] = layer_norm_rule
    for name in ("batch_norm", "group_norm", "instance_norm",
                 "local_response_norm", "spectral_norm", "weight_norm"):
        SPMD_RULES[name] = norm_rule
    for name in ("rotary_embedding", "fused_rotary_position_embedding",
                 "fused_rope"):
        SPMD_RULES[name] = rope_rule
    for name in ("sum", "mean", "max", "min", "prod", "reduce_sum",
                 "logsumexp", "argmax", "argmin", "norm", "all", "any",
                 "amax", "amin", "nanmean", "nansum", "count_nonzero",
                 "median", "nanmedian", "quantile", "std", "var"):
        SPMD_RULES[name] = reduction_rule
    for name in ("reshape", "reshape_", "view", "flatten",
                 "flatten_contiguous_range"):
        SPMD_RULES[name] = reshape_rule
    for name in ("transpose", "transpose_", "swapaxes", "moveaxis", "t",
                 "matrix_transpose"):
        SPMD_RULES[name] = transpose_rule
    SPMD_RULES["concat"] = concat_rule
    for name in ("split", "chunk", "unbind", "tensor_split", "hsplit",
                 "vsplit", "dsplit"):
        SPMD_RULES[name] = split_rule
    for name in ("stack", "vstack", "hstack", "dstack"):
        SPMD_RULES[name] = stack_rule
    for name in ("squeeze", "squeeze_", "unsqueeze", "unsqueeze_",
                 "expand_dims"):
        SPMD_RULES[name] = reshape_rule
    SPMD_RULES["embedding"] = embedding_rule
    SPMD_RULES["embedding_bag"] = embedding_bag_rule
    SPMD_RULES["scatter_add"] = scatter_add_rule
    for name in ("gather", "gather_nd", "index_select", "take_along_axis",
                 "index_sample", "take"):
        SPMD_RULES[name] = gather_rule
    for name in ("softmax", "log_softmax", "softmax_", "gumbel_softmax"):
        SPMD_RULES[name] = softmax_rule
    for name in ("cross_entropy", "softmax_with_cross_entropy",
                 "fused_linear_cross_entropy", "nll_loss",
                 "binary_cross_entropy", "binary_cross_entropy_with_logits",
                 "bce_with_logits", "sigmoid_cross_entropy"):
        SPMD_RULES[name] = cross_entropy_rule
    for name in ("getitem", "slice", "strided_slice", "index",
                 "masked_select"):
        SPMD_RULES[name] = getitem_rule
    for name in ("max_pool2d", "avg_pool2d", "max_pool1d", "avg_pool1d",
                 "max_pool3d", "avg_pool3d", "adaptive_avg_pool2d",
                 "adaptive_max_pool2d", "adaptive_avg_pool1d"):
        SPMD_RULES[name] = pooling_rule
    for name in ("cumsum", "cumprod", "cummax", "cummin"):
        SPMD_RULES[name] = scan_rule
    for name in ("dropout", "dropout_", "alpha_dropout", "relu", "gelu",
                 "silu", "swish", "tanh", "sigmoid", "cast", "scale",
                 "clip", "where", "add", "subtract", "multiply", "divide",
                 "maximum", "minimum", "add_n", "exp", "log", "sqrt",
                 "rsqrt", "square", "abs", "pow", "floor", "ceil", "sign",
                 "tril", "triu", "erf", "sin", "cos", "softplus", "log1p",
                 "leaky_relu", "elu", "selu", "celu", "hardswish",
                 "hardsigmoid", "hardtanh", "relu6", "mish", "prelu",
                 # comparison / logical / bitwise — all elementwise
                 "equal", "not_equal", "greater_than", "less_than",
                 "greater_equal", "less_equal", "logical_and",
                 "logical_or", "logical_not", "logical_xor",
                 "bitwise_and", "bitwise_or", "bitwise_xor",
                 "bitwise_not", "isnan", "isinf", "isfinite", "isclose",
                 # transcendental tail
                 "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh",
                 "asinh", "acosh", "atanh", "expm1", "log2", "log10",
                 "reciprocal", "round", "trunc", "frac", "fmod",
                 "remainder", "mod", "floor_divide", "floor_mod",
                 "heaviside", "hypot", "copysign", "lerp", "addcmul",
                 "addcdiv", "lgamma", "digamma", "erfinv", "i0", "i1",
                 "logaddexp", "logaddexp2", "nan_to_num", "deg2rad",
                 "rad2deg", "angle", "conj", "real", "imag", "sgn",
                 "softshrink", "hardshrink", "tanhshrink", "softsign",
                 "thresholded_relu", "log_sigmoid", "rrelu", "stanh",
                 "logit", "multiply_", "divide_", "subtract_", "add_",
                 "clip_", "scale_", "relu_", "sigmoid_", "tanh_",
                 "exp_", "sqrt_", "rsqrt_", "floor_", "ceil_",
                 "reciprocal_", "round_", "fill", "fill_"):
        SPMD_RULES[name] = elementwise_rule
    for name in ("expand", "expand_as", "broadcast_to", "tile",
                 "repeat_interleave"):
        SPMD_RULES[name] = broadcast_rule
    SPMD_RULES["pad"] = pad_rule
    for name in ("flip", "roll", "rot90"):
        SPMD_RULES[name] = pad_rule  # shape-preserving permute class
    # fused ops (compile/fusion rewrite targets): first-class rules so
    # round-13 propagation sees through the rewrite — a fused program
    # must report zero spmd fallbacks (ISSUE 10 acceptance)
    SPMD_RULES["fused_bias_act"] = elementwise_rule
    SPMD_RULES["fused_residual_norm"] = fused_residual_norm_rule
    SPMD_RULES["fused_norm_linear"] = fused_norm_linear_rule
    SPMD_RULES["fused_rope_proj"] = fused_rope_proj_rule
    for name in ("zeros", "ones", "full", "arange", "linspace", "empty",
                 "eye", "zeros_like", "ones_like", "full_like",
                 "empty_like", "rand", "randn", "randint", "uniform",
                 "normal", "randperm", "tril_indices", "triu_indices",
                 "meshgrid", "diag", "diagflat", "one_hot"):
        SPMD_RULES[name] = creation_rule


_fill_rules()

#: category fallback when an op has no named rule. Only categories whose
#: members genuinely share a propagation shape are listed — everything
#: else is replicate-and-warn, which the coverage audit surfaces.
CATEGORY_RULES: Dict[str, Callable] = {
    "math": elementwise_rule,
    "activation": elementwise_rule,
    "norm": norm_rule,
    "reduction": reduction_rule,
    "loss": cross_entropy_rule,
    "conv": conv_rule,
    "attention": attention_rule,
    "pooling": pooling_rule,
    "creation": creation_rule,
    "random": creation_rule,
    "indexing": gather_rule,
    "search": reduction_rule,
    # inplace variants are overwhelmingly elementwise (add_, relu_, …);
    # the named table already pins the shape-changing exceptions
    # (reshape_, transpose_, squeeze_, …) to their real classes
    "inplace": elementwise_rule,
    # fused ops carry NAMED rules (table above); the category fallback
    # only covers future fused registrations that miss the audit gate
    "fusion": elementwise_rule,
}


def attach_spmd_rules() -> int:
    """Attach the per-op-class rules to the live registry
    (``OpDef.spmd_rule``). Idempotent; a rule set by a
    register(..., spmd_rule=) site wins. Returns the number of ops now
    carrying a NAMED rule (category fallbacks stay dynamic so the audit
    can tell the tiers apart)."""
    from ...ops import registry as reg

    n = 0
    for name, od in reg.OPS.items():
        if od.spmd_rule is None:
            fn = SPMD_RULES.get(name)
            if fn is not None:
                od.spmd_rule = fn
        if od.spmd_rule is not None:
            n += 1
    return n


def rule_for(op_name: str):
    """Resolve an op's rule: (rule, tier) with tier one of 'rule',
    'category-fallback', 'replicate-warn'."""
    category = None
    try:
        from ...ops import registry as reg
        od = reg.OPS.get(op_name)
        if od is not None:
            if od.spmd_rule is not None:
                return od.spmd_rule, "rule"
            category = od.category
    except Exception:
        pass
    fn = SPMD_RULES.get(op_name)
    if fn is not None:
        return fn, "rule"
    if category is not None:
        fn = CATEGORY_RULES.get(category)
        if fn is not None:
            return fn, "category-fallback"
    return None, "replicate-warn"


def rule_class_of(rule: Callable) -> str:
    """Human name of a rule's op class (for the coverage audit)."""
    return getattr(rule, "__name__", str(rule)).replace("_rule", "")
