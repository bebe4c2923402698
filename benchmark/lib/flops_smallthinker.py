"""Operations and bytes a ``smallthinker`` configuration's training step
needs, computed from shapes (conventions as ``lib/flops.py``: a multiply-add
is 2 operations, embedding rows are gathers, attention is billed over the
keys a query sees, recomputed operations are never billed, forward +
backward is 3x forward). What is particular to this architecture:

* a layer's attention is billed over the (query, key) pairs INSIDE ITS MASK:
  a full layer's causal triangle ``S (S + 1) / 2``, a window layer's band
  ``W (W + 1) / 2 + (S - W) W`` (a query sees its own key and the ``W - 1``
  before it), never over the tiles a kernel runs;
* routed experts are billed for the (token, expert) pairs that LAND on the
  experts held here, ``moe_num_active_primary_experts x experts held /
  router_width`` a token on average (uniform random ids route evenly), and a
  reader that has the step's own counter bills the landed pairs it counted;
* the head is this chip's slice of the vocabulary (``vocab_size`` in the
  configuration), untied: its own matrix, the embedding a gather;
* a windowed flash call's least traffic is a causal call's: q, k, v, out
  (backward: dO and the gradients too) each read or written once, whatever
  part of K and V a query tile needs.
"""
from __future__ import annotations

from benchmark.lib import flops


def pairs_in_mask(seq: int, window=None) -> int:
    """(query, key) pairs one head attends to over ``seq`` positions: the
    causal triangle, or under a ``window`` the band (query ``i`` sees key
    ``j`` iff ``0 <= i - j < window``)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_window(cfg: dict, layer: int):
    """The layer's window, or None where it attends to every key."""
    return (cfg["sliding_window_size"]
            if cfg["sliding_window_layout"][layer] else None)


def attention_flops(cfg: dict, seq: int, layer: int) -> float:
    """One layer's attention a token: the four projections, and QK^T and PV
    over the pairs inside the layer's mask."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    proj = 2.0 * (h * nq + 2 * h * nkv + nq * h)
    pairs = pairs_in_mask(seq, layer_window(cfg, layer)) / seq
    return proj + 2 * 2 * nq * pairs


def expert_flops(cfg: dict) -> float:
    """One (token, expert) pair through one gated expert."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def pairs_landed_per_token(cfg: dict) -> float:
    return (cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"] / cfg["router_width"])


def moe_flops(cfg: dict) -> float:
    """Router and experts a token, the landed pairs under even routing."""
    return (2.0 * cfg["hidden_size"] * cfg["router_width"]
            + pairs_landed_per_token(cfg) * expert_flops(cfg))


def fwd_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward operations a trained token at sequence length ``seq``."""
    total = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]      # the head
    for i in range(cfg["num_hidden_layers"]):
        total += attention_flops(cfg, seq, i) + moe_flops(cfg)
    return total


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (2x forward): 3x forward."""
    return 3.0 * fwd_flops_per_token(cfg, seq)


def param_count(cfg: dict) -> int:
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    layer = (2 * h + h * nq + 2 * h * nkv + nq * h + h * cfg["router_width"]
             + cfg["moe_num_primary_experts"] * 3 * h
             * cfg["moe_ffn_hidden_size"])
    return 2 * cfg["vocab_size"] * h + h + cfg["num_hidden_layers"] * layer


# ------------------------------------------------- one (windowed) flash call
def flash_call_flops(kind: str, bh: int, seq: int, head_dim: int,
                     window=None) -> float:
    """Operations one flash-attention call needs over ``bh`` (batch x heads)
    sequences, billed over the pairs inside the mask: ``fwd`` two matmuls
    (QK^T, PV), ``dq`` three (QK^T, dO V^T, dS K), ``dkv`` two (dS^T Q,
    P^T dO), as ``lib/flops.py`` bills a causal call."""
    one = 2.0 * bh * pairs_in_mask(seq, window) * head_dim
    return {"fwd": 2, "dq": 3, "dkv": 2}[kind] * one


def flash_call_bytes(kind: str, bh: int, seq: int, head_dim: int,
                     itemsize: int = 2) -> float:
    """Least HBM traffic of one call, windowed or not: ``lib/flops.py``'s of
    a causal call (each operand read once, each result written once), since
    every key and value is some query's whatever the window."""
    return flops.flash_call_bytes(kind, bh, seq, head_dim, itemsize)
