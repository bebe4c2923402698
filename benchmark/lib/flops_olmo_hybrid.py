"""Operations and bytes an ``olmo_hybrid`` configuration's serving programs
need, computed from shapes (conventions as ``lib/flops.py``: a multiply-add
is 2 operations, embedding rows are gathers, causal attention is billed over
the keys it sees). What is particular to this architecture:

* a linear-attention layer's state (the convolution's window and the
  ``d_k x d_v`` float32 matrix a head) is read and written ONCE for every
  lane that decodes; a prefill chunk reads and writes one slot's;
* K/V of the cached tokens only in the full-attention layers, at the
  published 30 heads (the engine's pages hold 32: what the program pads to
  is not billed);
* every weight once a program call; the head is the whole vocabulary.
"""
from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def dims(cfg: dict) -> dict:
    """The widths the published keys imply (shared with the weight table)."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return {"head_dim": hd, "q": cfg["num_attention_heads"] * hd,
            "kv": cfg["num_key_value_heads"] * hd, "key": key,
            "value": value, "conv": 2 * key + value}


def counts(cfg: dict) -> dict:
    kinds = cfg["layer_types"]
    return {LINEAR: kinds.count(LINEAR), FULL: kinds.count(FULL)}


def linear_matmul_params(cfg: dict) -> int:
    """q / k / v / a / b / g projections and ``W_o``."""
    d, h = dims(cfg), cfg["hidden_size"]
    return (h * (2 * d["key"] + 2 * d["value"]
                 + 2 * cfg["linear_num_value_heads"]) + d["value"] * h)


def linear_small_params(cfg: dict) -> int:
    """Convolution taps, ``A_log``, ``dt_bias``, the output norm's scale."""
    return (dims(cfg)["conv"] * cfg["linear_conv_kernel_dim"]
            + 2 * cfg["linear_num_value_heads"]
            + cfg["linear_value_head_dim"])


def full_matmul_params(cfg: dict) -> int:
    d, h = dims(cfg), cfg["hidden_size"]
    return h * d["q"] + 2 * h * d["kv"] + d["q"] * h


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def param_count(cfg: dict) -> int:
    n, h, d = counts(cfg), cfg["hidden_size"], dims(cfg)
    per_block = mlp_params(cfg) + 2 * h             # two post-norms
    return (n[LINEAR] * (linear_matmul_params(cfg) + linear_small_params(cfg)
                         + per_block)
            + n[FULL] * (full_matmul_params(cfg) + d["q"] + d["kv"]
                         + per_block)
            + h + 2 * h * cfg["vocab_size"])


def state_bytes_per_slot_layer(cfg: dict, window_itemsize: int = 2) -> int:
    """A linear layer's carried state for one slot: the window in the
    model's dtype, the matrix state in float32."""
    return ((cfg["linear_conv_kernel_dim"] - 1) * dims(cfg)["conv"]
            * window_itemsize + rule_state_bytes(cfg))


def rule_state_bytes(cfg: dict) -> int:
    """The delta rule's matrix state of one slot and layer, float32."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * 4)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * dims(cfg)["kv"] * itemsize * counts(cfg)[FULL]


def weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Weights one program call streams: every layer and the head once
    (the embedding's rows are gathers); the small float32 leaves at 4."""
    n, h, d = counts(cfg), cfg["hidden_size"], dims(cfg)
    matmul = (n[LINEAR] * (linear_matmul_params(cfg)
                           + d["conv"] * cfg["linear_conv_kernel_dim"])
              + n[FULL] * full_matmul_params(cfg)
              + (n[LINEAR] + n[FULL]) * mlp_params(cfg)
              + h * cfg["vocab_size"])
    small = (n[LINEAR] * (2 * cfg["linear_num_value_heads"]
                          + cfg["linear_value_head_dim"])
             + n[FULL] * (d["q"] + d["kv"])
             + (n[LINEAR] + n[FULL]) * 2 * h + h)
    return matmul * itemsize + small * 4


def matmul_flops_per_token(cfg: dict) -> float:
    """Operations a token costs in the projections and MLPs (head, rule and
    attention scores excluded)."""
    n = counts(cfg)
    return 2.0 * (n[LINEAR] * linear_matmul_params(cfg)
                  + n[FULL] * full_matmul_params(cfg)
                  + (n[LINEAR] + n[FULL]) * mlp_params(cfg))


def rule_flops_per_token_layer(cfg: dict) -> float:
    """The recurrence itself for one token of one layer: ``S^T k`` (2 a
    cell), the decayed rank-one update (3 a cell), ``S^T q`` (2 a cell)."""
    cells = (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
             * cfg["linear_value_head_dim"])
    return 7.0 * cells


def scan_flops_per_token(cfg: dict) -> float:
    """Rule and convolution taps of every linear layer."""
    return counts(cfg)[LINEAR] * (
        rule_flops_per_token_layer(cfg)
        + 2.0 * cfg["linear_conv_kernel_dim"] * dims(cfg)["conv"])


# ------------------------------------------------------- the decode program
def decode_step_bytes(cfg: dict, lanes: int, cached_tokens: int) -> float:
    """Least HBM traffic of one decode step over ``lanes`` decoding slots
    whose caches hold ``cached_tokens`` tokens in all: weights once, state
    in and out once a decoding lane, K/V of the cached tokens in the full
    layers, the rows written (the new token's K and V)."""
    n = counts(cfg)
    state = 2 * lanes * n[LINEAR] * state_bytes_per_slot_layer(cfg)
    written = lanes * kv_bytes_per_token(cfg)
    return (weight_bytes(cfg) + state + written
            + cached_tokens * kv_bytes_per_token(cfg))


def decode_step_flops(cfg: dict, lanes: int, cached_tokens: int) -> float:
    return (lanes * (matmul_flops_per_token(cfg) + scan_flops_per_token(cfg)
                     + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
            + full_attn_decode_flops(cfg, cached_tokens))


# ------------------------------------------------------------ the rule alone
def rule_decode_bytes(cfg: dict, lanes: int, itemsize: int = 4) -> float:
    """What the rule's decode step cannot avoid moving in ONE linear layer:
    each decoding lane's matrix state in and out once, its ``q``, ``k``
    (``d_k`` a head) and ``v`` rows in and its ``o`` row out (``d_v`` a
    head), at ``itemsize`` (the rule runs in float32)."""
    d = dims(cfg)
    rows = (2 * d["key"] + 2 * d["value"]) * itemsize
    return lanes * (2 * rule_state_bytes(cfg) + rows)


def rule_decode_flops(cfg: dict, lanes: int) -> float:
    return lanes * rule_flops_per_token_layer(cfg)


# ----------------------------------------- the full layers' decode attention
def full_attn_decode_bytes(cfg: dict, lanes: int, cached_tokens: int,
                           itemsize: int = 2) -> float:
    """Least HBM traffic of a decode step's ``paged_decode_attn`` calls, one
    a full layer: K and V of every cached token once, a query row in and an
    output row out for every head of every decoding lane."""
    rows = 2 * lanes * dims(cfg)["q"] * itemsize * counts(cfg)[FULL]
    return cached_tokens * kv_bytes_per_token(cfg, itemsize) + rows


def full_attn_decode_flops(cfg: dict, cached_tokens: int) -> float:
    """QK^T and PV over the cached tokens, every full layer."""
    return 2.0 * 2 * dims(cfg)["q"] * counts(cfg)[FULL] * cached_tokens
