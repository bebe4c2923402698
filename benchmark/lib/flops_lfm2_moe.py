"""Operations and bytes an ``lfm2_moe`` configuration's training step needs,
computed from shapes (conventions as ``lib/flops.py``: a multiply-add is 2
operations, embedding rows are gathers, causal attention is billed over the
keys a query sees, recomputed operations are never billed, forward + backward
is 3x forward). What is particular to this architecture:

* routed experts are billed for the (token, expert) pairs that LAND on the
  experts held here, ``num_experts_per_tok * num_experts / router_width`` a
  token on average (uniform random ids route evenly), never for ``tokens x
  experts held``: a program that multiplies every token by every held expert
  does 8x this and is not credited for it;
* the gated short convolution is its two projections plus ``conv_L_cache``
  multiply-adds and two gates a channel;
* the head is this chip's slice of the vocabulary (``vocab_size`` in the
  configuration), tied to the embedding;
* a GROUPED matmul over the landed pairs (``jax.lax.ragged_dot`` and its two
  transposes) is billed from its group sizes: ``2 x pairs x in x out``
  operations whichever of the three it is; each pair's input row and output
  row once and each TOUCHED expert's matrix once, all at 2 bytes a number
  (the least: bfloat16 operands and results).
"""
from __future__ import annotations

CONV = "conv"      # ``layer_types``; anything else is full attention


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_operator_flops(cfg: dict) -> float:
    h = cfg["hidden_size"]
    return 2.0 * (h * 3 * h + h * h) + h * (2 * cfg["conv_L_cache"] + 2)


def attention_operator_flops(cfg: dict, seq: int) -> float:
    h, hd = cfg["hidden_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    proj = 2.0 * (h * nq + 2 * h * nkv + nq * h)
    return proj + 2 * 2 * nq * (seq + 1) / 2          # QK^T and PV, causal


def dense_mlp_flops(cfg: dict) -> float:
    return 2.0 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_flops(cfg: dict) -> float:
    """One (token, expert) pair through one SwiGLU expert."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_landed_per_token(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def moe_flops(cfg: dict, masked: bool = False) -> float:
    """Router and experts a token: the landed pairs, or with ``masked``
    what the product over every held expert executes."""
    pairs = cfg["num_experts"] if masked else pairs_landed_per_token(cfg)
    return (2.0 * cfg["hidden_size"] * cfg["router_width"]
            + pairs * expert_flops(cfg))


def fwd_flops_per_token(cfg: dict, seq: int, masked: bool = False) -> float:
    """Forward operations a trained token at sequence length ``seq``."""
    total = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]      # tied head
    for i in range(cfg["num_hidden_layers"]):
        total += (conv_operator_flops(cfg)
                  if cfg["layer_types"][i] == CONV
                  else attention_operator_flops(cfg, seq))
        total += (dense_mlp_flops(cfg) if i < cfg["num_dense_layers"]
                  else moe_flops(cfg, masked))
    return total


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (2x forward): 3x forward."""
    return 3.0 * fwd_flops_per_token(cfg, seq)


def expert_layer_share(cfg: dict, seq: int) -> float:
    """The routed layers' share of the billed operations."""
    sparse = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return sparse * moe_flops(cfg) / fwd_flops_per_token(cfg, seq)


def param_count(cfg: dict) -> int:
    h, hd = cfg["hidden_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    total = cfg["vocab_size"] * h + h
    for i in range(cfg["num_hidden_layers"]):
        total += 2 * h
        total += (3 * h * h + h * cfg["conv_L_cache"] + h * h
                  if cfg["layer_types"][i] == CONV
                  else h * nq + 2 * h * nkv + nq * h + 2 * hd)
        total += (3 * h * cfg["intermediate_size"]
                  if i < cfg["num_dense_layers"]
                  else cfg["num_experts"] * 3 * h
                  * cfg["moe_intermediate_size"]
                  + h * cfg["router_width"] + cfg["router_width"])
    return total


# ------------------------------------------------------- one grouped matmul
def grouped_call_flops(group_sizes, k_in: int, n_out: int) -> float:
    """``x[pairs, k_in] @ W[g, k_in, n_out]`` over the rows of each group,
    or either of its transposes (``g W^T``; ``x^T g`` a group): the same
    ``2 x pairs x k_in x n_out``."""
    return 2.0 * sum(group_sizes) * k_in * n_out


def grouped_call_bytes(group_sizes, k_in: int, n_out: int,
                       itemsize: int = 2) -> float:
    """Least HBM traffic of one grouped call: every pair's two rows (one in
    and one out, or for the weight gradient both in) and each touched
    expert's matrix (in, or for the weight gradient out) once."""
    pairs = sum(group_sizes)
    touched = sum(1 for s in group_sizes if s > 0)
    return float(itemsize) * (pairs * (k_in + n_out) + touched * k_in * n_out)
