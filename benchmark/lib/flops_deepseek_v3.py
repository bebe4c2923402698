"""Operations and bytes a ``deepseek_v3`` configuration's two serving
programs need, computed from shapes (conventions as ``lib/flops.py``: a
multiply-add is 2 operations, embedding rows are gathers, causal attention is
billed over the keys it sees). What is particular to this architecture:

* a layer caches ONE row a token, ``[c | k_r]``: ``kv_lora_rank +
  qk_rope_head_dim`` numbers, **1152 B in bfloat16** at the published sizes,
  whatever the program pads a row to. A decode step has to read every cached
  row once a layer and write the new ones;
* attention over cached rows is billed in the ABSORBED form, the one a
  program that caches only ``[c | k_r]`` can run: a (query, key) pair costs
  every head a ``kv_lora_rank + qk_rope_head_dim`` wide score and a
  ``kv_lora_rank`` wide weighted sum. The products with ``W_kvb`` (the
  absorb and the un-absorb) are a row's share of the layer's matrices, as
  applying ``W_kvb`` to a row is in the materialised form;
* routed experts are billed as TOUCHED, not as held (as
  ``lib/flops_exaone_moe.py`` does): of the ``E_held`` experts here, ``n``
  tokens that each pick ``k`` of ``E`` touch ``E_held * (1 - (1 - k / E) **
  n)`` on average;
* the shared expert, the router, attention's matrices and the dense layer
  are read once a call; the head is this chip's slice of the vocabulary
  (``vocab_size`` in the configuration).
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    """The widths the published keys imply (shared with the weight table)."""
    heads = cfg["num_attention_heads"]
    return {"q": heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
            "row": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            "kv_b": heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]),
            "o": heads * cfg["v_head_dim"],
            "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"]}


def counts(cfg: dict) -> dict:
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    return {"layers": n, "dense": dense, "sparse": n - dense}


def attention_matmul_params(cfg: dict) -> int:
    d, h = dims(cfg), cfg["hidden_size"]
    return (h * d["q"] + h * d["row"] + cfg["kv_lora_rank"] * d["kv_b"]
            + d["o"] * h)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_fixed_matmul_params(cfg: dict) -> int:
    """Router and shared expert: what every token uses."""
    h = cfg["hidden_size"]
    return h * cfg["router_width"] + 3 * h * dims(cfg)["shared"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_touched(cfg: dict, tokens: float) -> float:
    held, e = cfg["n_routed_experts"], cfg["router_width"]
    return held * (1.0 - (1.0 - cfg["num_experts_per_tok"] / e) ** tokens)


def pairs_landed(cfg: dict, tokens: float) -> float:
    return (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What one token caches in one layer: ``[c | k_r]``."""
    return dims(cfg)["row"] * itemsize


def pair_flops(cfg: dict) -> float:
    """One query against one cached row, every head, absorbed: the score
    over the row, the weighted sum over its ``kv_lora_rank`` columns."""
    return 2.0 * cfg["num_attention_heads"] * (dims(cfg)["row"]
                                               + cfg["kv_lora_rank"])


def weight_bytes(cfg: dict, tokens: float, itemsize: int = 2) -> float:
    """Weights one program call over ``tokens`` rows streams: attention, the
    dense layer, routers, shared experts and the head once, the touched
    experts. The norms' scales (two a layer, ``kv_a_layernorm``, the final
    one) and the routers' correction biases are float32."""
    n, h = counts(cfg), cfg["hidden_size"]
    matrices = (n["layers"] * attention_matmul_params(cfg)
                + n["dense"] * dense_mlp_params(cfg)
                + n["sparse"] * (moe_fixed_matmul_params(cfg)
                                 + experts_touched(cfg, tokens)
                                 * expert_params(cfg))
                + h * cfg["vocab_size"])
    scales = (n["layers"] * (2 * h + cfg["kv_lora_rank"]) + h
              + n["sparse"] * cfg["router_width"])
    return matrices * itemsize + scales * 4


def matmul_flops_per_token(cfg: dict) -> float:
    """Operations a token costs outside attention over cached rows and the
    routed experts (head excluded)."""
    n = counts(cfg)
    return 2.0 * (n["layers"] * attention_matmul_params(cfg)
                  + n["dense"] * dense_mlp_params(cfg)
                  + n["sparse"] * moe_fixed_matmul_params(cfg))


def latent_attn_decode_bytes(cfg: dict, lanes: int, cached_tokens: int,
                             itemsize: int = 2) -> float:
    """Least HBM traffic of the decode step's attention over cached rows
    (the kernel ``paged_decode_attn``, once a layer a step): every cached
    row once, an absorbed query row in and a latent output row out a head a
    lane."""
    heads = cfg["num_attention_heads"]
    rows = lanes * heads * (dims(cfg)["row"] + cfg["kv_lora_rank"]) * itemsize
    return counts(cfg)["layers"] * (
        cached_tokens * row_bytes(cfg, itemsize) + rows)


def latent_attn_decode_flops(cfg: dict, cached_tokens: int) -> float:
    """Scores and weighted sums over the cached rows, every layer."""
    return pair_flops(cfg) * counts(cfg)["layers"] * cached_tokens


def decode_step_bytes(cfg: dict, lanes: int, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step over ``lanes`` decoding slots
    whose caches hold ``cached_tokens`` tokens in all: the weights, every
    cached row once a layer, the new token's row written a layer."""
    return (weight_bytes(cfg, lanes, itemsize)
            + counts(cfg)["layers"] * (cached_tokens + lanes)
            * row_bytes(cfg, itemsize))


def decode_step_flops(cfg: dict, lanes: int, cached_tokens: int) -> float:
    n = counts(cfg)
    return (lanes * (matmul_flops_per_token(cfg)
                     + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
            + 2.0 * n["sparse"] * pairs_landed(cfg, lanes)
            * expert_params(cfg)
            + latent_attn_decode_flops(cfg, cached_tokens))


def prefill_chunk_bytes(cfg: dict, width: int, cached_tokens: int,
                        itemsize: int = 2) -> float:
    """One (1, width) chunk of a slot whose cache holds ``cached_tokens``
    (the chunk's own included): every layer reads the slot's rows once and
    writes the chunk's."""
    return (weight_bytes(cfg, width, itemsize)
            + counts(cfg)["layers"] * (cached_tokens + width)
            * row_bytes(cfg, itemsize))


def prefill_chunk_flops(cfg: dict, width: int, cached_tokens: int) -> float:
    """``width`` rows through every layer, the head for the last row only;
    a row sees on average the cache before the chunk plus half the chunk."""
    n = counts(cfg)
    keys = max(cached_tokens - width, 0) + (width + 1) / 2.0
    return (width * matmul_flops_per_token(cfg)
            + 2.0 * n["sparse"] * pairs_landed(cfg, width)
            * expert_params(cfg)
            + pair_flops(cfg) * n["layers"] * width * keys
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
