"""Operations and bytes an ``exaone_moe`` configuration's two serving
programs need, computed from shapes (conventions as ``lib/flops.py``: a
multiply-add is 2 operations, embedding rows are gathers, causal attention is
billed over the keys it sees). What is particular to this architecture:

* a FULL attention layer reads K/V of every cached token; a WINDOW layer
  reads ``sliding_window`` tokens a sequence however long the sequence is
  (every prompt of the cell is at least a window long), so its bytes need
  the lanes, not their lengths; both write the new rows;
* routed experts are billed as TOUCHED, not as held (as
  ``lib/flops_nemotron_h.py`` does): of the ``E_held`` experts here, ``n``
  tokens that each pick ``k`` of ``E`` touch ``E_held * (1 - (1 - k / E) **
  n)`` on average, and only those weights have to leave HBM; their
  operations are billed for the (token, expert) pairs that land here;
* the shared expert, the router, attention and the dense layer are read
  once a call; the head is this chip's slice of the vocabulary
  (``vocab_size`` in the configuration).
"""
from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def dims(cfg: dict) -> dict:
    """The widths the published keys imply (shared with the weight table)."""
    return {"q": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
            "shared": cfg["moe_intermediate_size"] * cfg["num_shared_experts"]}


def counts(cfg: dict) -> dict:
    n = cfg["num_hidden_layers"]
    kinds, mlps = cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]
    return {"window": kinds.count(SLIDING), "full": kinds.count(FULL),
            "dense": mlps.count("dense"), "sparse": mlps.count("sparse")}


def attention_matmul_params(cfg: dict) -> int:
    d, h = dims(cfg), cfg["hidden_size"]
    return h * d["q"] + 2 * h * d["kv"] + d["q"] * h


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_fixed_matmul_params(cfg: dict) -> int:
    """Router and shared expert: what every token uses."""
    h = cfg["hidden_size"]
    return h * cfg["router_width"] + 3 * h * dims(cfg)["shared"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_touched(cfg: dict, tokens: float) -> float:
    held, e = cfg["num_experts"], cfg["router_width"]
    return held * (1.0 - (1.0 - cfg["num_experts_per_tok"] / e) ** tokens)


def pairs_landed(cfg: dict, tokens: float) -> float:
    return (tokens * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token in one attention layer."""
    return 2 * dims(cfg)["kv"] * itemsize


def weight_bytes(cfg: dict, tokens: float, itemsize: int = 2) -> float:
    """Weights one program call over ``tokens`` rows streams: attention, the
    dense layer, routers, shared experts and the head once, the touched
    experts. The norms' scales (two a layer, two a head of attention, the
    final one) are float32."""
    n, h = counts(cfg), cfg["hidden_size"]
    layers = n["window"] + n["full"]
    matrices = (layers * attention_matmul_params(cfg)
                + n["dense"] * dense_mlp_params(cfg)
                + n["sparse"] * (moe_fixed_matmul_params(cfg)
                                 + experts_touched(cfg, tokens)
                                 * expert_params(cfg))
                + h * cfg["vocab_size"])
    scales = (layers * (2 * h + 2 * cfg["head_dim"]) + h
              + n["sparse"] * cfg["router_width"])
    return matrices * itemsize + scales * 4


def matmul_flops_per_token(cfg: dict) -> float:
    """Operations a token costs outside attention scores and the routed
    experts (head excluded)."""
    n = counts(cfg)
    return 2.0 * ((n["window"] + n["full"]) * attention_matmul_params(cfg)
                  + n["dense"] * dense_mlp_params(cfg)
                  + n["sparse"] * moe_fixed_matmul_params(cfg))


def full_attn_decode_bytes(cfg: dict, lanes: int, cached_tokens: int,
                           itemsize: int = 2) -> float:
    """Least HBM traffic of the full layers' decode attention (the kernel
    ``paged_decode_attn``, once a full layer a step): K and V of every
    cached token once, a query row in and an output row out a lane."""
    rows = 2 * lanes * dims(cfg)["q"] * itemsize
    return counts(cfg)["full"] * (
        cached_tokens * kv_bytes_per_token_layer(cfg, itemsize) + rows)


def full_attn_decode_flops(cfg: dict, cached_tokens: int) -> float:
    """QK^T and PV over the cached tokens, every full layer."""
    return 2.0 * 2 * dims(cfg)["q"] * counts(cfg)["full"] * cached_tokens


def decode_step_bytes(cfg: dict, lanes: int, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step over ``lanes`` decoding slots
    whose caches hold ``cached_tokens`` tokens in all: the weights, K/V of
    the cached tokens in the full layers and of a window's tokens a lane in
    the window layers, the new token's rows written in both."""
    n = counts(cfg)
    row = kv_bytes_per_token_layer(cfg, itemsize)
    seen = (n["full"] * cached_tokens
            + n["window"] * lanes * cfg["sliding_window"])
    written = (n["full"] + n["window"]) * lanes
    return weight_bytes(cfg, lanes, itemsize) + (seen + written) * row


def decode_step_flops(cfg: dict, lanes: int, cached_tokens: int) -> float:
    n = counts(cfg)
    keys = (n["full"] * cached_tokens
            + n["window"] * lanes * cfg["sliding_window"])
    return (lanes * (matmul_flops_per_token(cfg)
                     + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
            + 2.0 * n["sparse"] * pairs_landed(cfg, lanes)
            * expert_params(cfg)
            + 2.0 * 2 * dims(cfg)["q"] * keys)


def prefill_chunk_bytes(cfg: dict, width: int, cached_tokens: int,
                        itemsize: int = 2) -> float:
    """One (1, width) chunk of a slot whose cache holds ``cached_tokens``
    (the chunk's own included): the full layers read the whole cache, the
    window layers the window before the chunk and the chunk."""
    n = counts(cfg)
    row = kv_bytes_per_token_layer(cfg, itemsize)
    reach = min(cached_tokens, cfg["sliding_window"] - 1 + width)
    seen = n["full"] * cached_tokens + n["window"] * reach
    written = (n["full"] + n["window"]) * width
    return weight_bytes(cfg, width, itemsize) + (seen + written) * row


def prefill_chunk_flops(cfg: dict, width: int, cached_tokens: int) -> float:
    """``width`` rows through every layer, the head for the last row only;
    a full layer's row sees on average the cache before the chunk plus half
    the chunk, a window layer's row at most a window."""
    n = counts(cfg)
    before = max(cached_tokens - width, 0)
    full_keys = before + (width + 1) / 2.0
    window_keys = min(full_keys, float(cfg["sliding_window"]))
    attn = 2.0 * 2 * dims(cfg)["q"] * width * (
        n["full"] * full_keys + n["window"] * window_keys)
    return (width * matmul_flops_per_token(cfg)
            + 2.0 * n["sparse"] * pairs_landed(cfg, width)
            * expert_params(cfg) + attn
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
