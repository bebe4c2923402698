"""Operations and bytes a ``solar_open2`` configuration's serving programs
need, computed from shapes (conventions as ``lib/flops.py``: a multiply-add
is 2 operations, embedding rows are gathers, causal attention is billed over
the keys it sees). What is particular to this architecture:

* a KDA layer's state (three convolution windows and the ``d x d`` float32
  matrix a head) is read and written ONCE for every lane that decodes; a
  prefill chunk reads and writes one slot's;
* K/V of the cached tokens only in the gated-GQA layers;
* EVERY layer has the expert layer: routed experts are billed as TOUCHED,
  not as held (``lib/flops_exaone_moe.py`` has the expectation), their
  operations for the (token, expert) pairs that land here; router and shared
  expert once a call;
* the head is this chip's slice of the vocabulary (``vocab_size`` in the
  configuration).
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    """The widths the published keys imply (shared with the weight table)."""
    lin = cfg["linear_attn_config"]
    return {"q": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
            "lin_heads": lin["num_heads"], "lin_head_dim": lin["head_dim"],
            "lin": lin["num_heads"] * lin["head_dim"],
            "taps": lin["short_conv_kernel_size"],
            "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"]}


def counts(cfg: dict) -> dict:
    full = len([i for i in cfg["gqa_layers"]
                if i < cfg["num_hidden_layers"]])
    return {"full": full, "kda": cfg["num_hidden_layers"] - full}


def kda_matmul_params(cfg: dict) -> int:
    """q / k / v / o, the two rank-``head_dim`` pairs (decay, output gate)
    and ``W_b``."""
    d, h = dims(cfg), cfg["hidden_size"]
    return (4 * h * d["lin"] + 2 * (h * d["lin_head_dim"]
                                    + d["lin_head_dim"] * d["lin"])
            + h * d["lin_heads"])


def kda_small_params(cfg: dict) -> int:
    """Three convolutions' taps (in the model's dtype), then ``A_log``,
    ``dt_bias`` and the head norm's scale (float32)."""
    d = dims(cfg)
    return (3 * d["lin"] * d["taps"] + d["lin_heads"] + d["lin"]
            + d["lin_head_dim"])


def full_matmul_params(cfg: dict) -> int:
    """q, k, v, the gate's projection and ``W_o``."""
    d, h = dims(cfg), cfg["hidden_size"]
    return 3 * h * d["q"] + 2 * h * d["kv"]


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


def param_count(cfg: dict) -> int:
    n, h = counts(cfg), cfg["hidden_size"]
    per_block = (moe_fixed_matmul_params(cfg) + cfg["router_width"]
                 + cfg["n_routed_experts"] * expert_params(cfg) + 2 * h)
    return (n["kda"] * (kda_matmul_params(cfg) + kda_small_params(cfg))
            + n["full"] * full_matmul_params(cfg)
            + (n["kda"] + n["full"]) * per_block
            + h + 2 * h * cfg["vocab_size"])


def rule_state_bytes(cfg: dict) -> int:
    """The rule's matrix state of one slot and layer, float32."""
    d = dims(cfg)
    return d["lin_heads"] * d["lin_head_dim"] ** 2 * 4


def state_bytes_per_slot_layer(cfg: dict, window_itemsize: int = 2) -> int:
    """A KDA layer's carried state for one slot: three windows in the
    model's dtype, the matrix state in float32."""
    d = dims(cfg)
    return (3 * (d["taps"] - 1) * d["lin"] * window_itemsize
            + rule_state_bytes(cfg))


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * dims(cfg)["kv"] * itemsize * counts(cfg)["full"]


def weight_bytes(cfg: dict, tokens: float, itemsize: int = 2) -> float:
    """Weights one program call over ``tokens`` rows streams: mixers,
    routers, shared experts and the head once, the touched experts; the
    small float32 leaves at 4."""
    n, h, d = counts(cfg), cfg["hidden_size"], dims(cfg)
    layers = n["kda"] + n["full"]
    matrices = (n["kda"] * (kda_matmul_params(cfg)
                            + 3 * d["lin"] * d["taps"])
                + n["full"] * full_matmul_params(cfg)
                + layers * (moe_fixed_matmul_params(cfg)
                            + experts_touched(cfg, tokens)
                            * expert_params(cfg))
                + h * cfg["vocab_size"])
    scales = (n["kda"] * (d["lin_heads"] + d["lin"] + d["lin_head_dim"])
              + layers * (2 * h + cfg["router_width"]) + h)
    return matrices * itemsize + scales * 4


def matmul_flops_per_token(cfg: dict) -> float:
    """Operations a token costs in the mixers' projections, the router and
    the shared expert (head, rule, attention scores and routed experts
    excluded)."""
    n = counts(cfg)
    return 2.0 * (n["kda"] * kda_matmul_params(cfg)
                  + n["full"] * full_matmul_params(cfg)
                  + (n["kda"] + n["full"]) * moe_fixed_matmul_params(cfg))


def rule_flops_per_token_layer(cfg: dict) -> float:
    """The recurrence itself for one token of one layer: the rows' decay (1
    a cell), ``S^T k`` and ``S^T q`` (2 a cell each), the rank-one update (2
    a cell)."""
    return 7.0 * rule_state_bytes(cfg) / 4


def scan_flops_per_token(cfg: dict) -> float:
    """Rule and convolution taps of every KDA layer."""
    d = dims(cfg)
    return counts(cfg)["kda"] * (rule_flops_per_token_layer(cfg)
                                 + 2.0 * d["taps"] * 3 * d["lin"])


def routed_flops(cfg: dict, tokens: float) -> float:
    """The routed experts' products for the pairs that land here, every
    layer."""
    n = counts(cfg)
    return (2.0 * (n["kda"] + n["full"]) * pairs_landed(cfg, tokens)
            * expert_params(cfg))


# ------------------------------------------------------------ the rule alone
def rule_decode_bytes(cfg: dict, lanes: int, itemsize: int = 4) -> float:
    """What the rule's decode step cannot avoid moving in ONE KDA layer:
    each decoding lane's matrix state in and out once, its ``q``, ``k``,
    ``v`` and decay rows in and its ``o`` row out, at ``itemsize`` (the rule
    runs in float32)."""
    return lanes * (2 * rule_state_bytes(cfg)
                    + 5 * dims(cfg)["lin"] * itemsize)


def rule_decode_flops(cfg: dict, lanes: int) -> float:
    return lanes * rule_flops_per_token_layer(cfg)


# ------------------------------------------------------------- the programs
def decode_step_bytes(cfg: dict, lanes: int, cached_tokens: int) -> float:
    """Least HBM traffic of one decode step over ``lanes`` decoding slots
    whose caches hold ``cached_tokens`` tokens in all: weights once (experts
    as touched), state in and out once a decoding lane, K/V of the cached
    tokens in the GQA layers, the rows written."""
    n = counts(cfg)
    state = 2 * lanes * n["kda"] * state_bytes_per_slot_layer(cfg)
    return (weight_bytes(cfg, lanes) + state
            + (lanes + cached_tokens) * kv_bytes_per_token(cfg))


def decode_step_flops(cfg: dict, lanes: int, cached_tokens: int) -> float:
    return (lanes * (matmul_flops_per_token(cfg) + scan_flops_per_token(cfg)
                     + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
            + routed_flops(cfg, lanes)
            + 2.0 * 2 * dims(cfg)["q"] * counts(cfg)["full"] * cached_tokens)


def prefill_chunk_bytes(cfg: dict, width: int, cached_tokens: int) -> float:
    """One (1, width) chunk of a slot whose cache holds ``cached_tokens``
    (the chunk's own included): weights once, the slot's state in and out,
    the GQA layers' cache read and the chunk's rows written."""
    n = counts(cfg)
    return (weight_bytes(cfg, width)
            + 2 * n["kda"] * state_bytes_per_slot_layer(cfg)
            + (cached_tokens + width) * kv_bytes_per_token(cfg))


def prefill_chunk_flops(cfg: dict, width: int, cached_tokens: int) -> float:
    """``width`` rows through every layer, the head for the last row only;
    a GQA row sees on average the cache before the chunk plus half the
    chunk; the rule is billed as the recurrence (what the chunked form adds
    to it, the inverse and the pair terms, is the form's own cost)."""
    before = max(cached_tokens - width, 0)
    keys = before + (width + 1) / 2.0
    return (width * (matmul_flops_per_token(cfg) + scan_flops_per_token(cfg))
            + routed_flops(cfg, width)
            + 2.0 * 2 * dims(cfg)["q"] * counts(cfg)["full"] * width * keys
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def mixed_step_bytes(cfg: dict, width: int, chunk_cached: int, lanes: int,
                     cached_tokens: int) -> float:
    """A chunk with the decode step aboard: ONE stream of the weights
    (experts as touched by all the rows), both groups' state and K/V."""
    return (prefill_chunk_bytes(cfg, width, chunk_cached)
            + decode_step_bytes(cfg, lanes, cached_tokens)
            - weight_bytes(cfg, width) - weight_bytes(cfg, lanes)
            + weight_bytes(cfg, width + lanes))


def mixed_step_flops(cfg: dict, width: int, chunk_cached: int, lanes: int,
                     cached_tokens: int) -> float:
    """The chunk's and the step's operations; the head over the step's
    lanes and the chunk's one row."""
    return (prefill_chunk_flops(cfg, width, chunk_cached)
            + decode_step_flops(cfg, lanes, cached_tokens))
