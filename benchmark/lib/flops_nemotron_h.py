"""Operations and bytes a ``nemotron_h`` configuration's two serving
programs need, computed from shapes (conventions as ``lib/flops.py``: a
multiply-add is 2 operations, embedding rows are gathers, causal attention is
billed over the keys it sees). What is particular to this architecture:

* routed experts are billed as TOUCHED, not as held: of the ``E_held``
  experts here, ``n`` tokens that each pick ``k`` of ``E`` touch
  ``E_held * (1 - (1 - k / E) ** n)`` on average, and only those weights
  have to leave HBM; their operations are billed for the (token, expert)
  pairs that land here, ``n * k * E_held / E``;
* a Mamba layer's state (convolution window and SSM state) is read and
  written once for every lane that decodes; a prefill chunk reads and
  writes one slot's;
* K/V of the cached tokens only in the attention layers; the head is this
  chip's slice of the vocabulary (``vocab_size`` in the configuration).
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    """The widths the published keys imply (shared with the weight table)."""
    d_in = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return {"d_in": d_in, "conv": conv,
            "in_proj": d_in + conv + cfg["mamba_num_heads"],
            "q": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv": cfg["num_key_value_heads"] * cfg["head_dim"]}


def counts(cfg: dict) -> dict:
    p = cfg["hybrid_override_pattern"]
    return {"M": p.count("M"), "*": p.count("*"), "E": p.count("E")}


def mamba_matmul_params(cfg: dict) -> int:
    d, h = dims(cfg), cfg["hidden_size"]
    return h * d["in_proj"] + d["d_in"] * h


def mamba_layer_params(cfg: dict) -> int:
    d, h = dims(cfg), cfg["hidden_size"]
    small = (d["conv"] * (cfg["conv_kernel"] + 1)       # conv weight, bias
             + 3 * cfg["mamba_num_heads"] + d["d_in"] + h)
    return mamba_matmul_params(cfg) + small


def attention_matmul_params(cfg: dict) -> int:
    d, h = dims(cfg), cfg["hidden_size"]
    return h * d["q"] + 2 * h * d["kv"] + d["q"] * h


def moe_fixed_matmul_params(cfg: dict) -> int:
    """Router, latent down and up, shared expert: what every token uses."""
    h = cfg["hidden_size"]
    return (h * cfg["router_width"] + 2 * h * cfg["moe_latent_size"]
            + 2 * h * cfg["moe_shared_expert_intermediate_size"])


def expert_params(cfg: dict) -> int:
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def experts_touched(cfg: dict, tokens: float) -> float:
    held, e = cfg["n_routed_experts"], cfg["router_width"]
    return held * (1.0 - (1.0 - cfg["num_experts_per_tok"] / e) ** tokens)


def pairs_landed(cfg: dict, tokens: float) -> float:
    return (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def state_bytes_per_slot_layer(cfg: dict, window_itemsize: int = 2) -> int:
    d = dims(cfg)
    return ((cfg["conv_kernel"] - 1) * d["conv"] * window_itemsize
            + cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            * cfg["ssm_state_size"] * 4)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * dims(cfg)["kv"] * itemsize * counts(cfg)["*"]


def weight_bytes(cfg: dict, tokens: float, itemsize: int = 2) -> float:
    """Weights one program call over ``tokens`` rows streams: every mixer
    and the head once, the touched experts."""
    n, h = counts(cfg), cfg["hidden_size"]
    params = (n["M"] * mamba_layer_params(cfg)
              + n["*"] * (attention_matmul_params(cfg) + h)
              + n["E"] * (moe_fixed_matmul_params(cfg) + h
                          + cfg["router_width"]
                          + experts_touched(cfg, tokens) * expert_params(cfg))
              + h + h * cfg["vocab_size"])
    return params * itemsize


def matmul_flops_per_token(cfg: dict) -> float:
    """Operations a token costs outside attention scores, the scan and the
    routed experts (head excluded)."""
    n = counts(cfg)
    return 2.0 * (n["M"] * mamba_matmul_params(cfg)
                  + n["*"] * attention_matmul_params(cfg)
                  + n["E"] * moe_fixed_matmul_params(cfg))


def scan_flops_per_token(cfg: dict) -> float:
    """The recurrence itself: decay and input term of the state (3 a cell)
    and its read-out (2 a cell), the convolution's taps."""
    cells = (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
             * cfg["ssm_state_size"])
    return counts(cfg)["M"] * (5.0 * cells + 2.0 * cfg["conv_kernel"]
                               * dims(cfg)["conv"])


def decode_step_bytes(cfg: dict, lanes: int, cached_tokens: int) -> float:
    """Least HBM traffic of one decode step over ``lanes`` decoding slots
    whose caches hold ``cached_tokens`` tokens in all."""
    state = (2 * lanes * counts(cfg)["M"]
             * state_bytes_per_slot_layer(cfg))          # read and written
    return (weight_bytes(cfg, lanes) + state
            + cached_tokens * kv_bytes_per_token(cfg))


def decode_step_flops(cfg: dict, lanes: int, cached_tokens: int) -> float:
    attn = (2 * 2 * dims(cfg)["q"] * counts(cfg)["*"] * cached_tokens)
    return (lanes * (matmul_flops_per_token(cfg) + scan_flops_per_token(cfg)
                     + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
            + 2.0 * counts(cfg)["E"] * pairs_landed(cfg, lanes)
            * expert_params(cfg) + attn)


def prefill_chunk_bytes(cfg: dict, width: int, cached_tokens: int) -> float:
    """One (1, width) chunk of a slot whose cache holds ``cached_tokens``
    (the chunk's own included)."""
    state = 2 * counts(cfg)["M"] * state_bytes_per_slot_layer(cfg)
    return (weight_bytes(cfg, width) + state
            + cached_tokens * kv_bytes_per_token(cfg))


def prefill_chunk_flops(cfg: dict, width: int, cached_tokens: int) -> float:
    """``width`` rows through every mixer, the head for the last row only,
    attention over the keys each row sees (on average the cache before the
    chunk plus half the chunk)."""
    keys = max(cached_tokens - width, 0) + (width + 1) / 2.0
    attn = 2 * 2 * dims(cfg)["q"] * counts(cfg)["*"] * keys * width
    return (width * (matmul_flops_per_token(cfg) + scan_flops_per_token(cfg))
            + 2.0 * counts(cfg)["E"] * pairs_landed(cfg, width)
            * expert_params(cfg) + attn
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
