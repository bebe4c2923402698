"""Operations and bytes the algorithm needs, computed from shapes.

Every share of a peak the benchmark reports divides by a number from this
file, so a later PR cannot move the yardstick. Conventions:

* a multiply-add is 2 operations;
* causal attention is billed as causal: each query attends to (S + 1) / 2
  keys on average, not S;
* embedding rows are gathers, not matmuls: no operations;
* recomputed operations (activation checkpointing) are never billed.
"""
from __future__ import annotations


# ------------------------------------------------------------- GPT-2 train
def gpt2_matmul_params_per_layer(cfg: dict) -> int:
    h = cfg["n_embd"]
    inner = cfg.get("n_inner") or 4 * h
    return 3 * h * h + h * h + 2 * h * inner


def gpt2_fwd_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward operations per trained token at sequence length ``seq``:
    the block matmuls, causal QK^T and PV, and the tied head."""
    h, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    matmul = 2 * gpt2_matmul_params_per_layer(cfg)
    attn = 2 * 2 * h * (seq + 1) / 2          # QK^T and PV, causal
    return layers * (matmul + attn) + 2 * h * vocab


def gpt2_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (2x forward): 3x forward."""
    return 3.0 * gpt2_fwd_flops_per_token(cfg, seq)


def gpt2_param_count(cfg: dict) -> int:
    h, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * h
    per_layer = (gpt2_matmul_params_per_layer(cfg)
                 + 3 * h + h + inner + h        # biases
                 + 4 * h)                       # two LayerNorms
    return (cfg["vocab_size"] * h + cfg["n_positions"] * h
            + layers * per_layer + 2 * h)


# ------------------------------------------------- flash attention kernels
def flash_call_flops(kind: str, bh: int, seq: int, head_dim: int) -> float:
    """Operations one causal flash-attention call needs over ``bh``
    (batch x heads) sequences. ``fwd``: QK^T and PV. The backward needs five
    matmuls of the same size (QK^T again, dO V^T, dS K, dS^T Q, P^T dO);
    a dQ kernel and a dK/dV kernel that each rebuild QK^T and dO V^T run
    seven, but only what the algorithm needs is billed: ``dq`` is billed
    three (QK^T, dO V^T, dS K) and ``dkv`` two (dS^T Q, P^T dO)."""
    one = 2.0 * bh * seq * (seq + 1) / 2 * head_dim     # one causal matmul
    return {"fwd": 2, "dq": 3, "dkv": 2}[kind] * one


def flash_call_bytes(kind: str, bh: int, seq: int, head_dim: int,
                     itemsize: int = 2) -> float:
    """Least HBM traffic of one call: each operand read once, each result
    written once (log-sum-exp and delta rows are f32)."""
    t = bh * seq * head_dim * itemsize
    row = bh * seq * 4
    return {"fwd": 4 * t + row,                 # q k v -> o, lse
            "dq": 5 * t + 2 * row,              # q k v do, lse delta -> dq
            "dkv": 6 * t + 2 * row}[kind]       # q k v do, lse delta -> dk dv


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


# ------------------------------------------------------ Llama-like serving
def llama_like_layer_params(cfg: dict) -> int:
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = h * cfg["num_attention_heads"] * hd
    kv = 2 * h * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * h
    return q + kv + o + 3 * h * inter + 2 * h


def llama_like_param_count(cfg: dict) -> int:
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else vocab * h
    return (vocab * h + head + h
            + cfg["num_hidden_layers"] * llama_like_layer_params(cfg))


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    hd = cfg.get("head_dim") or (cfg["hidden_size"]
                                 // cfg["num_attention_heads"])
    return (2 * cfg["num_key_value_heads"] * hd * itemsize
            * cfg["num_hidden_layers"])


def decode_step_bytes(cfg: dict, cached_tokens: int,
                      weight_itemsize: int = 2, kv_itemsize: int = 2) -> float:
    """Least HBM traffic of one batched decode step: every layer's weights
    and the head once (the embedding table is a gather of a few rows), and
    the keys and values of every cached token of every active slot once."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    weights = (cfg["num_hidden_layers"] * llama_like_layer_params(cfg)
               + h + vocab * h) * weight_itemsize
    return weights + cached_tokens * kv_bytes_per_token(cfg, kv_itemsize)


def decode_step_flops(cfg: dict, batch: int, cached_tokens: int) -> float:
    """Operations of one decode step over ``batch`` active slots."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    matmul = (cfg["num_hidden_layers"]
              * (llama_like_layer_params(cfg) - 2 * h) + vocab * h)
    attn = (2 * 2 * cfg["num_attention_heads"] * hd
            * cfg["num_hidden_layers"] * cached_tokens)
    return 2.0 * matmul * batch + attn
