"""Tiny preset of the K-EXAONE (``exaone_moe``) serving kind for the CPU
tests: the same driver, table of weights and reference, at widths a laptop
holds (hidden 64, 4 query heads over 2 KV heads of 16, a window of 8, five
layers dense + ``S S F S`` as the benchmark's cut has them, 16 experts of
which 4 are held, top-4, expert width 48, dense width 96).
``initializer_range`` is 0.1, not 0.02: at hidden 64 a 0.02 matrix passes a
tenth of its input on and every layer would be a rounding error beside the
residual."""
from __future__ import annotations

_S, _F = "sliding_attention", "full_attention"

EXAONE = {
    "arch": "exaone_moe", "hidden_size": 64, "vocab_size": 251,
    "num_hidden_layers": 5,
    "layer_types": [_S, _S, _S, _F, _S],
    "sliding_windows": [8, 8, 8, 0, 8], "sliding_window": 8,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_experts": 4, "router_width": 16, "experts_held": [0, 4],
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "rms_norm_eps": 1e-5, "initializer_range": 0.1,
    "engine": {"max_batch": 4, "context": 128, "block_size": 8,
               "num_blocks": 128},
    # tiny-size limits, set as the real ones are (program's largest over a
    # dozen seeds on CPU, below the control's smallest)
    "check": {"control_precision": "int8", "logit_gap_mean": 5e-3,
              "logit_gap_max": 0.5},
}

#: the same at widths whose decode step takes the paged decode-attention
#: kernel in the full layer (heads of 128, pages of 16 tokens x 2 KV heads,
#: 16 query heads: ``paddle_tpu.ops.pallas.paged_attention.supports``); the
#: recorded trace the readers' tests read was made with it
KERNEL = dict(EXAONE, hidden_size=256, num_attention_heads=16,
              num_key_value_heads=2, head_dim=128,
              engine={"max_batch": 4, "context": 128, "block_size": 16,
                      "num_blocks": 64})
