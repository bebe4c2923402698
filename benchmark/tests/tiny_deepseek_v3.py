"""Tiny preset of the DeepSeek-V3 (``deepseek_v3``) serving kind for the CPU
tests: the same driver, table of weights and reference, at widths a laptop
holds (hidden 64, 4 heads of 16 + 8 over a latent row of 32 + 8, values of
16, three layers dense + two sparse, 16 experts of which 4 are held, top-4,
expert width 48, two shared experts, dense width 96). ``initializer_range``
is 0.1, not 0.02: at hidden 64 a 0.02 matrix passes a tenth of its input on
and every layer would be a rounding error beside the residual."""
from __future__ import annotations

DEEPSEEK = {
    "arch": "deepseek_v3", "hidden_size": 64, "vocab_size": 251,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
    "v_head_dim": 16, "rope_theta": 1000000, "rope_interleave": True,
    "rope_scaling": None,
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "n_routed_experts": 4, "router_width": 16, "experts_held": [0, 4],
    "num_experts_per_tok": 4, "n_shared_experts": 2, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.448, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "initializer_range": 0.1,
    "engine": {"max_batch": 4, "context": 128, "block_size": 8,
               "num_blocks": 128},
    # tiny-size limits, above the program's largest over sixteen runs on the
    # CPU (8 seeds x the two tiny mixes: mean 0.0069, widest 0.40, both at
    # one seed where bfloat16 flips a router's choice; the others read under
    # 0.002 and 0.08); an altered token reads a widest gap over 1
    "check": {"control_precision": "int8", "logit_gap_mean": 2e-2,
              "logit_gap_max": 0.8},
}

#: the same at widths whose decode step takes the paged decode-attention
#: kernel over latent pages (a row of 192 + 64 = 256 lanes, values its first
#: 128, pages of 16 tokens, 16 query heads:
#: ``paddle_tpu.ops.pallas.paged_attention.supports``); the recorded trace
#: the readers' tests read was made with it
KERNEL = dict(DEEPSEEK, hidden_size=256, num_attention_heads=16,
              kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=64,
              qk_head_dim=96, v_head_dim=32,
              engine={"max_batch": 4, "context": 128, "block_size": 16,
                      "num_blocks": 64})
