"""Tiny preset of the hybrid (``nemotron_h``) serving kind for the CPU
tests: the same driver, table of weights and reference, at widths a laptop
holds (hidden 64, 8 Mamba heads of 8, state 16, 2 groups, 16 experts of which
4 are held, top-4, latent 32, pattern ``MEM*E``). ``initializer_range`` is
0.1, not 0.02: at hidden 64 a 0.02 matrix passes a tenth of its input on and
every mixer would be a rounding error beside the residual."""
from __future__ import annotations

NEMOTRON = {
    "arch": "nemotron_h", "hidden_size": 64, "vocab_size": 251,
    "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "n_routed_experts": 4, "router_width": 16, "experts_held": [0, 4],
    "num_experts_per_tok": 4, "moe_latent_size": 32,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "routed_scaling_factor": 5.0, "norm_topk_prob": True,
    "layer_norm_epsilon": 1e-5, "initializer_range": 0.1,
    "engine": {"max_batch": 4, "context": 128, "block_size": 8,
               "num_blocks": 128},
    # tiny-size limits, set as the real ones are (program's largest over a
    # dozen seeds on CPU, below the altered token's reading)
    "check": {"control_precision": "int8", "logit_gap_mean": 5e-3,
              "logit_gap_max": 0.5},
}
