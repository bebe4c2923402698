"""A test-size ``solar_open2`` configuration in the configuration file's
keys: one period ``G K K K`` as the benchmark's cut has it, 4 linear heads of
16 x 16 behind three 4-tap convolutions, 4 query heads over 2 K/V heads of
16, 16 experts of which 4 are held and a token picks 4, expert width 48.
``initializer_range`` is 0.1, not 0.02: at hidden 64 a 0.02 matrix passes a
tenth of its input on and every layer would be a rounding error beside the
residual."""
from __future__ import annotations

CFG = {
    "name": "tiny-solar-open2", "arch": "solar_open2",
    "model_type": "solar_open2",
    "vocab_size": 251, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "gqa_layers": [0], "gqa_interval": 3, "use_gqa_gate": True,
    "use_rope": False,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "intermediate_size": 160, "first_k_dense_replace": 0,
    "moe_intermediate_size": 48, "n_routed_experts": 4, "router_width": 16,
    "experts_held": [0, 4], "n_shared_experts": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1, "norm_topk_prob": True,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-5,
    "initializer_range": 0.1, "chunk_size": 8,
    "engine": {"max_batch": 4, "context": 128, "block_size": 8,
               "num_blocks": 64, "prefill_token_budget": 16},
    # tiny-size limits: the sound program (bfloat16 weights and activations
    # at hidden 64, logits of order 3) read 0.014-0.028 mean and 0.32-0.89
    # widest on the CPU, a served token altered 2.9-3.8 widest and 1.0-1.1
    # mean. At this size int8 weights are as close to float32 as bfloat16
    # arithmetic is: the control is held to a limit at a larger size
    # (``test_correct_solar_open2.py``)
    "check": {"control_precision": "int8", "logit_gap_mean": 0.08,
              "logit_gap_max": 1.5},
}

#: the same at widths whose decode step takes BOTH kernels (linear heads of
#: 128 x 128, the step kernel's whole lane tiles; attention heads of 128,
#: pages of 16 tokens x 2 K/V heads): the recorded trace the readers' tests
#: read was made with it
KERNEL = dict(CFG, hidden_size=256, num_attention_heads=16,
              num_key_value_heads=2, head_dim=128,
              linear_attn_config={"short_conv_kernel_size": 4,
                                  "head_dim": 128, "num_heads": 16,
                                  "num_kv_heads": None},
              engine={"max_batch": 4, "context": 128, "block_size": 16,
                      "num_blocks": 64, "prefill_token_budget": 16})
