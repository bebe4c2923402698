"""Tiny ``lfm2_moe`` preset for the CPU tests: the cell's six layers
(``conv conv | full conv conv conv``, two dense and four with experts), a
router 16 wide that picks 4, of which this share holds experts 4-7."""
from __future__ import annotations

LFM2 = {
    "arch": "lfm2_moe", "model_type": "lfm2_moe",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 2, "num_experts": 4, "router_width": 16,
    "experts_held": [4, 8], "num_experts_per_tok": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "rope_theta": 1000000, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 97, "initializer_range": 0.02,
    "run": {"learning_rate": 1e-4, "weight_decay": 0.1, "beta1": 0.9,
            "beta2": 0.999, "epsilon": 1e-8, "recompute": True},
    # tiny-size limits, set as the real ones are: above what the sound
    # program reads on CPU over a dozen seeds, below the broken step's 1.0
    "check": {"control_precision": "fp8", "loss_gap_step1": 1e-3,
              "loss_gap_step2": 1e-3, "loss_gap_step3": 1e-3,
              "grad_norm_gap": 0.05, "delta_norm_gap": 0.6},
}
FIT = {"kind": "fit_lfm2_moe", "batch": 2, "seq_len": 64,
       "steps_per_epoch": 2, "table_epochs": 2, "check_calls": [1, 2]}
