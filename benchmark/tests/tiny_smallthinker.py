"""Tiny ``smallthinker`` presets for the CPU tests: the cell's four layers
(full + NoPE, then three window + rotary), a window of 24 of the 64 tokens,
a router 16 wide that picks 4, of which this share holds experts 4-7.
``KERNEL`` / ``KERNEL_FIT`` are the preset the readers' test trace was
recorded with on the chip: heads of 128 and 2048 tokens a row against a
window of 512, so that the flash kernels run under both scopes and the
expert product takes its grouped form."""
from __future__ import annotations

SMALLTHINKER = {
    "arch": "smallthinker", "model_name": "smallthinker_tiny",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4,
    "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": 24, "rope_theta": 1500000, "rope_scaling": None,
    "max_position_embeddings": 64, "moe_ffn_hidden_size": 48,
    "moe_num_primary_experts": 4, "router_width": 16,
    "experts_held": [4, 8], "moe_num_active_primary_experts": 4,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "vocab_size": 97, "initializer_range": 0.02,
    "run": {"learning_rate": 1e-4, "weight_decay": 0.1, "beta1": 0.9,
            "beta2": 0.999, "epsilon": 1e-8, "recompute": True},
    # tiny-size limits, set as the real ones are: above what the sound
    # program reads on CPU (seeds 11-13: the gradient's norm 0.001-0.037, a
    # top-4 choice that flips under bfloat16; the change's 0.0024-0.0028),
    # below the faults' readings (the window dropped from layer 1 reads
    # 0.013-0.018 on the change, a step that changes nothing 1.0)
    # (the losses' 2e-6 to 2.3e-5; half the batch left out reads 5.7e-4)
    "check": {"control_precision": "fp8", "loss_gap_step1": 3e-4,
              "loss_gap_step2": 3e-4, "loss_gap_step3": 3e-4,
              "grad_norm_gap": 0.06, "delta_norm_gap": 0.007},
}
FIT = {"kind": "fit_smallthinker", "batch": 2, "seq_len": 64,
       "steps_per_epoch": 2, "table_epochs": 2, "check_calls": [1, 2]}

KERNEL = dict(SMALLTHINKER, hidden_size=256, head_dim=128,
              num_attention_heads=2, num_key_value_heads=1,
              sliding_window_size=512, max_position_embeddings=2048,
              moe_ffn_hidden_size=128, vocab_size=509)
KERNEL_FIT = dict(FIT, batch=1, seq_len=2048)
