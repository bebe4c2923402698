"""A test-size ``olmo_hybrid`` configuration in the configuration file's
keys: two periods ``L L L F`` as the benchmark's cut has them, six heads (no
whole sublane tile, as the published 30 are none), a state of 8 x 64 a head
packed two to a row."""
LINEAR, FULL = "linear_attention", "full_attention"

CFG = {
    "name": "tiny-olmo-hybrid", "arch": "olmo_hybrid",
    "model_type": "olmo_hybrid",
    "vocab_size": 256, "hidden_size": 96, "intermediate_size": 160,
    "num_hidden_layers": 8, "num_attention_heads": 6,
    "num_key_value_heads": 6,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 2,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6,
    "linear_key_head_dim": 8, "linear_value_head_dim": 64,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "attention_bias": False, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-6, "initializer_range": 0.02, "chunk_size": 8,
    "engine": {"max_batch": 4, "context": 128, "block_size": 8,
               "num_blocks": 64, "prefill_token_budget": 16},
    "check": {"control_precision": "int8", "logit_gap_mean": 1.0,
              "logit_gap_max": 10.0},
}
