"""Tiny presets for the CPU tests: the same drivers, a model a laptop holds."""
from __future__ import annotations

import copy

GPT2 = {
    "arch": "gpt2", "n_embd": 64, "n_head": 4, "n_layer": 2,
    "n_positions": 32, "vocab_size": 97, "layer_norm_epsilon": 1e-5,
    "initializer_range": 0.02,
    "run": {"learning_rate": 1e-4, "weight_decay": 0.1, "beta1": 0.9,
            "beta2": 0.999, "epsilon": 1e-8, "recompute": True},
    # tiny-size limits, set as the real ones are: above what the sound
    # program reads on CPU over a dozen seeds, below the broken step's 1.0
    "check": {"control_precision": "fp8", "loss_gap_step1": 1e-3,
              "loss_gap_step2": 1e-3, "loss_gap_step3": 1e-3,
              "grad_norm_gap": 0.05, "delta_norm_gap": 0.6},
}
FIT = {"kind": "fit", "batch": 4, "seq_len": 32, "steps_per_epoch": 4,
       "table_epochs": 4, "check_calls": [1, 2]}

LLAMA = {
    "arch": "llama_like", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 509, "rope_theta": 1e6,
    "rms_norm_eps": 1e-5, "initializer_range": 0.02,
    "tie_word_embeddings": False, "sliding_window": None,
    "engine": {"max_batch": 4, "context": 128, "block_size": 8,
               "num_blocks": 128},
    "check": {"control_precision": "int8", "logit_gap_mean": 2e-3,
              "logit_gap_max": 0.5},
}
_LENS = {"prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.9,
                        "min": 4, "max": 96},
         "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                        "min": 2, "max": 24},
         "pairing_seed": 7, "check_requests": 4}
OPEN = {"kind": "serve", "loop": "open", "rate_rps": 20.0,
        "first_token_grace_s": 2.0, **_LENS}
CLOSED = {"kind": "serve", "loop": "closed", "clients": 8,
          "requests_per_cycle": 16, "cycles": 64, **_LENS}


def cell(config, traffic, name="tiny"):
    return {"config": copy.deepcopy(config), "traffic": copy.deepcopy(traffic),
            "cell": {"name": name, "chips": 1}}
