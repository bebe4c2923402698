"""A tiny preset whose decode step takes the paged decode-attention kernel:
heads of 128, pages of 16 tokens x 2 KV heads, 16 query heads (shapes
``paddle_tpu.ops.pallas.paged_attention.supports``). The traffic and the
other keys are ``tiny.py``'s."""
from __future__ import annotations

from benchmark.tests import tiny

LLAMA = dict(tiny.LLAMA, hidden_size=2048, intermediate_size=256,
             num_attention_heads=16, num_key_value_heads=2, head_dim=128,
             engine={"max_batch": 4, "context": 128, "block_size": 16,
                     "num_blocks": 64})
