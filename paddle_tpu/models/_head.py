"""A causal LM's head and loss when it is handed labels.

One implementation for the model zoo: the head's product lives inside the
loss (``F.fused_linear_cross_entropy``), chunk by chunk with each chunk's
gradient taken while its logits are at hand, so a training step multiplies
by the vocabulary three times and holds no (rows, vocabulary) array. A
forward with labels therefore returns ``(None, loss)``; logits are what a
forward WITHOUT labels returns.
"""
from __future__ import annotations

import jax

from .. import ops
from ..nn import functional as F

IGNORE_INDEX = -100


def next_token_loss(h, table, labels, transpose_y):
    """Mean cross entropy of ``h (B, S, H)`` times the head's ``table``
    against the NEXT token of ``labels (B, S)``. The op gets all ``S``
    positions, the last one's label ``IGNORE_INDEX``: whole sequences chunk
    with no padding and nothing as wide as the vocabulary is sliced."""
    with jax.named_scope("loss"):       # head and loss in one op
        shifted = ops.concat(
            [labels[:, 1:], ops.full_like(labels[:, :1], IGNORE_INDEX)],
            axis=1)
        return F.fused_linear_cross_entropy(
            h, table, shifted, transpose_y=transpose_y,
            ignore_index=IGNORE_INDEX)
