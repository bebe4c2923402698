"""In-graph serving kernels: int8 KV (de)quantization + speculative verify.

Siblings to :mod:`.fused_ops`, but these are the serving tier's hot
inner loops (reference: the block_multi_head_attention serving family in
phi/kernels/fusion/ plus PaddleNLP's speculative-decoding verify step).
Both are expressed as pure jnp/lax composites so they fuse into the ONE
jitted engine tick — the int8<->fp convert is vectorized on the VPU and the
scale multiply folds into the composite attention's einsum prologue. The
hand-written Mosaic kernel of this tier is the decode step's attention,
:mod:`.paged_attention` (``paged_decode_attn``: float pages, one new token
a lane); int8 pages, chunked prefill and the speculative verify step still
attend through the composite in ``nn.functional.paged_attention``, and
these helpers define the quantized-page ABI a kernel for them would read.

* ``kv_quantize_int8`` / ``kv_dequantize_int8`` — symmetric per-token,
  per-KV-head abs-max int8 over the head dim (the ``nn/quant``
  ``weight_only_linear`` pattern applied to KV pages: payload int8,
  sidecar fp scales, dequant at the consumer). Per-(position, head)
  scales keep the quantization error ~0.4% worst-case, small enough
  that greedy decode stays token-identical on the parity gate.
* ``spec_accept_prefix`` — the accept-prefix rule of greedy speculative
  decoding as lax ops: given the target model's per-position greedy
  tokens over ``[last_token, draft...]`` and the draft tokens, count the
  longest matching prefix (bounded per slot by ``max_accept``) so the
  whole verify — draft append, one forward, acceptance — is ONE
  compiled program with a stable ``(B, k+1)`` shape.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["KV_QMAX", "kv_quantize_int8", "kv_dequantize_int8",
           "spec_accept_prefix"]

#: symmetric int8 range for KV payloads (−127..127; −128 unused so the
#: scale inverse is exact for the abs-max element)
KV_QMAX = 127.0


def kv_quantize_int8(x):
    """Quantize KV activations ``(..., D)`` to (int8 payload, scales).

    Scales are per leading element (one per ``(..., )`` position/head
    vector, abs-max over the head dim D) in float32 — the sidecar is
    ``D * itemsize`` times smaller than the payload, so the resident
    page pool still shrinks ~2x vs bf16 (~4x vs fp32).
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / KV_QMAX
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), scale


def kv_dequantize_int8(q, scale, dtype=jnp.float32):
    """Dequantize an int8 KV payload with its sidecar scales back to
    ``dtype`` (the attention math's accumulation dtype). XLA fuses the
    broadcast multiply into the consuming einsum's operand read."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


def spec_accept_prefix(draft, greedy, max_accept):
    """Greedy speculative-decoding acceptance as ONE lax expression.

    Args:
      draft: ``(B, k)`` int32 draft tokens fed at positions 1..k of the
        verify chunk.
      greedy: ``(B, k+1)`` int32 target-model greedy tokens, where
        ``greedy[:, i]`` is the model's next token after consuming chunk
        position ``i``.
      max_accept: ``(B,)`` int32 per-slot cap on accepted draft tokens
        (0 disables speculation for a slot — e.g. sampling slots, or
        slots butting against a learned-position table).

    Returns ``(n_emit, accepted)`` — ``accepted[b]`` is the length of the
    longest prefix ``i`` with ``draft[b, i] == greedy[b, i]`` (bounded by
    ``max_accept[b]``); ``n_emit = accepted + 1`` because the token after
    the accepted prefix is always the target model's own prediction and
    is emitted unconditionally (the decode step's normal output).
    """
    k = draft.shape[1]
    match = draft == greedy[:, :k]
    match = jnp.logical_and(
        match, jnp.arange(k, dtype=jnp.int32)[None, :]
        < max_accept[:, None].astype(jnp.int32))
    accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                       axis=1)
    return accepted + 1, accepted
