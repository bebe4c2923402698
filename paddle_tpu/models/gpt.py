"""GPT-2 style decoder-only transformer — the flagship model.

Capability parity with the reference's GPT fixture
(reference: test/auto_parallel/get_gpt_model.py; PaddleNLP GPT uses the same
fleet TP layers). TPU-native: attention is the flash-attention functional
(Pallas kernel on TPU), all math is bf16-friendly, and the model can be
constructed tensor-parallel (mp_degree > 1) using the Megatron-style
parallel layers from paddle_tpu.distributed.fleet — weights then carry
NamedShardings over the 'mp' mesh axis and XLA inserts the collectives.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax

from .. import nn
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.parameter import ParamAttr
from .. import ops
from ._head import next_token_loss


def _init_attr(std=0.02):
    """GPT-2 init: N(0, 0.02), residual projections scaled by depth."""
    return ParamAttr(initializer=Normal(0.0, std))


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    intermediate_size: int = 0      # 0 -> 4*hidden
    dropout: float = 0.0
    use_flash_attention: bool = True
    mp_degree: int = 1              # tensor-parallel ways ('mp' mesh axis)
    sequence_parallel: bool = False
    #: activation-checkpoint every block (reference recompute pass) —
    #: required to train the 345M+ rungs on a 16 GB chip
    recompute: bool = False
    #: long-context attention backend over the 'sep' axis:
    #: "" (dense/flash local), "ring" (ring attention), "ulysses"
    #: (all-to-all head-scatter) — see fleet.meta_parallel.sep_utils
    context_parallel: str = ""

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        if self.context_parallel not in ("", "ring", "ulysses"):
            raise ValueError(
                f"context_parallel must be '', 'ring' or 'ulysses', got "
                f"{self.context_parallel!r}")
        if self.context_parallel == "ring" and self.dropout > 0:
            raise ValueError(
                "attention dropout is not supported with ring attention "
                "(the probability mask is never materialized globally); "
                "set dropout=0 or use context_parallel='ulysses'")


def gpt2_small(**kw) -> "GPTConfig":
    return GPTConfig(**kw)


def gpt2_medium(**kw) -> "GPTConfig":
    kw.setdefault("hidden_size", 1024)
    kw.setdefault("num_layers", 24)
    kw.setdefault("num_heads", 16)
    return GPTConfig(**kw)


def _linears(cfg: GPTConfig):
    """Pick (column, row, vocab-embedding) layer classes by mp_degree."""
    if cfg.mp_degree > 1:
        from ..distributed import fleet
        if cfg.sequence_parallel:
            col = fleet.ColumnSequenceParallelLinear
            row = fleet.RowSequenceParallelLinear
        else:
            col = fleet.ColumnParallelLinear
            row = fleet.RowParallelLinear
        return col, row, fleet.VocabParallelEmbedding
    return None, None, None


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.use_flash = cfg.use_flash_attention
        self.dropout = cfg.dropout
        self.context_parallel = cfg.context_parallel
        col, row, _ = _linears(cfg)
        h = cfg.hidden_size
        if col is not None:
            self.qkv_proj = col(h, 3 * h, has_bias=True, gather_output=False)
            self.out_proj = row(h, h, has_bias=True, input_is_parallel=True)
        else:
            self.qkv_proj = nn.Linear(h, 3 * h, weight_attr=_init_attr())
            self.out_proj = nn.Linear(
                h, h, weight_attr=_init_attr(0.02 / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        # local width under TP: heads split across mp ranks is expressed by
        # the sharded last dim; global semantics keep shape (b, s, 3h)
        q, k, v = ops.split(qkv, 3, axis=-1)
        q = ops.reshape(q, [b, s, self.num_heads, self.head_dim])
        k = ops.reshape(k, [b, s, self.num_heads, self.head_dim])
        v = ops.reshape(v, [b, s, self.num_heads, self.head_dim])
        if self.context_parallel == "ring":
            from ..distributed.fleet import ring_flash_attention
            out = ring_flash_attention(q, k, v, causal=True)
        elif self.context_parallel == "ulysses":
            from ..distributed.fleet import scatter_gather_attention
            out = scatter_gather_attention(
                q, k, v, causal=True,
                dropout_p=self.dropout if self.training else 0.0)
        elif self.use_flash:
            out, _ = F.flash_attention(q, k, v, dropout=self.dropout,
                                       causal=True, training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.dropout,
                training=self.training)
        out = ops.reshape(out, [b, s, h])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        col, row, _ = _linears(cfg)
        h, ffn = cfg.hidden_size, cfg.intermediate_size
        if col is not None:
            self.fc1 = col(h, ffn, has_bias=True, gather_output=False)
            self.fc2 = row(ffn, h, has_bias=True, input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(h, ffn, weight_attr=_init_attr())
            self.fc2 = nn.Linear(
                ffn, h, weight_attr=_init_attr(0.02 / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.mlp = GPTMLP(cfg)
        self.dropout = cfg.dropout

    def forward(self, x):
        with jax.named_scope("attn"):
            y = self.attn(self.ln1(x))
            if self.dropout > 0:
                y = F.dropout(y, p=self.dropout, training=self.training)
            x = x + y
        with jax.named_scope("mlp"):
            y = self.mlp(self.ln2(x))
            if self.dropout > 0:
                y = F.dropout(y, p=self.dropout, training=self.training)
            return x + y


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        _, _, vocab_emb = _linears(cfg)
        if vocab_emb is not None:
            self.wte = vocab_emb(cfg.vocab_size, cfg.hidden_size)
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                    weight_attr=_init_attr())
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size,
                                weight_attr=_init_attr())
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids):
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            pos = ops.arange(0, s, dtype="int64")
            x = self.wte(input_ids) + self.wpe(pos)
        if self.cfg.recompute:
            from ._remat import remat_block
            for blk in self.blocks:
                x = remat_block(blk, x)
        else:
            for blk in self.blocks:
                x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    """LM head ties to wte; loss = next-token cross entropy."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids, labels=None):
        """Logits; with ``labels``, ``(None, loss)``: the head's product is
        inside the loss (``_head.next_token_loss``)."""
        h = self.gpt(input_ids)
        if labels is not None:
            return None, next_token_loss(h, self.gpt.wte.weight, labels,
                                         transpose_y=True)
        with jax.named_scope("lm_head"):
            return ops.matmul(h, self.gpt.wte.weight, transpose_y=True)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self) -> float:
        """Dense training FLOPs/token ~= 6*N + attention term
        (per the scaling-book accounting: fwd 2N, bwd 4N, attention
        12*L*h*s for fwd+bwd)."""
        c = self.cfg
        n = self.num_params()
        attn = 12 * c.num_layers * c.hidden_size * c.max_seq_len
        return 6 * n + attn
