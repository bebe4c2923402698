"""LLaMA-family decoder model — the hybrid-parallel north star.

Capability parity with the reference's LLaMA support (reference: the
fleet hybrid-parallel stack is exercised by PaddleNLP's LLaMA configs —
test/auto_parallel fixtures; RoPE/RMSNorm/SwiGLU ops in
paddle/phi/ops/yaml: rms_norm, swiglu, fused_rope). TPU-native: RoPE is a
fused jnp expression, attention is the Pallas flash kernel (or ring
attention over the sep axis for long context), GQA repeats KV heads inside
the kernel-feeding reshape, and mp_degree>1 builds the Megatron TP layers
so weights carry 'mp' shardings.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..core import dispatch
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.parameter import ParamAttr
from ._head import next_token_loss


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 0            # 0 -> = num_heads (MHA); < heads = GQA
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    use_flash_attention: bool = True
    tie_embeddings: bool = False
    mp_degree: int = 1
    sequence_parallel: bool = False
    context_parallel: str = ""       # "", "ring", "ulysses"
    recompute: bool = False          # activation-checkpoint every block

    def __post_init__(self):
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        if self.context_parallel not in ("", "ring", "ulysses"):
            raise ValueError(f"bad context_parallel "
                             f"{self.context_parallel!r}")


def llama_7b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama_tiny(**kw) -> LlamaConfig:
    kw.setdefault("vocab_size", 512)
    kw.setdefault("hidden_size", 128)
    kw.setdefault("intermediate_size", 256)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_seq_len", 128)
    return LlamaConfig(**kw)


def llama2_13b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 5120)
    kw.setdefault("intermediate_size", 13824)
    kw.setdefault("num_layers", 40)
    kw.setdefault("num_heads", 40)
    return LlamaConfig(**kw)


def llama2_70b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 8192)
    kw.setdefault("intermediate_size", 28672)
    kw.setdefault("num_layers", 80)
    kw.setdefault("num_heads", 64)
    kw.setdefault("num_kv_heads", 8)   # GQA
    return LlamaConfig(**kw)


def rope_rotate(a, theta, pos_offset):
    """The rope rotation on a [B, S, H, D] array — THE one copy of the
    (even, odd)-pair math: `rotary_embedding`'s lowering, the fused
    `rope_proj` composite (the rewrite's numerics reference), and the
    rope autotune probes all call this."""
    b, s, h, d = a.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                             / half))
    off = jnp.asarray(pos_offset, jnp.float32)
    if off.ndim == 2:                          # (B, S): every row's own
        positions = off
    else:
        if off.ndim == 0:
            off = off[None]                    # (1,) broadcast over B
        positions = (off[:, None]
                     + jnp.arange(s, dtype=jnp.float32)[None, :])
    pos = positions[:, :, None] * freqs[None, None, :]
    cos = jnp.cos(pos)[:, :, None, :]          # (B|1, S, 1, half)
    sin = jnp.sin(pos)[:, :, None, :]
    x1, x2 = a[..., :half], a[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(a.dtype)


def rotary_embedding(x, theta: float = 10000.0, pos_offset=0):
    """Apply RoPE to [B, S, H, D] (reference fused_rope op). Pairs are the
    (even, odd) channel convention. ``pos_offset`` may be a python int, a
    traced scalar (cached decoding compiles one step for every position),
    a per-batch ``(B,)`` vector (continuous-batching serving: every
    sequence in the batch sits at a different length), or the ``(B, S)``
    positions themselves (serving: the rows of one program need not be
    consecutive)."""
    def f(a):
        return rope_rotate(a, theta, pos_offset)
    # static (python-int) offsets ride the IR record as semantic attrs
    # so compile/fusion can fold rope into the projection; traced /
    # per-batch offsets keep the op opaque (and unfusable), as before
    attrs = None
    if isinstance(pos_offset, int):
        attrs = {"theta": float(theta), "pos_offset": int(pos_offset)}

        def f(a, theta=float(theta), pos_offset=int(pos_offset),
              __f=f):
            return __f(a)
    return dispatch.call("rotary_embedding", f,
                         [x if isinstance(x, Tensor) else Tensor(x)],
                         attrs=attrs)


def _linears(cfg: LlamaConfig):
    if cfg.mp_degree > 1:
        from ..distributed import fleet
        if cfg.sequence_parallel:
            return (fleet.ColumnSequenceParallelLinear,
                    fleet.RowSequenceParallelLinear,
                    fleet.VocabParallelEmbedding)
        return (fleet.ColumnParallelLinear, fleet.RowParallelLinear,
                fleet.VocabParallelEmbedding)
    return None, None, None


def _make_linear(cls, in_f, out_f, is_row=False):
    if cls is None:
        return nn.Linear(in_f, out_f, bias_attr=False,
                         weight_attr=ParamAttr(initializer=Normal(0, 0.02)))
    if is_row:
        return cls(in_f, out_f, has_bias=False, input_is_parallel=True)
    return cls(in_f, out_f, has_bias=False, gather_output=False)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        col, row, _ = _linears(cfg)
        h = cfg.hidden_size
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = _make_linear(col, h, h)
        self.k_proj = _make_linear(col, h, kv)
        self.v_proj = _make_linear(col, h, kv)
        self.o_proj = _make_linear(row, h, h, is_row=True)

    def forward(self, x, cache=None, pos: int = 0):
        b, s, h = x.shape
        hd, nh, nkv = self.head_dim, self.num_heads, self.num_kv_heads
        q = ops.reshape(self.q_proj(x), [b, s, nh, hd])
        k = ops.reshape(self.k_proj(x), [b, s, nkv, hd])
        v = ops.reshape(self.v_proj(x), [b, s, nkv, hd])
        q = rotary_embedding(q, self.cfg.rope_theta, pos_offset=pos)
        k = rotary_embedding(k, self.cfg.rope_theta, pos_offset=pos)
        if cache is not None:
            return self._cached_attention(x, q, k, v, cache, pos)
        if nkv != nh:   # GQA: repeat kv heads
            rep = nh // nkv
            k = ops.reshape(
                ops.tile(ops.unsqueeze(k, 3), [1, 1, 1, rep, 1]),
                [b, s, nh, hd])
            v = ops.reshape(
                ops.tile(ops.unsqueeze(v, 3), [1, 1, 1, rep, 1]),
                [b, s, nh, hd])
        cp = self.cfg.context_parallel
        if cp == "ring":
            from ..distributed.fleet import ring_flash_attention
            out = ring_flash_attention(q, k, v, causal=True)
        elif cp == "ulysses":
            from ..distributed.fleet import scatter_gather_attention
            out = scatter_gather_attention(q, k, v, causal=True)
        elif self.cfg.use_flash_attention:
            out, _ = F.flash_attention(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(ops.reshape(out, [b, s, h]))

    def _cached_attention(self, x, q, k, v, cache, pos: int):
        """Decode-time attention against the KV cache (reference cached
        decoding in fused_multi_transformer): writes this step's K/V at
        ``pos`` and attends the query over all cached positions <= its
        global position. Returns (out, new_cache)."""
        import jax
        b, s, h = x.shape
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        scale = 1.0 / math.sqrt(hd)

        def f(qa, ka, va, kc, vc):
            zero = jnp.asarray(0, jnp.int32)
            p0 = jnp.asarray(pos, jnp.int32)
            kc = jax.lax.dynamic_update_slice(kc, ka,
                                              (zero, p0, zero, zero))
            vc = jax.lax.dynamic_update_slice(vc, va,
                                              (zero, p0, zero, zero))
            kk, vv = kc, vc
            if nkv != nh:
                rep = nh // nkv
                kk = jnp.repeat(kc, rep, axis=2)
                vv = jnp.repeat(vc, rep, axis=2)
            logits = jnp.einsum("bqhd,bkhd->bhqk", qa,
                                kk).astype(jnp.float32) * scale
            total = kk.shape[1]
            kpos = jnp.arange(total)[None, None, None, :]
            qpos = (p0 + jnp.arange(s))[None, None, :, None]
            logits = jnp.where(kpos <= qpos, logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(qa.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
            return out.reshape(b, s, nh * hd), kc, vc

        out, kc, vc = dispatch.call(
            "llama_cached_attention", f,
            [q, k, v, Tensor(cache["k"]), Tensor(cache["v"])])
        return self.o_proj(out), {"k": kc._data, "v": vc._data}


class LlamaMLP(nn.Layer):
    """SwiGLU MLP: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        col, row, _ = _linears(cfg)
        h, ffn = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _make_linear(col, h, ffn)
        self.up_proj = _make_linear(col, h, ffn)
        self.down_proj = _make_linear(row, ffn, h, is_row=True)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaBlock(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cache=None, pos: int = 0):
        if cache is not None:
            att, new_cache = self.self_attn(self.input_layernorm(x),
                                            cache=cache, pos=pos)
            x = x + att
            return x + self.mlp(self.post_attention_layernorm(x)), \
                new_cache
        with jax.named_scope("attn"):
            x = x + self.self_attn(self.input_layernorm(x))
        with jax.named_scope("mlp"):
            return x + self.mlp(self.post_attention_layernorm(x))


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        _, _, vemb = _linears(cfg)
        if vemb is not None:
            self.embed_tokens = vemb(cfg.vocab_size, cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(
                cfg.vocab_size, cfg.hidden_size,
                weight_attr=ParamAttr(initializer=Normal(0, 0.02)))
        self.layers = nn.LayerList([LlamaBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        if self.cfg.recompute:
            from ._remat import remat_block
            for blk in self.layers:
                x = remat_block(blk, x)
        else:
            for blk in self.layers:
                x = blk(x)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if cfg.tie_embeddings:
            self.lm_head = None
        else:
            col, _, _ = _linears(cfg)
            # vocab-parallel head under TP: the [hidden, vocab] matrix is
            # the largest in the model and must shard over 'mp'
            self.lm_head = _make_linear(col, cfg.hidden_size,
                                        cfg.vocab_size)

    def forward(self, input_ids, labels=None):
        """Logits; with ``labels``, ``(None, loss)``: the head's product is
        inside the loss (``_head.next_token_loss``)."""
        h = self.model(input_ids)
        tied = self.lm_head is None
        if labels is not None:
            table = self.model.embed_tokens if tied else self.lm_head
            return None, next_token_loss(h, table.weight, labels,
                                         transpose_y=tied)
        with jax.named_scope("lm_head"):
            if tied:
                return ops.matmul(h, self.model.embed_tokens.weight,
                                  transpose_y=True)
            return self.lm_head(h)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    @dispatch.no_grad()
    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, use_cache: bool = True):
        """Autoregressive decode. ``use_cache=True`` (default) runs a
        KV-cached jitted decode loop — prefill once, then one [B, 1] step
        per token against the cache (reference: the fused_multi_transformer
        cached-decoding path); ``use_cache=False`` recomputes the full
        context every token (numerics ground truth)."""
        from ..core.generator import next_key
        import jax
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(input_ids))
        # identical RNG contract on both paths: greedy consumes no keys;
        # sampling pre-splits one stream of per-token keys
        keys = (jax.random.split(next_key(), max_new_tokens)
                if temperature > 0 else
                jnp.zeros((max_new_tokens, 2), jnp.uint32))
        if not use_cache:
            for i in range(max_new_tokens):
                logits = self(ids)
                last = logits[:, -1, :]
                if temperature > 0:
                    nxt = jax.random.categorical(
                        keys[i], last._data / temperature, axis=-1)
                else:
                    nxt = jnp.argmax(last._data, axis=-1)
                ids = ops.concat([ids, Tensor(nxt[:, None].astype(
                    ids._data.dtype))], axis=1)
            return ids
        return self._generate_cached(ids, max_new_tokens, temperature,
                                     keys)

    def _decode_logits(self, token_arr, cache, pos: int):
        """One cached step: token_arr [B, t]; returns (last-token logits,
        new cache) — traced under jit by _generate_cached."""
        h = self.model.embed_tokens(Tensor(token_arr))
        new_cache = []
        for li, blk in enumerate(self.model.layers):
            h, c = blk(h, cache=cache[li], pos=pos)
            new_cache.append(c)
        h = self.model.norm(h)
        if self.lm_head is None:
            logits = ops.matmul(h, self.model.embed_tokens.weight,
                                transpose_y=True)
        else:
            logits = self.lm_head(h)
        return logits._data[:, -1, :], new_cache

    def _generate_cached(self, ids: Tensor, max_new_tokens: int,
                         temperature: float, keys):
        import jax
        cfg = self.cfg
        b, prompt_len = ids.shape
        total = prompt_len + max_new_tokens
        hd = cfg.hidden_size // cfg.num_heads
        params = list(self.parameters())
        # the cache holds K/V in the model's compute dtype (bf16 serving
        # weights project bf16 K/V; PagedEngine sizes its pages the same)
        kv_dtype = next((p._data.dtype for p in params
                         if jnp.issubdtype(p._data.dtype, jnp.floating)),
                        jnp.float32)
        cache = [
            {"k": jnp.zeros((b, total, cfg.num_kv_heads, hd), kv_dtype),
             "v": jnp.zeros((b, total, cfg.num_kv_heads, hd), kv_dtype)}
            for _ in range(cfg.num_layers)]

        def with_params(fn):
            def wrapped(pa, *args):
                originals = [p._data for p in params]
                for p, a in zip(params, pa):
                    p._data = a
                try:
                    return fn(*args)
                finally:
                    for p, o in zip(params, originals):
                        p._data = o
            return wrapped

        # ONE compiled program: prefill + a lax.scan over decode steps
        # (pos is a traced scalar; the cache lives in the scan carry, so
        # there is a single device dispatch for the whole generation)
        tok_dtype = ids._data.dtype

        def decode_all(prompt, cache_, keys):
            logits, cache_ = self._decode_logits(prompt, cache_, 0)

            def body(carry, key):
                logits, cache_, pos = carry
                if temperature > 0:
                    nxt = jax.random.categorical(
                        key, logits / temperature, axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                logits, cache_ = self._decode_logits(
                    nxt[:, None].astype(tok_dtype), cache_, pos)
                return (logits, cache_, pos + 1), nxt

            init = (logits, cache_, jnp.asarray(prompt_len, jnp.int32))
            (_, _, _), new_toks = jax.lax.scan(body, init, keys)
            return jnp.swapaxes(new_toks, 0, 1).astype(tok_dtype)  # [B, n]

        if not hasattr(self, "_decode_jit"):
            self._decode_jit = {}
        # the concrete temperature is baked into the compiled body, so it
        # must key the cache; cap the cache (serving with many distinct
        # prompt lengths should bucket/pad prompts instead)
        jit_key = (b, prompt_len, max_new_tokens, float(temperature))
        fn = self._decode_jit.get(jit_key)
        if fn is None:
            if len(self._decode_jit) >= 16:
                self._decode_jit.pop(next(iter(self._decode_jit)))
            fn = jax.jit(with_params(decode_all))
            self._decode_jit[jit_key] = fn

        pa = [p._data for p in params]
        new_toks = fn(pa, ids._data, cache, keys)
        return Tensor(jnp.concatenate([ids._data, new_toks], axis=1))
