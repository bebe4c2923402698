"""Continuous-batching LLM serving over paged KV caches.

Reference surface: the block-attention serving op family
(phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
fused_multi_transformer cached decoding) that PaddleNLP's serving stack
drives. TPU-native redesign: the whole decode tick for every in-flight
request is ONE jitted SPMD-friendly program — paged K/V caches live as
donated device arrays, a host-side BlockManager owns the physical-block
free list, and admission/eviction is plain Python between ticks:

* prefill runs one request a program, oldest admission first, in chunks
  of W tokens: ONE compiled shape, (1, W), that carries only the
  prefilling slot's rows and block table and appends its K/V pages via
  ``nn.functional.block_multihead_attention``. W is a multiple of
  ``block_size`` the engine derives (``prefill_width``): the tick's
  whole prefill budget under a phase-split scheduler
  (``paddle_tpu.serving.Scheduler``), so the budgeted chunk interleaves
  with decode and a long prompt stops stalling every in-flight stream's
  inter-token latency; ``_PREFILL_WIDTH`` without one. A prefix is
  left-padded to a multiple of W, so its last chunk ends on the
  prompt's last token;
* a tick's programs are chunk, step, or chunk-with-step. A prefill chunk
  and a decode step each stream every weight for a few rows; where a tick
  holds a chunk to launch AND a decode step with a lane to feed, the
  tick's LAST chunk and the step go out as ONE program
  (``paged_mixed_step``): what is row-wise (embedding, norms,
  projections, MLPs, experts, the head) runs once over the chunk's W rows
  and the lanes' B rows, so a weight leaves HBM once, and every mixer
  (paged / window / latent attention, a recurrence) runs on each group's
  own rows through the path it has (``_PagedCache``'s groups). Earlier
  chunks of the same tick, and a tick with only one of the two, launch
  the program they always did. What decides is what the tick holds, no
  option; ``health()["mixed_share"]`` is the share of decode steps that
  went out with a chunk aboard. The slot a final chunk finishes takes its
  first token from that program, so it joins the NEXT tick's step. One
  tick in ``share_window_ticks`` (32) that could mix launches the two
  apart, so that each program's own device time stays readable (the
  phase-share gauge, a device trace) where every step rides a chunk;
* decode runs ALL active slots in one (B, 1) step; idle slots point at a
  reserved trash block so the compiled program never branches on
  occupancy. Its attention is the ``paged_decode_attn`` Pallas kernel
  where the step's shapes allow (``health()["decode_attention"]``): each
  lane reads the pages it holds, not its whole block table. With
  ``speculate=`` the decode step becomes a speculative
  verify: draft tokens appended to the feed, one (B, k+1) forward, and
  the accept-prefix rule in-graph — still ONE compiled program, now
  yielding up to k+1 tokens per request per tick;
* positions are per-slot (each sequence is at a different length — the
  batch shares one program, not one position): RoPE offsets for Llama,
  learned-position gathers for GPT (architecture adapters `_LlamaArch` /
  `_GPTArch`);
* what a layer keeps follows from the model's ``cache_layout``, one of
  five state kinds or several of them: K/V pages (``paged_kv``:
  full-attention layers), latent pages (``latent_kv``: latent-attention
  layers, ONE pool of rows ``[c | k_r]`` a layer under the same block
  table; these two kinds alone have page pools, so only they make a block
  cost bytes), a fixed ring of K/V rows a slot (``window_kv``:
  sliding-window layers, ``window + prefill_width`` rows whatever the
  context), a recurrent state a slot (``slot_state``: arrays of any
  shape and dtype behind the slot axis, a convolution window beside an SSM
  state, or beside a delta-rule layer's float32 matrix a head, megabytes
  a lane, which its layer zeroes and keeps itself in the one visit it
  makes: ``recur(..., masks=True)``), a device-side counter
  (``accumulator``); Llama and GPT keep K/V pages in every layer;
* K/V pages are stored in the model's compute dtype, or as an int8 page
  pool with sidecar per-(position, head) scales (``kv_dtype="int8"`` —
  the ``nn/quant`` weight-only pattern applied to KV), halving resident
  KV vs bf16 and roughly doubling the resident batch a chip can hold.

One program stays in flight: a tick plans, builds and launches its
program(s) first and only then reads the tokens of the program launched
before, so the host's work for a tick (read, emit, deliver, admit, plan,
build) runs while the chip executes that tick's program. The token a
lane feeds stays on the device (the decode step's output is the next
step's input), the host counts the tokens it has launched but not read,
and ``step()`` returns with its last program still running: its tokens
are delivered by the next ``step()``. ``speculate=`` engines (the next
rows depend on the accepted count) and the dense scorer read their
program in the tick that launched it, and keep chunk and step apart.

Sampling is per-request deterministic: every sampled token draws from a
key folded from (engine seed, request id, token position), so a request
preempted and re-prefilled resumes the SAME sampled continuation — a
replica restart or recompute preemption is invisible in the tokens.

Greedy numerics are locked to the training models by token-parity tests
against ``LlamaForCausalLM.generate`` and a full-recompute GPT greedy
loop; the int8-KV and speculative paths are parity-gated greedy-token-
identical against the baseline engine.

Resilience contract (see ``inference/resilience.py`` and README "Serving
resilience"): the tick loop never raises — overload, deadline expiry,
memory races and injected faults become per-request terminal statuses
(``FINISHED/SHED/DEADLINE_MISSED/CANCELLED/FAILED``) recorded in
``engine.outcomes``; submitters see :class:`Overloaded` backpressure from
the bounded queue; the replica walks an explicit lifecycle
(``STARTING→WARMING→READY→DEGRADED→DRAINING→STOPPED``) with ``drain()``
and health/readiness probes, and a stalled tick flips it DEGRADED via the
attached watchdog. ``engine.stream(rid)`` exposes per-request incremental
tokens under the same nothing-raises contract (the stream ends with the
terminal status). The multi-replica front door over R engines is
``paddle_tpu.serving.Router``.
"""
from __future__ import annotations

import contextlib
import time
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn.functional.paged_attention import log_paths as _attention_paths
from ..observability import reqtrace as _reqtrace
from ..observability import trace as _trace
from .resilience import (Overloaded, ReplicaLifecycle, ReplicaState,
                         RequestOutcome, RequestStatus, ResilienceConfig,
                         TERMINAL_STATUSES)
from . import resilience as _res

__all__ = ["BlockManager", "Request", "PagedEngine", "LlamaPagedEngine",
           "GPTPagedEngine", "Overloaded", "RequestStatus", "ReplicaState",
           "ResilienceConfig", "RequestOutcome"]


class BlockManager:
    """Physical-block free list (block 0 is the reserved trash block idle
    slots write into). One table serves the model: a block id indexes the
    page pool of every ``paged_kv`` layer, so a request's demand in blocks
    does not depend on how many layers page (layers that keep a window or a
    recurrent state hold no pages); what a block costs in bytes does."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (one is reserved)")
        self._free = list(range(num_blocks - 1, 0, -1))

    @property
    def available(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"paged KV cache exhausted: need {n} blocks, "
                f"{len(self._free)} free")
        return [self._free.pop() for _ in range(n)]

    def release(self, blocks: List[int]):
        self._free.extend(b for b in blocks if b != 0)


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0          # 0 = greedy
    top_p: float = 1.0
    generated: List[int] = field(default_factory=list)
    # --- resilience bookkeeping (engine-managed) ---
    status: str = RequestStatus.QUEUED
    detail: str = ""                  # terminal reason for non-FINISHED
    submit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    ttft_deadline_s: Optional[float] = None   # submit → first token
    deadline_s: Optional[float] = None        # submit → completion

    @property
    def seq_len(self) -> int:
        return len(self.prompt) + len(self.generated)


class _LlamaArch:
    """Architecture adapter: per-chunk forward for LlamaForCausalLM."""

    def __init__(self, model):
        self.model = model
        self.cfg = model.cfg
        self.num_kv_heads = model.cfg.num_kv_heads or model.cfg.num_heads

    def forward_chunk(self, tokens, cache, logits_t: int = 1):
        from paddle_tpu import ops
        from ..models.llama import rotary_embedding

        model = self.model
        cfg = self.cfg
        B, T = tokens.shape
        nh = cfg.num_heads
        hd = cfg.hidden_size // nh
        nkv = self.num_kv_heads
        pos = cache.positions
        with jax.named_scope("embed"):
            x = model.model.embed_tokens(Tensor(tokens))
        for li, blk in enumerate(model.model.layers):
            with jax.named_scope("attn"):
                ln = blk.input_layernorm(x)
                q = ops.reshape(blk.self_attn.q_proj(ln), [B, T, nh, hd])
                k = ops.reshape(blk.self_attn.k_proj(ln), [B, T, nkv, hd])
                v = ops.reshape(blk.self_attn.v_proj(ln), [B, T, nkv, hd])
                q = rotary_embedding(q, cfg.rope_theta, pos_offset=pos)
                k = rotary_embedding(k, cfg.rope_theta, pos_offset=pos)
                out = cache.attend(li, q, k, v)
                x = x + blk.self_attn.o_proj(
                    ops.reshape(out, [B, T, nh * hd]))
            with jax.named_scope("mlp"):
                x = x + blk.mlp(blk.post_attention_layernorm(x))
        x = model.model.norm(x)
        last = cache.head_rows(x, logits_t)
        with jax.named_scope("lm_head"):
            if model.lm_head is None:
                return ops.matmul(last, model.model.embed_tokens.weight,
                                  transpose_y=True)
            return model.lm_head(last)


class _GPTArch:
    """Architecture adapter for GPTForCausalLM (learned positions, fused
    qkv, tied head)."""

    def __init__(self, model):
        self.model = model
        self.cfg = model.cfg
        self.num_kv_heads = model.cfg.num_heads
        self.max_positions = model.cfg.max_seq_len

    def forward_chunk(self, tokens, cache, logits_t: int = 1):
        from paddle_tpu import ops

        m = self.model.gpt
        cfg = self.cfg
        B, T = tokens.shape
        nh = cfg.num_heads
        hd = cfg.hidden_size // nh
        # learned positional embeddings at per-row positions; a prefill
        # chunk's left padding sits at negative positions (up to
        # prefill_width - 1 of them), whose rows are discarded: row 0
        # stands in, so no gather reaches outside the table
        pos_idx = jnp.maximum(cache.positions, 0)
        with jax.named_scope("embed"):
            pos_emb = jnp.take(m.wpe.weight._data, pos_idx, axis=0)
            x = m.wte(Tensor(tokens)) + Tensor(pos_emb)
        for li, blk in enumerate(m.blocks):
            with jax.named_scope("attn"):
                ln = blk.ln1(x)
                qkv = blk.attn.qkv_proj(ln)
                q, k, v = ops.split(qkv, 3, axis=-1)
                q = ops.reshape(q, [B, T, nh, hd])
                k = ops.reshape(k, [B, T, nh, hd])
                v = ops.reshape(v, [B, T, nh, hd])
                out = cache.attend(li, q, k, v)
                x = x + blk.attn.out_proj(
                    ops.reshape(out, [B, T, nh * hd]))
            with jax.named_scope("mlp"):
                x = x + blk.mlp(blk.ln2(x))
        x = m.ln_f(x)
        last = cache.head_rows(x, logits_t)
        with jax.named_scope("lm_head"):
            return ops.matmul(last, m.wte.weight, transpose_y=True)


class _DenseArch:
    """Adapter for dense-scoring models (DLRM / two-tower recsys): the
    model provides ``serve_dense(flat_ids) -> (B,) scores in [0, 1]``
    plus ``serve_dense_width`` (the flat-id row width requests pad to).
    No KV cache, no positions, no autoregression — each request is ONE
    forward that emits a single "score token" (the score in basis
    points), so the whole engine surface (Router placement, outcomes,
    streams, SLO burn, warmup/drain) works unchanged on top of it."""

    def __init__(self, model):
        self.model = model
        self.width = int(model.serve_dense_width)


def _pick_arch(model):
    from ..models.gpt import GPTForCausalLM
    from ..models.llama import LlamaForCausalLM
    if hasattr(model, "paged_adapter"):
        # the protocol: a model brings its own adapter (``cfg``,
        # ``num_kv_heads``, ``head_dim``, ``cache_layout(dtype)``: one entry
        # a layer, a state or a tuple of states, and
        # ``forward_chunk(tokens, cache, logits_t)``: positions, the real
        # rows and the rows the head reads come from the cache handle)
        return model.paged_adapter()
    if isinstance(model, LlamaForCausalLM):
        return _LlamaArch(model)
    if isinstance(model, GPTForCausalLM):
        return _GPTArch(model)
    if hasattr(model, "serve_dense"):
        return _DenseArch(model)
    raise TypeError(
        f"PagedEngine supports LlamaForCausalLM / GPTForCausalLM (or "
        f"subclasses), models that bring a paged_adapter() (cfg, "
        f"num_kv_heads, head_dim, forward_chunk(tokens, cache, "
        f"logits_t) and cache_layout(dtype): per layer None, one state "
        f"('paged_kv',) / ('latent_kv', row_width) / ('window_kv', window) "
        f"/ ('slot_state', {{name: (shape, dtype)}}) / ('accumulator', "
        f"shape, dtype), or a "
        f"tuple of such states), and dense-scoring models exposing "
        f"serve_dense(); got {type(model).__name__}")


#: model -> {(arch name, program kind): jitted tick fn} — shared across
#: engines of one model (entries die with the model; see
#: PagedEngine.__init__)
_PAGED_JIT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

#: tokens a prefill program carries when no scheduler budget says how many
#: a tick may advance: a chunk this wide still costs little more than
#: streaming the weights once, and of 128 / 256 / 512 it served the most
#: tokens a second on a TPU v5e (PERF.md §6 "PR 25"). An engine never
#: runs more rows than its decode batch has lanes x ``block_size``, nor
#: more than one sequence can hold.
_PREFILL_WIDTH = 256

#: seconds a ``health()`` probe may report the expert load it read last
#: before it reads the device counters again (a probe polled every tick
#: must not put a device read into every tick)
_EXPERT_LOAD_POLL_S = 1.0


def _request_keys(base_key, rids, ngens):
    """Per-slot sampling keys folded from (engine seed, request id, token
    position): a request's key stream depends only on its own identity
    and how many tokens it has sampled, NEVER on which tick/slot/batch
    it happens to run in — preemption, re-admission and replica restarts
    reproduce the same sampled continuation under a fixed seed."""
    return jax.vmap(lambda r, n: jax.random.fold_in(
        jax.random.fold_in(base_key, r), n))(rids, ngens)


def _sample_tokens(logits, temps, top_ps, base_key, rids, ngens,
                   sampling: bool):
    """Per-slot greedy / temperature / nucleus sampling — the same
    kernel as ops.top_p_sampling (shared helper), keyed per (request,
    position) so the program is reusable across calls AND deterministic
    per request (see _request_keys). ``sampling`` is STATIC: the
    all-greedy tick (the common serving batch) compiles without the
    sort/cumsum/gumbel kernel at all — a smaller, faster program; the
    sampled variant traces only once a sampled request enters the
    batch."""
    greedy = jnp.argmax(logits, axis=-1)
    if not sampling:
        return greedy
    from ..ops.search import nucleus_sample_ids
    safe_t = jnp.maximum(temps, 1e-6)[:, None]
    probs = jax.nn.softmax(logits / safe_t, axis=-1)
    keys = _request_keys(base_key, rids, ngens)
    sampled = jax.vmap(
        lambda pr, pp, kk: nucleus_sample_ids(
            pr[None], pp[None, 0], kk)[0, 0])(
        probs, top_ps[:, None], keys)
    return jnp.where(temps > 0, sampled, greedy)


@jax.jit
def _feed_tokens(unread, on_host, from_host):
    """The decode step's (B, 1) feed while the program that sampled some of
    its tokens is still unread: ``unread`` is that program's output on the
    device (a decode step's (B,) tokens, or the (1,) first token of a
    slot's final prefill chunk), ``on_host`` the tokens the host has read,
    ``from_host`` (B,) which lanes take the host's."""
    return jnp.where(from_host, on_host, unread)[:, None]


@dataclass
class _Launched:
    """A launched program whose outputs the host has not read yet."""
    phase: str                 # "prefill" / "decode": its span and gauge
    #: "prefill" / "decode" / "verify" / "mixed" (a decode step with a
    #: chunk aboard): its emit, its program
    kind: str
    outs: list                 # the device arrays it hands the host
    t0: float                  # host clock at its build
    #: phase -> rows x width, for ``scheduler.note_phase`` (a mixed step
    #: has rows of both phases: its seconds are split by them)
    positions: Dict[str, int]
    tick: int
    #: ``(slot, tenancy)`` of each lane it sampled a token for; a lane
    #: released since (finished, cancelled, evicted, expired) drops it
    lanes: list = field(default_factory=list)
    meta: dict = field(default_factory=dict)


def _bind_params(params, param_arrays):
    """Swap traced arrays into the model's Parameter objects; returns
    the originals for the caller's finally-restore."""
    originals = [p._data for p in params]
    for p, a in zip(params, param_arrays):
        p._data = a
    return originals


def _array(x):
    """A Tensor's array, an array as it is."""
    return x._data if isinstance(x, Tensor) else x


@dataclass
class _Rows:
    """One group of a program's rows: ``rows`` sequences of ``width`` new
    tokens each, with the block tables, lengths and slots they belong to."""
    tables: Tensor
    seq_lens: Tensor
    start: "jax.Array"          # (rows,) position of a sequence's first row
    lanes: Optional["jax.Array"]
    rows: int
    width: int

    @property
    def positions(self):
        """(rows, width) position of every row in its sequence."""
        return self.start[:, None] + jnp.arange(
            self.width, dtype=self.start.dtype)[None, :]


class _PagedCache:
    """The one cache handle a model's ``forward_chunk`` sees for a program's
    rows. The model's adapter declares per layer which kind of state it
    keeps (``cache_layout``); the handle serves each kind:

    * ``attend(li, q, k, v)`` — paged K/V (attention layers): append the
      chunk's K/V pages and attend over the slot's block table. Cache
      entries are arrays (float pages) or (payload, scales) tuples (int8
      pages) — the structure picks the kernel path at trace time.
    * ``attend_latent(li, q, rows, value_dim, scale)`` — latent pages
      (latent-attention layers): append the chunk's rows ``[c | k_r]`` to
      the layer's ONE pool and attend over the slot's block table in the
      absorbed form (``nn.functional.latent_paged_attention``): a row is
      its token's key and, in its first ``value_dim`` columns, its value.
    * ``attend(li, q, k, v, window=w)`` — window K/V (sliding-window
      attention layers): per SLOT a fixed ring of rows that does not grow
      with the sequence; the chunk's K/V are written round-robin by
      position and a query sees the ``w`` positions up to its own
      (``nn.functional.window_ring_attention``). It follows ``recur``'s
      rules: rows a lane's own sequence did not write are never seen, left
      padding and the ``seq = 0`` sentinel lanes write nothing.
    * ``recur(li, fn, *rows)`` — per-SLOT state that does not grow with the
      sequence (a convolution window, an SSM state, a delta-rule matrix):
      ``fn(state, *rows) -> (out, new state)`` runs on the lanes' states
      and their rows of each of ``rows``. A lane whose chunk starts a
      sequence (``start <= 0``) starts from zeros; a lane with no real row
      (the ``seq = 0`` sentinel of mid-prefill and memory-stalled lanes)
      gets its state back bit for bit. With ``masks=True`` the handle
      leaves those two rules to ``fn(state, *rows, fresh, idle)`` ((lanes,)
      bool each): a state of megabytes a lane is then zeroed and kept
      inside the one visit ``fn`` makes, where the handle's own ``where``
      before and after would each be another pass over it.
    * ``accumulate(li, delta)`` — a device-side counter carried with the
      caches (expert load), read by the host only on request.

    A layer may keep more than one state (attention state and an expert
    counter): ``index`` maps ``(layer, kind)`` to the state's position.
    ``states`` is a flat list over the latent pools, window rows, slot
    states and counters; ``lanes`` (B,) maps the chunk's rows to slots
    (None: row i is slot i, the decode batch).

    **Rows and groups.** The program's rows are one group, (B, T): B
    sequences of T new tokens, the layout the model's tensors have. Or
    several (``riders``, each ``(tables, seq_lens, start, lanes, width)``: a
    prefill chunk with the decode batch riding it): the model's tensors are
    then ONE row (1, sum of B x T) holding each group's rows in order, what
    is row-wise (projections, norms, MLPs, experts, the head) runs once over
    all of them, and every method above runs group by group on that group's
    rows, tables and lanes, over the same pools. What a model needs of a
    row beside its values comes from the handle in the tensors' layout:
    ``positions`` (negative: left padding, a sentinel lane), ``valid``
    (positions >= 0) and ``head_rows(x, n)``, the last ``n`` rows of every
    sequence, which the head is applied to."""

    def __init__(self, index, kcs, vcs, states, tables, seq_lens, start,
                 lanes, width, riders=()):
        # (layer, kind) -> position; None: paged K/V in every layer. The
        # form of one state a layer, layer -> (kind, position), is taken too
        if index is None:
            index = {(li, "paged_kv"): li for li in range(len(kcs))}
        self.index = {
            (key if isinstance(key, tuple) else (key, at[0])):
            (at if isinstance(key, tuple) else at[1])
            for key, at in index.items()}
        self.kcs, self.vcs, self.states = kcs, vcs, list(states)
        self.groups = [
            _Rows(Tensor(tb), Tensor(sl), st, ln, tb.shape[0], w)
            for tb, sl, st, ln, w in ((tables, seq_lens, start, lanes,
                                       width),) + tuple(riders)]
        self.positions = self._as_rows([g.positions for g in self.groups])
        self.valid = self.positions >= 0

    # ------------------------------------------------- rows <-> groups
    @staticmethod
    def _as_rows(per_group):
        """Arrays (B, T, ...), one a group, in the tensors' layout."""
        if len(per_group) == 1:
            return per_group[0]
        return jnp.concatenate(
            [a.reshape((1, -1) + a.shape[2:]) for a in per_group], axis=1)

    def _per_group(self, *xs):
        """Per group the (B, T, ...) rows it has of each of ``xs``."""
        if len(self.groups) == 1:
            return [xs]
        out, at = [], 0
        for g in self.groups:
            n = g.rows * g.width
            out.append(tuple(
                Tensor(a[0, at:at + n].reshape((g.rows, g.width)
                                               + a.shape[2:]))
                for a in map(_array, xs)))
            at += n
        return out

    def _each(self, fn, *xs):
        """``fn(group, *its rows of xs)`` a group, joined in the tensors'
        layout."""
        outs = [fn(g, *mine)
                for g, mine in zip(self.groups, self._per_group(*xs))]
        if len(outs) == 1:
            return outs[0]
        return Tensor(self._as_rows([_array(o) for o in outs]))

    def head_rows(self, x, n: int = 1) -> Tensor:
        """The last ``n`` rows of every sequence of ``x`` (B, T, hidden)."""
        return self._each(lambda _g, mine: Tensor(mine._data[:, -n:, :]), x)

    # ------------------------------------------------------ the states
    def attend(self, li, q, k, v, window=None):
        if window is not None:
            return self._each(
                lambda g, *qkv: self._attend_window(g, li, *qkv, window),
                q, k, v)
        return self._each(lambda g, *qkv: self._attend_paged(g, li, *qkv),
                          q, k, v)

    def _attend_paged(self, g, li, q, k, v):
        import paddle_tpu.nn.functional as F

        kcs, vcs = self.kcs, self.vcs
        li = self.index[li, "paged_kv"]
        if isinstance(kcs[li], tuple):
            (kp, ksc), (vp, vsc) = kcs[li], vcs[li]
            out, nkp, nvp, nks, nvs = F.block_multihead_attention(
                q, Tensor(kp), Tensor(vp), g.tables, g.seq_lens,
                new_k=k, new_v=v, causal=True,
                k_scale=Tensor(ksc), v_scale=Tensor(vsc))
            kcs[li] = (nkp._data, nks._data)
            vcs[li] = (nvp._data, nvs._data)
        else:
            out, nkc, nvc = F.block_multihead_attention(
                q, Tensor(kcs[li]), Tensor(vcs[li]), g.tables,
                g.seq_lens, new_k=k, new_v=v, causal=True)
            kcs[li] = nkc._data
            vcs[li] = nvc._data
        return out

    def attend_latent(self, li, q, rows, value_dim, scale):
        import paddle_tpu.nn.functional as F

        at = self.index[li, "latent_kv"]

        def attend(g, q, rows):
            pool = self.states[at]          # (num_blocks, block_size, D)
            # the pool's rows are whole lane tiles: zeros past [c | k_r],
            # in the queries too
            pad = pool.shape[-1] - rows.shape[-1]
            q, rows = q._data, rows._data
            if pad:
                q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad),))
                rows = jnp.pad(rows, ((0, 0),) * 2 + ((0, pad),))
            out, new = F.latent_paged_attention(
                Tensor(q), Tensor(pool), g.tables, g.seq_lens,
                Tensor(rows), value_dim, scale)
            self.states[at] = new._data
            return out

        return self._each(attend, q, rows)

    @staticmethod
    def _lane_rows(whole, lanes):
        """The lanes' rows of a per-slot state ``{name: (max_batch,
        ...)}``."""
        if lanes is None:
            return whole
        return {k: jnp.concatenate(
            [jax.lax.dynamic_slice_in_dim(v, lanes[b], 1)
             for b in range(lanes.shape[0])]) for k, v in whole.items()}

    def _put_lane_rows(self, at, whole, new, lanes):
        if lanes is None:
            self.states[at] = new
            return
        for k, v in new.items():
            for b in range(lanes.shape[0]):
                whole[k] = jax.lax.dynamic_update_slice_in_dim(
                    whole[k], v[b:b + 1], lanes[b], 0)
        self.states[at] = whole

    def _attend_window(self, g, li, q, k, v, window):
        import paddle_tpu.nn.functional as F

        at = self.index[li, "window_kv"]
        whole = dict(self.states[at])      # {"k", "v": (max_batch, R, ..)}
        mine = self._lane_rows(whole, g.lanes)
        out, nk, nv = F.window_ring_attention(
            q, Tensor(mine["k"]), Tensor(mine["v"]), g.seq_lens, k, v,
            window=window)
        self._put_lane_rows(at, whole, {"k": nk._data, "v": nv._data},
                            g.lanes)
        return out

    def recur(self, li, fn, *rows, masks=False):
        at = self.index[li, "slot_state"]

        def per_lane(flag, v):
            return flag.reshape((-1,) + (1,) * (v.ndim - 1))

        def run(g, *rows):
            whole = dict(self.states[at])      # {name: (max_batch, ...)}
            mine = self._lane_rows(whole, g.lanes)
            fresh = g.start <= 0
            if masks:       # fn zeroes and keeps as it visits the state
                idle = ~jnp.any(g.positions >= 0, axis=1)
                out, new = fn(mine, *rows, fresh, idle)
                self._put_lane_rows(at, whole, new, g.lanes)
                return out
            out, new = fn({k: jnp.where(per_lane(fresh, v),
                                        jnp.zeros_like(v), v)
                           for k, v in mine.items()}, *rows)
            idle = ~jnp.any(g.positions >= 0, axis=1)
            new = {k: jnp.where(per_lane(idle, v), mine[k],
                                v.astype(mine[k].dtype))
                   for k, v in new.items()}
            self._put_lane_rows(at, whole, new, g.lanes)
            return out

        return self._each(run, *rows)

    def accumulate(self, li, delta):
        at = self.index[li, "accumulator"]
        self.states[at] = self.states[at] + delta.astype(
            self.states[at].dtype)


def _max_over_mean(tokens):
    """Per row of host counts the largest over the mean (None for a row
    of zeros): 1.0 is a perfectly even load."""
    return [float(row.max() / row.mean()) if row.any() else None  # tpulint: disable=TPU103 — host numpy totals
            for row in tokens]


_STATE_KINDS = ("paged_kv", "latent_kv", "window_kv", "slot_state",
                "accumulator")

#: lanes of a vector register: a latent row is stored in whole tiles of them
_LANE_TILE = 128


def _layer_states(layout):
    """``(layer, state)`` for every state of a ``cache_layout``, in order:
    a layer's entry is None, one state (a tuple that starts with its
    kind) or a tuple of states."""
    for li, entry in enumerate(layout):
        if entry is None:
            continue
        for state in ((entry,) if isinstance(entry[0], str) else entry):
            if state[0] not in _STATE_KINDS:
                raise ValueError(f"layer {li}: unknown cache state kind "
                                 f"{state[0]!r}; known: {_STATE_KINDS}")
            yield li, state


def _cache_index(layout):
    """``(layer, kind)`` -> position among the states of its list: paged
    K/V states index ``kcs`` / ``vcs``; latent pools, window rows, slot
    states and accumulators share the flat ``states`` list. A layer keeps at most one
    state of a kind."""
    index, pages, states = {}, 0, 0
    for li, state in _layer_states(layout):
        if (li, state[0]) in index:
            raise ValueError(f"layer {li} declares two {state[0]!r} states")
        if state[0] == "paged_kv":
            index[li, "paged_kv"] = pages
            pages += 1
        else:
            index[li, state[0]] = states
            states += 1
    return index


def _paged_forward(arch, params, param_arrays, kcs, vcs, tokens, seq_lens,
                   tables, temps, top_ps, rids, ngens, base_key, states=(),
                   lanes=None, sampling: bool = False, index=None):
    """One chunk for a (B, T) token batch; returns (next-token ids, new
    caches). Traced under jit. A module-level function (arch + params
    pre-bound via functools.partial) so the shared jit cache holds only
    the model's small adapter/parameter objects — NEVER an engine
    instance, whose paged K/V arrays are the largest allocation in the
    process."""
    originals = _bind_params(params, param_arrays)
    try:
        B, T = tokens.shape
        start = seq_lens - T
        cache = _PagedCache(index, kcs, vcs, states, tables, seq_lens,
                            start, lanes, T)
        logits = arch.forward_chunk(tokens, cache)
        nxt = _sample_tokens(logits._data[:, -1, :], temps, top_ps,
                             base_key, rids, ngens, sampling)
        return nxt.astype(jnp.int32), kcs, vcs, cache.states
    finally:
        for p, o in zip(params, originals):
            p._data = o


def _paged_mixed(arch, params, param_arrays, kcs, vcs, tokens, seq_lens,
                 tables, temps, top_ps, rids, ngens, base_key, states, lanes,
                 chunk, sampling: bool = False, index=None):
    """A prefill chunk with the decode step aboard: ``chunk`` is ONE slot's
    ``(tokens (1, W), seq_lens, tables, temps, top_ps, rids, ngens)`` as
    ``_paged_forward`` takes them, ``lanes`` (1,) that slot, the other rows
    the decode batch's (B, 1). One pass over the layers: what is row-wise
    runs once over the W + B rows, so a weight leaves HBM once, and every
    mixer runs on each group's rows through the path it has (the cache
    handle's groups). Returns ``_paged_forward``'s outputs: (B,) next-token
    ids, the chunk's in its own slot's lane (which rides the decode group
    as a sentinel lane; the token means something on a slot's final
    chunk), and the new caches."""
    originals = _bind_params(params, param_arrays)
    try:
        c_tokens, c_seq, c_tables, c_temps, c_top_ps, c_rids, c_ngens = chunk
        B, W = tokens.shape[0], c_tokens.shape[1]
        cache = _PagedCache(
            index, kcs, vcs, states, c_tables, c_seq, c_seq - W, lanes, W,
            riders=((tables, seq_lens, seq_lens - 1, None, 1),))
        logits = arch.forward_chunk(
            jnp.concatenate([c_tokens, tokens.reshape(1, B)], axis=1), cache)
        # logits (1, 1 + B, V): the chunk's last row, then the lanes'
        nxt = _sample_tokens(
            logits._data[0], jnp.concatenate([c_temps, temps]),
            jnp.concatenate([c_top_ps, top_ps]), base_key,
            jnp.concatenate([c_rids, rids]),
            jnp.concatenate([c_ngens, ngens]), sampling).astype(jnp.int32)
        return nxt[1:].at[lanes[0]].set(nxt[0]), kcs, vcs, cache.states
    finally:
        for p, o in zip(params, originals):
            p._data = o


def _paged_verify(arch, params, param_arrays, kcs, vcs, tokens, seq_lens,
                  tables, temps, top_ps, rids, ngens, base_key, states,
                  max_accept, sampling: bool = False, index=None):
    """Speculative verify: one (B, k+1) forward over [last_token, k
    draft tokens] per slot, greedy accept-prefix in-graph — draft
    append, target forward, and acceptance are ONE compiled program with
    a stable shape (``ops.pallas.serving.spec_accept_prefix``). Returns
    (emit (B, k+1) candidate tokens, n_emit (B,) how many of them are
    real, new caches). Sampling slots ride the same program with
    ``max_accept=0``: their position-0 logits sample exactly as a normal
    decode step would (same per-request key), drafts ignored."""
    from ..ops.pallas.serving import spec_accept_prefix

    originals = _bind_params(params, param_arrays)
    try:
        B, T = tokens.shape
        start = seq_lens - T
        cache = _PagedCache(index, kcs, vcs, states, tables, seq_lens,
                            start, None, T)
        logits = arch.forward_chunk(tokens, cache, logits_t=T)
        lg = logits._data                      # (B, T, V)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        first = _sample_tokens(lg[:, 0, :], temps, top_ps,
                               base_key, rids, ngens, sampling)
        emit = jnp.concatenate(
            [jnp.where(temps > 0, first, greedy[:, 0])[:, None],
             greedy[:, 1:]], axis=1)
        n_emit, _accepted = spec_accept_prefix(
            tokens[:, 1:], greedy, max_accept)
        return (emit.astype(jnp.int32), n_emit.astype(jnp.int32), kcs, vcs,
                cache.states)
    finally:
        for p, o in zip(params, originals):
            p._data = o


def _dense_forward(arch, params, param_arrays, ids):
    """Dense-path scoring program: one (B, width) padded id batch in,
    (B,) scores out. Same param-rebinding discipline as _paged_forward
    so the shared jit cache never captures an engine instance."""
    originals = _bind_params(params, param_arrays)
    try:
        scores = arch.model.serve_dense(Tensor(ids))
        return scores._data.astype(jnp.float32)
    finally:
        for p, o in zip(params, originals):
            p._data = o


class PagedEngine:
    """Continuous-batching engine for causal LMs (paged KV caches).

    What a layer keeps follows from the model's ``cache_layout``: pages of
    K/V that grow with the sequence (``paged_kv``: full-attention layers),
    pages of latent rows ``[c | k_r]`` (``latent_kv``: latent-attention
    layers, one pool a layer; these two kinds have page pools, and only
    they make a block cost bytes), a fixed ring of K/V rows a slot
    (``window_kv``: sliding-window layers), a recurrent state a slot
    (``slot_state``), a device-side counter (``accumulator``), or several
    of these. Llama and GPT keep K/V pages in every layer.

    Dense-scoring models (anything exposing ``serve_dense`` /
    ``serve_dense_width``, e.g. :class:`~paddle_tpu.models.DLRM`) run
    on the same engine through the dense path: no KV pool, one forward
    per tick over up to ``max_batch`` queued requests, one score token
    per request — so the Router load-balances recsys replicas exactly
    like LM replicas."""

    @_trace.in_startup_phase("startup.engine_build")
    def __init__(self, model, *, max_batch: int = 8,
                 block_size: int = 16,
                 num_blocks: int = 256, max_blocks_per_seq: int = 32,
                 eos_id: Optional[int] = None, seed: int = 0,
                 kv_dtype=None, scheduler=None, speculate=None,
                 speculate_k: int = 4,
                 resilience: Optional[ResilienceConfig] = None):
        from ..serving.scheduler import Scheduler, SchedulerConfig

        _trace.note_backend(query=True)
        self.model = model
        self.arch = _pick_arch(model)
        # the adapter rides in the compiled programs that engines of one
        # model share (``_PAGED_JIT_CACHE``, entries keyed weakly by the
        # model): it reaches the model through a weak proxy, or an entry's
        # value would hold its own key, and a model that every engine and
        # caller has let go of would keep its parameters on the device
        if getattr(self.arch, "model", None) is model:
            self.arch.model = weakref.proxy(model)
        self._dense = isinstance(self.arch, _DenseArch)
        self.cfg = model.cfg
        self.max_batch = max_batch
        if self._dense:
            # dense path: "block size" only sizes the synthetic warmup
            # prompt — use the model's id-row width so warmup compiles
            # the exact steady-state program
            block_size = self.arch.width
            speculate = None
        if type(block_size) is not int or block_size < 1:
            raise ValueError(
                f"block_size must be a positive int, got {block_size!r}")
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.eos_id = eos_id
        cfg = self.cfg
        if self._dense:
            self.head_dim = 0
            nkv = 0
        else:
            self.head_dim = getattr(self.arch, "head_dim", None) or (
                cfg.hidden_size // cfg.num_heads)
            nkv = self.arch.num_kv_heads
        self.num_kv_heads = nkv

        # ---- phase-split scheduler (paddle_tpu.serving.Scheduler) ----
        if scheduler is None:
            scheduler = Scheduler()
        elif isinstance(scheduler, SchedulerConfig):
            scheduler = Scheduler(scheduler)
        self.scheduler = scheduler
        #: slot -> in-progress chunked-prefill state (padded prefix,
        #: chunk cursor), in admission order; a slot decodes only once
        #: it leaves this map
        self._prefilling: Dict[int, dict] = {}
        #: W, the one width of this engine's prefill program, in whole
        #: blocks: the tick's whole budget where the scheduler has one
        widest = block_size * min(max(1, _PREFILL_WIDTH // block_size),
                                  max_batch, max_blocks_per_seq)
        self.prefill_width = min(
            scheduler.token_quota(block_size) or widest, widest)

        # ---- speculative decoding (paddle_tpu.serving.NgramProposer) --
        if speculate == "ngram":
            from ..serving.speculative import NgramProposer
            speculate = NgramProposer(k=speculate_k)
        self._spec = speculate
        self._spec_k = getattr(speculate, "k", speculate_k)
        self.spec_proposed = 0
        self.spec_accepted = 0

        self.bm = BlockManager(num_blocks)
        self._total_usable = num_blocks - 1
        # K/V pages live in the model's compute dtype (the attention math
        # upcasts to f32 inside the kernel) — a bf16 model must not pay
        # 2x KV HBM for fp32 pages; on a 16 GB chip KV capacity IS the
        # serving ceiling. kv_dtype="int8" swaps in the quantized page
        # pool (payload int8 + per-(position, head) fp32 scales), halving
        # resident KV again vs bf16.
        self._kv_int8 = (kv_dtype == "int8"
                         or (kv_dtype is not None
                             and jnp.dtype(kv_dtype) == jnp.int8))
        compute_dtype = next(
            (p._data.dtype for p in model.parameters()
             if jnp.issubdtype(p._data.dtype, jnp.floating)), jnp.float32)
        self._compute_dtype = compute_dtype
        if self._kv_int8:
            kv_dtype = jnp.int8
        elif kv_dtype is None:
            kv_dtype = compute_dtype
        self.kv_dtype = jnp.dtype(kv_dtype)
        self._kv_shape = (num_blocks, block_size, nkv, self.head_dim)
        self._kv_scale_shape = (num_blocks, block_size, nkv)
        # ---- the cache states a layer declares: ``paged_kv`` (a K and a
        # V page pool, full-attention layers), ``latent_kv`` (ONE page pool
        # [num_blocks, block_size, row width in whole lane tiles] of rows
        # [c | k_r]: latent-attention layers), ``window_kv`` (K/V rows
        # [max_batch, window + prefill_width, KVH, D] written round-robin:
        # sliding-window layers), ``slot_state`` (arrays [max_batch, ...]
        # that do not grow with the sequence: a recurrent layer's window
        # and state), ``accumulator`` (a device-side counter), or a tuple
        # of these. A model's own adapter declares them; Llama and GPT
        # keep pages in every layer. The dense path keeps none.
        if self._dense:
            self._layout = []
        elif hasattr(self.arch, "cache_layout"):
            self._layout = list(self.arch.cache_layout(compute_dtype))
        else:
            self._layout = [("paged_kv",)] * cfg.num_layers
        self._cache_index = _cache_index(self._layout)
        kinds = {state[0] for _li, state in _layer_states(self._layout)}
        #: whether a prefill chunk must say which slot its rows belong to
        self._has_slot_state = bool(kinds & {"slot_state", "window_kv"})
        if "slot_state" in kinds and speculate is not None:
            raise TypeError(
                "speculate= needs a state rollback this engine does not "
                "have: a verify step feeds k draft tokens through the "
                "recurrent layers, and a rejected draft would have to take "
                "its update of the per-slot state (conv window, SSM state) "
                "back. Serve a model with slot_state layers without "
                "speculate=.")
        if "latent_kv" in kinds and self._kv_int8:
            raise TypeError(
                "kv_dtype='int8' quantizes K and V pages per (position, "
                "head); a latent_kv layer keeps one row [c | k_r] a token "
                "that is key and value at once, and no int8 form of it is "
                "built. Serve a model with latent_kv layers without "
                "kv_dtype='int8'.")
        if "window_kv" in kinds and speculate is not None:
            raise TypeError(
                "speculate= needs a state rollback this engine does not "
                "have: a verify step writes k draft tokens' K/V into the "
                "window layers' rows over the oldest positions they hold, "
                "and a rejected draft would have to take its rows back. "
                "Serve a model with window_kv layers without speculate=.")
        self.kc, self.vc, self.state = self._fresh_caches()

        self.tables = np.zeros((max_batch, max_blocks_per_seq), np.int32)
        self.seq_lens = np.ones((max_batch,), np.int32)  # idle: len 1
        self.last_token = np.zeros((max_batch,), np.int32)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.slot_blocks: List[List[int]] = [[] for _ in range(max_batch)]
        #: tokens launched for a slot's tenant and not read yet (0 or 1
        #: between ticks): sequence lengths, block demand, the sampling
        #: key's counter and the ``max_new_tokens`` test count them
        self._inflight = np.zeros((max_batch,), np.int32)
        #: a slot's tenancy, bumped at every release: the read of a
        #: program tells the tenant it was launched for from a successor
        self._tenancy = np.zeros((max_batch,), np.int64)
        #: the one launched program whose outputs the host has not read
        self._unread: Optional[_Launched] = None
        #: whether a tick returns with its last program unread (a verify
        #: step's accepted count decides the next launch's rows, and the
        #: dense scorer keeps no state between ticks: both read at once)
        self._overlap = not self._dense and self._spec is None
        self._launches = self._launches_overlapped = 0
        self._steps = self._steps_mixed = 0
        #: ticks that held a chunk and a step to launch together (every
        #: ``share_window_ticks``-th of them launches the two apart)
        self._mixable = 0
        self._reads = self._reads_late = 0
        self.queue: List[Request] = []
        self.rejected: Dict[int, str] = {}
        self._params = [p for p in model.parameters()]
        # one jit wrapper per program kind: jax.jit itself specializes
        # per (B, T) shape and cache pytree structure. Engines over the
        # SAME model share them — the forward fns read only the model's
        # Parameter objects (identical across engines) and take
        # caches/tables/tokens as arguments, so a second replica reuses
        # compiled programs instead of re-tracing identical ones. The
        # cache lives in a weak side table, NOT on the model: jitted
        # callables hold locks and must not ride through deepcopy/pickle
        # of the model.
        import functools
        cache = _PAGED_JIT_CACHE.setdefault(model, {})
        arch_key = type(self.arch).__name__
        # what the attention layers of each program were lowered to: kept
        # beside the compiled programs, because an engine that shares them
        # traces none of its own
        self._attention_lowered = cache.setdefault((arch_key, "attention"),
                                                   {})

        def program(kind, forward, **jit_kw):
            """The shared jit wrapper of one program kind. ``kind`` is the
            program's name on a trace's ``XLA Modules`` line
            (``jit_<kind>``): the chunk forward is wrapped twice, as
            ``paged_prefill_chunk`` and ``paged_decode_step``, so the two
            phases are told apart there (their shapes differ already, so
            nothing compiles twice); a decode step with a chunk aboard is
            ``paged_mixed_step``."""
            fn = cache.get((arch_key, kind))
            if fn is None:
                bound = functools.partial(forward, self.arch,
                                          tuple(self._params),
                                          **({} if self._dense else
                                             {"index": self._cache_index}))
                bound.__name__ = kind
                fn = cache[(arch_key, kind)] = jax.jit(bound, **jit_kw)
            return fn

        if self._dense:
            self._dense_fn = program("dense_forward", _dense_forward)
            self._fns = self._vfn = None
        else:
            # the K/V pools and the slot states are donated: every program
            # updates them in place
            paged = dict(donate_argnums=(1, 2, 11),
                         static_argnames=("sampling",))
            self._fns = {
                "prefill": program("paged_prefill_chunk", _paged_forward,
                                   **paged),
                "decode": program("paged_decode_step", _paged_forward,
                                  **paged),
                "mixed": program("paged_mixed_step", _paged_mixed, **paged)}
            self._vfn = program("paged_verify", _paged_verify, **paged)
        self._base_key = jax.random.key(seed)
        self._done: List[Request] = []
        self._rid = 0
        # --- resilience state ---
        self.resilience = resilience or ResilienceConfig()
        self._clock = time.monotonic      # seam for deterministic tests
        self.lifecycle = ReplicaLifecycle(clock=self._clock)
        # SLO burn-rate accounting (reqtrace): every terminal outcome
        # feeds the multiwindow burn gauges for this replica's scope
        rc = self.resilience
        self._slo = _reqtrace.SloTracker(
            self.lifecycle.name, target=rc.slo_target,
            fast_window_s=rc.slo_fast_window_s,
            slow_window_s=rc.slo_slow_window_s)
        #: terminal outcome per request (drained by ``drain_outcomes``;
        #: long-running callers should drain it alongside step())
        self.outcomes: Dict[int, RequestOutcome] = {}
        self._ticks = 0
        self.tick_failures = 0
        self._watchdog = None
        # finished results produced while warmup() owned the step loop —
        # re-delivered by the next step()/run_to_completion
        self._spillover: Dict[int, List[int]] = {}
        #: per-request incremental token buffers (see stream())
        self._stream_bufs: Dict[int, List[int]] = {}
        # HBM attribution: KV pages report under the "kv_cache" tag (the
        # getter re-reads kc/vc, which donation replaces every tick)
        from ..observability.perf import memory as _perf_memory
        _perf_memory.register_object(
            "kv_cache", self, lambda e: (e.kc, e.vc, e._latent_pools()))
        _res.M_KV_BYTES_PER_TOKEN.set(self.kv_bytes_per_token)
        _res.M_STATE_BYTES.set(self.state_bytes_per_slot * max_batch)
        _res.M_WINDOW_BYTES.set(self.window_bytes_per_slot * max_batch)
        _res.M_LATENT_BYTES.set(self.latent_bytes)
        #: host-side totals of the expert-load accumulators, one row a
        #: layer that has one (see ``expert_load``)
        self._expert_load, self._expert_load_t = None, 0.0
        # fleet telemetry: this replica's health() rides every
        # fleet.snapshot(), so a multi-replica router polls one endpoint
        # per rank (weakly held — a dropped engine unregisters itself)
        from ..observability import fleet as _fleet
        _fleet.register_replica(self)
        _trace.startup_args(
            replica=self.lifecycle.name,
            pool_bytes=sum(a.nbytes for a in jax.tree_util.tree_leaves(
                (self.kc, self.vc, self.state))))

    def _fresh_cache(self):
        """One layer's K (or V) page pool: a float array, or the int8
        (payload, scales) pair."""
        if self._kv_int8:
            return (jnp.zeros(self._kv_shape, jnp.int8),
                    jnp.zeros(self._kv_scale_shape, jnp.float32))
        return jnp.zeros(self._kv_shape, self.kv_dtype)

    def _window_rows(self, window: int) -> int:
        """Rows a slot holds in a window layer: the window plus one prefill
        chunk (a chunk's first query still sees the window before it after
        the chunk's last row is written), in whole sublane tiles."""
        return -(-(window + self.prefill_width) // 8) * 8

    @staticmethod
    def _latent_row(width: int) -> int:
        """Columns a latent pool's row takes: ``width`` in whole lane tiles
        (576 -> 640; a bfloat16 array whose minor dimension is 576 is laid
        out in 640 lanes on the device anyway), zeros past ``width``."""
        return -(-width // _LANE_TILE) * _LANE_TILE

    def _fresh_caches(self):
        """``(kc, vc, state)`` zeroed: a K and a V pool per ``paged_kv``
        state, and per ``latent_kv`` / ``window_kv`` / ``slot_state`` /
        ``accumulator`` state its arrays (in the order of
        ``_cache_index``)."""
        kc, vc, state = [], [], []
        for _li, entry in _layer_states(self._layout):
            if entry[0] == "paged_kv":
                kc.append(self._fresh_cache())
                vc.append(self._fresh_cache())
            elif entry[0] == "latent_kv":
                state.append(jnp.zeros(
                    self._kv_shape[:2] + (self._latent_row(entry[1]),),
                    self._compute_dtype))
            elif entry[0] == "window_kv":
                shape = (self.max_batch, self._window_rows(entry[1]),
                         self.num_kv_heads, self.head_dim)
                state.append({"k": jnp.zeros(shape, self._compute_dtype),
                              "v": jnp.zeros(shape, self._compute_dtype)})
            elif entry[0] == "slot_state":
                state.append({
                    name: jnp.zeros((self.max_batch,) + tuple(shape), dtype)
                    for name, (shape, dtype) in entry[1].items()})
            else:
                state.append(jnp.zeros(tuple(entry[1]), entry[2]))
        return kc, vc, state

    @property
    def kv_bytes_per_token(self) -> int:
        """Resident KV bytes one cached token costs across the layers that
        page (``paged_kv`` and ``latent_kv``; window and recurrent layers
        cost a slot, not a token); the resident-batch ceiling is HBM /
        (this * mean seq len)."""
        if self._dense:
            return 0                     # dense path keeps no KV state
        per = self.num_kv_heads * self.head_dim * self.kv_dtype.itemsize
        if self._kv_int8:
            per += self.num_kv_heads * 4          # sidecar fp32 scale
        # K and V of the paged layers; one row of the latent layers
        return 2 * len(self.kc) * per + self._latent_bytes_per_token

    @property
    def _latent_bytes_per_token(self) -> int:
        return sum(self._latent_row(entry[1])
                   for _li, entry in _layer_states(self._layout)
                   if entry[0] == "latent_kv") * jnp.dtype(
                       self._compute_dtype).itemsize

    def _latent_pools(self):
        return [self.state[pos] for (_li, kind), pos
                in self._cache_index.items() if kind == "latent_kv"]

    @property
    def latent_bytes(self) -> int:
        """Resident bytes of the latent-attention layers' page pools: a row
        a token a layer for ``num_blocks`` pages (0 for a model with no
        such layer)."""
        return (self._latent_bytes_per_token * self._kv_shape[0]
                * self._kv_shape[1])

    @property
    def state_bytes_per_slot(self) -> int:
        """Resident bytes one slot costs in the layers that keep state
        per slot, not per token (0 for a pure-attention model): it does
        not grow with the sequence and is reserved for ``max_batch``
        slots whether they are in use or not."""
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for _li, entry in _layer_states(self._layout)
                   if entry[0] == "slot_state"
                   for shape, dtype in entry[1].values())

    @property
    def window_bytes_per_slot(self) -> int:
        """Resident bytes one slot costs in the sliding-window layers: K
        and V rows for the window plus one prefill chunk, whatever the
        context (0 for a model with no such layer)."""
        row = (2 * self.num_kv_heads * self.head_dim
               * jnp.dtype(self._compute_dtype).itemsize)
        return sum(self._window_rows(entry[1]) * row
                   for _li, entry in _layer_states(self._layout)
                   if entry[0] == "window_kv")

    def _program_key(self, phase, tokens_shape):
        return (phase, tuple(tokens_shape), self._kv_shape,
                self.kv_dtype.name)

    @property
    def decode_attention(self) -> Optional[str]:
        """``"kernel"`` or ``"composite"``: what the attention layers of the
        decode program were lowered to when it was traced (``nn.functional.
        paged_attention.log_paths`` around that call; one query a lane,
        ``speculate_k + 1`` under ``speculate=``). None until then, and for
        the dense path, which attends to no cache."""
        if self._dense:
            return None
        t = 1 if self._spec is None else self._spec_k + 1
        return self._attention_lowered.get(
            self._program_key("decode", (self.max_batch, t)))

    def expert_load(self, max_age_s: float = 0.0) -> Optional[dict]:
        """Expert load since the engine started, from the counters the
        compiled programs keep on the device (``accumulator`` layers): per
        expert layer the tokens each held expert received, the (token,
        expert) pairs that landed on held experts and the pairs selected.
        THE host read of those counters: nothing reads them during a tick.
        Each read moves the device counts into host totals (so the int32
        counters never run over) and exports the difference as
        ``paddle_tpu_moe_*``. A caller that polls (``health()``) passes
        ``max_age_s`` and gets the last reading while it is younger than
        that. None for a model with no such layer."""
        at = [(li, pos) for (li, kind), pos in self._cache_index.items()
              if kind == "accumulator"]
        if not at:
            return None
        now = time.monotonic()
        if (self._expert_load is None
                or now - self._expert_load_t >= max_age_s):
            self._expert_load_t = now
            self._read_expert_counters([pos for _li, pos in at])
        total = self._expert_load
        tokens = total[:, :-2]
        # host numpy: the totals left the device in _read_expert_counters
        return {"layers": [li for li, _pos in at],
                "tokens": tokens.tolist(),  # tpulint: disable=TPU102 — host numpy totals
                "pairs_held": total[:, -2].tolist(),  # tpulint: disable=TPU102 — host numpy totals
                "pairs_selected": total[:, -1].tolist(),  # tpulint: disable=TPU102 — host numpy totals
                "max_over_mean": _max_over_mean(tokens)}

    def _read_expert_counters(self, positions):
        """Move the device counters into ``_expert_load`` (host int64
        totals), zero them, export the difference."""
        from ..distributed.fleet import moe as _moe

        # one transfer, no compiled op: a probe must not compile
        fresh = np.stack(jax.device_get(  # tpulint: disable=TPU104 — telemetry-by-design: the counters' one host read, on request only
            [self.state[pos] for pos in positions])).astype(np.int64)
        for pos in positions:
            self.state[pos] = jax.device_put(
                np.zeros(self.state[pos].shape, self.state[pos].dtype))
        self._expert_load = fresh if self._expert_load is None \
            else self._expert_load + fresh
        worst = [v for v in _max_over_mean(self._expert_load[:, :-2])
                 if v is not None]
        _moe.stamp_expert_load(
            fresh[:, :-2].sum(axis=0),
            getattr(self.cfg, "experts_held", (0, 0))[0],
            fresh[:, -2].sum(), fresh[:, -1].sum(),
            max(worst, default=None))

    # ------------------------------------------------- request tracing
    @property
    def reqtrace_scope(self) -> str:
        """Timeline scope this replica records under (the lifecycle's
        stable per-process replica name)."""
        return self.lifecycle.name

    def _rt_event(self, rid: int, event: str,
                  t: Optional[float] = None, **meta):
        """Stamp one lifecycle event into the request flight recorder
        (``reqtrace.emit``: enabled-gate first — the disabled path reads
        NO clock — timestamps from the engine clock seam so FakeClock
        drills produce deterministic timelines)."""
        _reqtrace.emit(self.lifecycle.name, self._clock, rid, event, t,
                       **meta)

    # ---------------------------------------------------------------- API
    def add_request(self, prompt_ids, max_new_tokens: int = 32,
                    temperature: float = 0.0, top_p: float = 1.0,
                    ttft_deadline_s: Optional[float] = None,
                    deadline_s: Optional[float] = None) -> int:
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("add_request: prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError("add_request: max_new_tokens must be >= 1")
        if not 0.0 < top_p <= 1.0:
            raise ValueError("add_request: top_p must be in (0, 1]")
        if not temperature >= 0.0:   # also rejects NaN
            raise ValueError("add_request: temperature must be >= 0")
        if self._dense and len(prompt) > self.arch.width:
            # the id row is padded, never truncated — silently dropping
            # trailing feature ids would score a different request
            raise ValueError(
                f"add_request: dense-path prompt ({len(prompt)} ids) "
                f"exceeds the model's serve width ({self.arch.width})")
        max_pos = getattr(self.arch, "max_positions", None)
        if max_pos is not None and len(prompt) + max_new_tokens > max_pos:
            # learned-position models: a sequence growing past the table
            # would silently clip-gather the last embedding
            raise ValueError(
                f"add_request: prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the model's position table "
                f"({max_pos})")
        # ---- admission control (backpressure is an exception the
        # SUBMITTER handles; everything after acceptance is a status) ----
        if not self.lifecycle.admitting():
            raise Overloaded(
                f"replica is {self.lifecycle.state}: not accepting "
                f"requests")
        rcfg = self.resilience
        if len(self.queue) >= rcfg.max_queue:
            raise Overloaded(
                f"admission queue full ({rcfg.max_queue} queued); retry "
                f"on another replica")
        self._rid += 1
        req = Request(self._rid, prompt, max_new_tokens,
                      temperature=temperature, top_p=top_p)
        req.submit_t = self._clock()
        req.ttft_deadline_s = (ttft_deadline_s if ttft_deadline_s
                               is not None
                               else rcfg.default_ttft_deadline_s)
        req.deadline_s = (deadline_s if deadline_s is not None
                          else rcfg.default_deadline_s)
        self._rt_event(req.rid, "submitted", t=req.submit_t,
                       prompt_tokens=len(prompt),
                       max_new_tokens=max_new_tokens,
                       ttft_deadline_s=req.ttft_deadline_s,
                       deadline_s=req.deadline_s)
        need_total = self._blocks_needed(len(prompt) + max_new_tokens)
        if (need_total > self.max_blocks_per_seq
                or need_total > self._total_usable):
            # can NEVER fit this replica's geometry: terminal FAILED at
            # submit time (round 3 raised MemoryError from
            # run_to_completion after other requests already ran)
            reason = (f"needs {need_total} blocks (max_blocks_per_seq="
                      f"{self.max_blocks_per_seq}, usable="
                      f"{self._total_usable})")
            self.rejected[req.rid] = reason
            self._finish_request(req, RequestStatus.FAILED, detail=reason)
            return req.rid
        self.queue.append(req)
        _res.M_QUEUE_DEPTH.set(len(self.queue))
        return req.rid

    @property
    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def has_work(self) -> bool:
        """Whether a ``step()`` has something to do: a queued or running
        request, or a launched program whose tokens are still unread."""
        return (bool(self.queue) or self.num_active > 0
                or self._unread is not None)

    # ----------------------------------------------------------- compute
    def _chunk_args(self, tokens_np, seq_lens_np, tables_np, temps_np,
                    top_ps_np, rids_np, ngens_np):
        """The program's arguments. Every host array is handed over as a
        snapshot: the launch returns while the transfer (on the CPU
        backend, where the device array may alias the numpy buffer, the
        program itself) can still be reading it, and the next tick's
        admission mutates ``tables`` in place."""
        return ([p._data for p in self._params], self.kc, self.vc,
                *self._row_args(tokens_np, seq_lens_np, tables_np, temps_np,
                                top_ps_np, rids_np, ngens_np),
                self._base_key, self.state)

    @staticmethod
    def _row_args(tokens_np, seq_lens_np, tables_np, temps_np, top_ps_np,
                  rids_np, ngens_np):
        """One group's rows on the device (snapshots, as ``_chunk_args``
        says)."""
        def snap(a, dtype):
            return a if isinstance(a, jax.Array) else jnp.asarray(
                np.array(a, dtype))

        return (snap(tokens_np, np.int32), snap(seq_lens_np, np.int32),
                snap(tables_np, np.int32), snap(temps_np, np.float32),
                snap(top_ps_np, np.float32), snap(rids_np, np.int32),
                snap(ngens_np, np.int32))

    def _call_program(self, rec: _Launched, fn, host_args, extra=(),
                      aboard=None, **span_args):
        """Launch one program under its boundary spans and read the one
        launched before it: ``serving.<phase>`` over ``.build`` (eval mode
        on, the host arrays to the device), ``.launch`` (the compiled call;
        the caches are rebound to its outputs, the training flag restored)
        and ``.wait`` (the blocking read of the oldest unread program's
        outputs: the program launched before this one, or this one on an
        engine that does not overlap), then ``serving.emit`` for what was
        read. ``rec`` stays behind as the unread program. ``aboard``: the
        host rows of the chunk a mixed step carries, after ``extra``."""
        phase = rec.phase
        tokens, seq_lens_np, _tables_np, temps_np, *_ = host_args
        sampling = any(np.any(np.asarray(rows[3]) > 0)
                       for rows in (host_args, aboard) if rows is not None)
        with contextlib.ExitStack() as restore, _trace.boundary(
                f"serving.{phase}",
                args=dict(span_args, phase=phase,
                          batch=int(len(seq_lens_np)))):
            with _trace.boundary(f"serving.{phase}.build"):
                # serving always runs eval-mode (dropout off); restore the
                # caller's training flag afterwards — the engine must not
                # mutate a model a training loop is still using. Either
                # switch walks every sublayer, so both sit inside a leaf
                # span: host work that runs beside the program before
                if getattr(self.model, "training", False):
                    self.model.eval()
                    restore.callback(self.model.train)
                rec.t0 = time.perf_counter()
                args = self._chunk_args(*host_args) + tuple(
                    jnp.asarray(np.array(a, np.int32)) for a in extra)
                if aboard is not None:
                    args += (self._row_args(*aboard),)
            with _trace.boundary(f"serving.{phase}.launch"), \
                    _attention_paths() as lowered:
                *rec.outs, self.kc, self.vc, self.state = fn(
                    *args, sampling=bool(sampling))
                restore.close()
            if lowered:     # this call traced the program
                self._attention_lowered[self._program_key(
                    "mixed" if aboard is not None else phase,
                    tokens.shape)] = "+".join(sorted(set(lowered)))
            due, self._unread = self._unread, rec
            self._launches += 1
            if phase == "decode":
                self._steps += 1
                self._steps_mixed += aboard is not None
            _res.M_LAUNCHES.inc(overlapped=str(due is not None).lower(),
                                kind=rec.kind)
            if due is not None:
                self._launches_overlapped += 1
            elif not self._overlap:
                due, self._unread = rec, None
            if due is None:
                return
            outs = self._read(due, phase)
        self._emit(due, outs)

    def _read(self, rec: _Launched, phase: str):
        """The blocking read of a launched program's host-bound outputs
        (numpy arrays), under the ``.wait`` span of the launch it follows
        (``phase``). A failure of the program surfaces here."""
        from ..fault import inject as _inject

        with _trace.boundary(f"serving.{phase}.wait"):
            _inject.check("serving.program_failure", tick=rec.tick)
            late = all(o.is_ready() for o in rec.outs)
            # np.asarray blocks until the program finishes: launch to read
            # bounds its device execution from above — the per-tick
            # prefill-vs-decode attribution tools/loadgen.py reports
            outs = [np.asarray(o) for o in rec.outs]  # tpulint: disable=TPU104 — host boundary by design: sampled token ids feed python-side scheduling
        self._reads += 1
        self._reads_late += late
        _res.M_READS.inc(host_late=str(late).lower())
        seconds = time.perf_counter() - rec.t0
        rows = sum(rec.positions.values())
        for phase, n in rec.positions.items():
            self.scheduler.note_phase(phase, n, seconds * n / rows)
        return outs

    def _settle(self):
        """Read the unread program, if there is one, with nothing new to
        launch (inside a tick: a failure goes the tick's way)."""
        rec, self._unread = self._unread, None
        if rec is not None:
            self._emit(rec, self._read(rec, rec.phase))

    def _flush(self):
        """``_settle`` for the callers outside a tick (``cancel``,
        ``recover``, ``warmup``): a failure is contained as a tick's is."""
        try:
            self._settle()
        except Exception as e:
            self._on_tick_failure(e)

    def _holds(self, slot: int, tenancy: int) -> Optional[Request]:
        """The request a program was launched for, if the lane still holds
        it: cancel, eviction, deadline expiry or a finish learnt late (an
        ``eos_id`` hit) may have released the lane since the launch."""
        return self.slots[slot] if self._tenancy[slot] == tenancy else None

    def _run_chunk(self, rec: _Launched, tokens, seq_lens_np, tables_np,
                   temps_np, top_ps_np, rids_np, ngens_np, lanes=None,
                   aboard=None):
        """``lanes``: the slots the chunk's rows belong to, for the layers
        that keep state per slot (a prefill chunk carries one slot's rows;
        the decode batch's row i is slot i and passes none). An engine
        with no such layer never sends them. ``aboard``: the host rows of
        the chunk a decode step carries (``lanes`` is then that chunk's
        slot, and always sent: its token comes back in that lane)."""
        extra = (lanes,) if lanes is not None and (
            self._has_slot_state or aboard is not None) else ()
        self._call_program(
            rec, self._fns[rec.kind],
            (tokens, seq_lens_np, tables_np, temps_np, top_ps_np,
             rids_np, ngens_np), extra=extra, aboard=aboard)

    def _run_verify(self, rec: _Launched, tokens_np, seq_lens_np, tables_np,
                    temps_np, top_ps_np, rids_np, ngens_np, max_accept_np):
        """Speculative verify program: decode-phase compute (the spans
        and token counters attribute it to decode — it IS the decode
        step, just yielding up to k+1 tokens)."""
        self._call_program(
            rec, self._vfn,
            (tokens_np, seq_lens_np, tables_np, temps_np, top_ps_np,
             rids_np, ngens_np), extra=(max_accept_np,), speculative=True)

    def _record(self, phase: str, shape, kind: Optional[str] = None,
                **kw) -> _Launched:
        return _Launched(phase=phase, kind=kind or phase, outs=[], t0=0.0,
                         positions={phase: int(shape[0]) * int(shape[1])},
                         tick=self._ticks, **kw)

    def _emit(self, rec: _Launched, outs):
        """Per-slot bookkeeping of the tokens a program sampled, at its
        read."""
        with _trace.boundary("serving.emit"):
            now = self._clock()
            if rec.kind == "verify":
                self._emit_verified(rec.meta["active"], rec.meta["seq"],
                                    *outs, rec.meta["max_accept"])
                return
            if "chunk" in rec.meta:         # a chunk, alone or aboard
                self._rt_event(rec.meta["rid"], "prefill_chunk", t=now,
                               tick=rec.tick, **rec.meta["chunk"])
            (toks,) = outs
            for slot, tenancy in rec.lanes:
                req = self._holds(slot, tenancy)
                if req is None:
                    continue
                # a prefill chunk carries its slot's one row, the decode
                # step (a chunk aboard or not) row i for slot i
                tok = int(toks[0 if rec.kind == "prefill" else slot])
                req.generated.append(tok)
                self.last_token[slot] = tok
                self._inflight[slot] -= 1
                if rec.phase == "decode" and slot != rec.meta.get("first"):
                    self._rt_event(req.rid, "decode_tick", t=now,
                                   tick=rec.tick, new_tokens=1)
                self._record_token(req, now)
                self._maybe_finish(slot)

    # -------------------------------------------------------- scheduling
    def _blocks_needed(self, length: int) -> int:
        return -(-length // self.block_size)

    def _ensure_blocks(self, slot: int, length: int) -> bool:
        need = self._blocks_needed(length)
        have = len(self.slot_blocks[slot])
        if need > self.max_blocks_per_seq:
            raise MemoryError(
                f"sequence needs {need} blocks > max_blocks_per_seq "
                f"{self.max_blocks_per_seq}")
        if need > have:
            if need - have > self.bm.available:
                return False
            new = self.bm.allocate(need - have)
            for j, b in enumerate(new):
                self.tables[slot, have + j] = b
            self.slot_blocks[slot].extend(new)
        return True

    def _admit(self):
        from ..fault import inject as _inject

        for slot in range(self.max_batch):
            if not self.queue or self.slots[slot] is not None:
                continue
            req = self.queue[0]
            prefix_len = len(req.prompt) + len(req.generated)
            if (self._blocks_needed(prefix_len + 1)
                    > self.bm.available):
                break  # head-of-line blocks until memory frees
            self.queue.pop(0)
            self.slots[slot] = req
            self.tables[slot, :] = 0
            self.slot_blocks[slot] = []
            # allocate the prefix blocks NOW so the next admission's
            # availability check sees the reduced pool
            raced = _inject.fire("serving.admission_oom") is not None
            if raced or not self._ensure_blocks(slot, prefix_len):
                # admission raced cache exhaustion (a concurrent slot's
                # growth won the last blocks between the availability
                # check and the allocate): un-admit and retry next tick
                # — round 3 raised MemoryError here and killed the
                # engine with every in-flight decode
                self._release_slot(slot)
                self.queue.insert(0, req)
                break
            req.status = RequestStatus.RUNNING
            _res.M_ADMITTED.inc()
            self._rt_event(req.rid, "admitted", slot=slot,
                           prefix_tokens=prefix_len,
                           tick=self._ticks,
                           kv_blocks=len(self.slot_blocks[slot]))
            # stage the chunked prefill; compute happens in
            # _prefill_step under the scheduler's per-tick budget. The
            # prefix is LEFT-padded to a multiple of the chunk width —
            # padded positions sit at negative sequence positions, which
            # the paged-attention kernel drops from the cache write and
            # fully masks, so only two compiled shapes exist in steady
            # state: (1, prefill_width) and the (max_batch, 1-or-k+1)
            # decode/verify.
            width = self.prefill_width
            prefix = np.asarray(req.prompt + req.generated, np.int32)
            n_chunks = -(-len(prefix) // width)
            pad = n_chunks * width - len(prefix)
            self._prefilling[slot] = {
                "prefix": np.concatenate(
                    [np.zeros(pad, np.int32), prefix]),
                "n_chunks": n_chunks, "next": 0, "pad": pad}

    def _prefill_step(self):
        """Advance pending chunked prefills under the scheduler's
        per-tick budget: each chunk program carries the next
        ``prefill_width`` tokens of ONE prefilling slot, the one admitted
        first, and only that slot's rows and block table. The final chunk
        of a slot yields its first sampled token (read with the next
        launch); chunks past the budget defer to later ticks so the decode
        step below never waits out a long prompt.

        Returns the tick's LAST chunk, booked and not launched (``(record,
        host rows, slot)``), where the engine reads its programs a tick
        late: ``_decode_active`` sends it out, with the decode step aboard
        if one goes out (apart, each streams every weight). None: no chunk
        this tick, or every chunk launched."""
        width = self.prefill_width
        quota = self.scheduler.token_quota(self.block_size)
        # whole programs the tick's tokens pay for (a budget wider than
        # the engine's widest chunk buys several)
        programs = (float("inf") if quota is None
                    else max(1, quota // width))
        last = None
        while self._prefilling:
            with _trace.boundary("serving.plan"):
                plan = self._plan_prefill_chunk(programs)
            if plan is None:
                break
            if last is not None:        # another chunk follows it
                self._launch_chunk(last)
            slot, chunk, final, rows = plan
            st = self._prefilling[slot]
            req = self.slots[slot]
            rec = self._record(
                "prefill", rows[0].shape,
                meta={"rid": req.rid,
                      "chunk": {"chunk": chunk, "n_chunks": st["n_chunks"],
                                "tokens": width}})
            # what the launch settles is booked before it: by count the
            # host knows it without reading the program
            self.scheduler.note_prompt_tokens(
                width - (st["pad"] if chunk == 0 else 0))
            self._tick_work["prompt_tokens"] += width
            programs -= 1
            if final:
                del self._prefilling[slot]
                # cached positions == the prefilled prefix; the sampled
                # token (in flight until this program is read) lands in
                # the cache on its decode step
                self.seq_lens[slot] = len(req.prompt) + len(req.generated)
                self._inflight[slot] += 1
                rec.lanes.append((slot, int(self._tenancy[slot])))
                rec.meta["first"] = slot
            last = (rec, rows, slot)
        if last is not None and not self._overlap:
            self._launch_chunk(last)
            last = None
        return last

    def _launch_chunk(self, chunk):
        """A booked prefill chunk (``_prefill_step``) as its own program."""
        rec, rows, slot = chunk
        self._run_chunk(rec, *rows, lanes=np.asarray([slot], np.int32))

    def _plan_prefill_chunk(self, programs):
        """The next chunk call: ``(slot, chunk index, is the slot's last
        chunk, host rows (tokens, seq, tables, temps, top_ps, rids,
        ngens))`` for the slot admitted first; None (and the deferral
        noted) when the tick's ``programs`` are spent."""
        width = self.prefill_width
        if programs <= 0:
            self.scheduler.note_deferred(sum(
                st["n_chunks"] - st["next"]
                for st in self._prefilling.values()))
            # the WHY of a slow TTFT: this tick's budget pushed
            # these requests' remaining chunks to a later tick
            for slot, st in self._prefilling.items():
                self._rt_event(
                    self.slots[slot].rid, "prefill_deferred",
                    tick=self._ticks,
                    chunks_left=st["n_chunks"] - st["next"])
            return None
        slot, st = next(iter(self._prefilling.items()))
        req = self.slots[slot]
        j = st["next"]
        st["next"] = j + 1
        rows = (st["prefix"][None, j * width:(j + 1) * width],
                np.asarray([(j + 1) * width - st["pad"]], np.int32),
                self.tables[slot:slot + 1],
                np.asarray([req.temperature], np.float32),
                np.asarray([req.top_p], np.float32),
                np.asarray([req.rid], np.int32),
                np.asarray([len(req.generated)], np.int32))
        return slot, j, st["next"] == st["n_chunks"], rows

    def _evict(self, slot: int,
               reason: str = "kv-block pressure (livelock preemption)"):
        """Preempt a running request: release its blocks and requeue it
        for later re-admission (its generated prefix re-prefills then —
        vLLM-style recompute preemption)."""
        req = self.slots[slot]
        freed = len(self.slot_blocks[slot])
        self._release_slot(slot)
        req.status = RequestStatus.QUEUED
        _res.M_EVICTIONS.inc()
        self._rt_event(req.rid, "preempted", victim_reason=reason,
                       tick=self._ticks, kv_blocks_reclaimed=freed,
                       tokens_so_far=len(req.generated))
        self.queue.append(req)

    def _release_slot(self, slot: int):
        """Return a slot's KV blocks to the free list and reset its lane
        in the batch state (idle lanes point at the trash block)."""
        self.slots[slot] = None
        self._prefilling.pop(slot, None)
        self.bm.release(self.slot_blocks[slot])
        self.slot_blocks[slot] = []
        self.tables[slot, :] = 0
        self.seq_lens[slot] = 1
        self.last_token[slot] = 0
        # a token still in flight for the tenant is dropped at its read
        self._inflight[slot] = 0
        self._tenancy[slot] += 1

    def _finish_request(self, req: Request, status: str,
                        detail: str = ""):
        """Move ``req`` to a terminal status and record its outcome. The
        caller must already have released any slot/blocks it held."""
        req.status = status
        req.detail = detail
        req.finish_t = self._clock()
        self._rt_event(req.rid, "terminal", t=req.finish_t,
                       outcome=status, detail=detail,
                       tokens=len(req.generated))
        self._slo.note(req.finish_t,
                       good=(status == RequestStatus.FINISHED))
        _res.M_REQUESTS.inc(outcome=status)
        if status == RequestStatus.SHED:
            _res.M_SHED.inc()
        elif status == RequestStatus.DEADLINE_MISSED:
            _res.M_DEADLINE_MISSED.inc()
        self.outcomes[req.rid] = RequestOutcome(
            rid=req.rid, status=status, detail=detail,
            tokens=list(req.generated), submit_t=req.submit_t,
            first_token_t=req.first_token_t, finish_t=req.finish_t,
            token_times=list(req.token_times))
        self._stream_bufs.pop(req.rid, None)
        if status == RequestStatus.FINISHED:
            self._done.append(req)

    def _record_token(self, req: Request, now: float):
        """TTFT / inter-token latency bookkeeping for one new token.
        Exemplar linkage rides here: the worst TTFT/ITL samples keep
        the request id, so a p99 regression resolves to a timeline."""
        traced = _reqtrace.enabled()
        if req.first_token_t is None:
            req.first_token_t = now
            if req.submit_t is not None:
                ttft = now - req.submit_t
                _res.M_TTFT.observe(ttft)
                if traced:
                    self._rt_event(req.rid, "first_token", t=now,
                                   ttft_s=ttft)
                    _reqtrace.EXEMPLARS.note(
                        "ttft", self.lifecycle.name, req.rid, ttft, now)
        elif req.token_times:
            itl = now - req.token_times[-1]
            _res.M_ITL.observe(itl)
            if traced:
                _reqtrace.EXEMPLARS.note(
                    "itl", self.lifecycle.name, req.rid, itl, now)
        req.token_times.append(now)
        buf = self._stream_bufs.get(req.rid)
        if buf is not None:
            buf.append(req.generated[-1])

    def _maybe_finish(self, slot: int):
        req = self.slots[slot]
        if req is None:
            return
        last = req.generated[-1] if req.generated else None
        if (len(req.generated) >= req.max_new_tokens
                or (self.eos_id is not None and last == self.eos_id)):
            self._release_slot(slot)
            self._finish_request(req, RequestStatus.FINISHED)

    # ------------------------------------------------- deadlines/overload
    def _deadline_expired(self, req: Request, now: float) -> Optional[str]:
        """Reason string when ``req`` is past a deadline, else None."""
        if req.submit_t is None:
            return None
        waited = now - req.submit_t
        if req.deadline_s is not None and waited > req.deadline_s:
            return (f"total deadline {req.deadline_s}s expired after "
                    f"{waited:.3f}s ({len(req.generated)} tokens)")
        if (req.first_token_t is None and req.ttft_deadline_s is not None
                and waited > req.ttft_deadline_s):
            return (f"TTFT deadline {req.ttft_deadline_s}s expired after "
                    f"{waited:.3f}s with no first token")
        return None

    def _expire_deadlines(self):
        """Cancel queued AND in-flight requests whose TTFT/total deadline
        has passed; in-flight cancellations reclaim their KV blocks."""
        now = self._clock()
        kept = []
        for req in self.queue:
            why = self._deadline_expired(req, now)
            if why is None:
                kept.append(req)
            else:
                self._finish_request(req, RequestStatus.DEADLINE_MISSED,
                                     detail=why)
        self.queue = kept
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            why = self._deadline_expired(req, now)
            if why is not None:
                self._release_slot(slot)
                self._finish_request(req, RequestStatus.DEADLINE_MISSED,
                                     detail=why)

    def _shed_overload(self):
        """Past the queue high-water mark, shed the NEWEST queued
        requests (they would wait longest; the oldest are closest to a
        slot) down to the mark. Preempted requests carrying generated
        tokens are spared — shedding them would discard paid-for
        prefill/decode compute (the queue stays bounded by max_queue
        regardless)."""
        hw = self.resilience.queue_high_water
        if hw is None or len(self.queue) <= hw:
            return
        excess = len(self.queue) - hw
        kept_rev: List[Request] = []
        for req in reversed(self.queue):          # newest first
            if excess > 0 and not req.generated:
                excess -= 1
                self._finish_request(
                    req, RequestStatus.SHED,
                    detail=f"queue past high-water mark ({hw})")
            else:
                kept_rev.append(req)
        self.queue = kept_rev[::-1]

    def _eviction_key(self, slot: int):
        """Preemption victim ordering: most deadline slack first (no
        deadline = infinite slack), youngest rid as tie-break — evicting
        the request closest to its deadline would turn one preemption
        into a deadline miss."""
        req = self.slots[slot]
        if req.deadline_s is not None and req.submit_t is not None:
            dl = req.submit_t + req.deadline_s
        else:
            dl = float("inf")
        return (dl, req.rid)

    # ------------------------------------------------------------- ticks
    def step(self) -> Dict[int, List[int]]:
        """One engine tick: expire deadlines, admit queued requests,
        shed overload, advance chunked prefill under the scheduler's
        budget, then a single batched decode (or speculative verify)
        step for every fully-prefilled slot. Returns
        {rid: generated_tokens} for requests that finished this tick.

        One program stays in flight: every launch is followed by the read
        of the program launched before it, and the tick returns with its
        last program still running. The tokens of a program are therefore
        delivered (``req.generated``, streams, outcomes) by the next
        ``step()``, and a finished request is returned one ``step()``
        after its last program; a ``step()`` with nothing to launch reads
        what is unread. A ``speculate=`` engine and the dense scorer read
        each program in the tick that launched it.

        Never raises from scheduling, memory pressure, or injected
        faults: an internal tick failure marks the in-flight requests
        FAILED, reclaims their KV blocks, and flips the replica
        DEGRADED — the engine keeps serving."""
        wd = self._watchdog
        if wd is not None:
            wd.begin_work()
        self._ticks += 1
        t0 = time.perf_counter()
        span_args = {"tick": self._ticks, "active": self.num_active,
                     "queued": len(self.queue)}
        self._tick_work = {"prompt_tokens": 0, "decode_slots": 0}
        try:
            with _trace.boundary("serving.tick", args=span_args):
                try:
                    self._tick()
                    if self.lifecycle.state == ReplicaState.STARTING:
                        self.lifecycle.to(ReplicaState.READY, "serving")
                except Exception as e:
                    self._on_tick_failure(e)
                finally:
                    # this tick's work and phase split ride its span
                    # (read at span EXIT — end_tick resets the
                    # accumulator later)
                    span_args.update(self._tick_work)
                    span_args.update(
                        self.scheduler.tick_phase_seconds())
        finally:
            if wd is not None:
                wd.end_work()
            self.scheduler.end_tick()
            _res.M_TICK_SECONDS.observe(time.perf_counter() - t0)
            _res.M_QUEUE_DEPTH.set(len(self.queue))
            _res.M_KV_BLOCKS.set(self._total_usable - self.bm.available)
        return self._drain_done()

    def _tick(self):
        from ..fault import inject as _inject

        stall = _inject.fire("serving.tick_stall")
        if stall is not None:
            # a wedged device transfer/compile: the tick thread blocks,
            # no heartbeat reaches the watchdog
            time.sleep(float(stall.get("seconds", 0.1)))
        if _inject.fire("serving.crash_at_tick",
                        tick=self._ticks) is not None:
            raise _inject.InjectedFault(
                "serving.crash_at_tick",
                f"injected crash at tick {self._ticks}")
        if self._dense:
            # dense path: the tick itself admits (it consumes up to
            # max_batch from the queue head), so shed only what the
            # forward could not absorb
            self._expire_deadlines()
            self._dense_tick()
            self._shed_overload()
            return
        with _trace.boundary("serving.admit"):
            self._expire_deadlines()
            # admit BEFORE shedding: a burst hitting an idle replica flows
            # into free decode slots first; only what capacity could not
            # absorb this tick counts against the high-water mark
            self._admit()
            self._shed_overload()
        # phase split: bounded prefill, then decode — decode runs EVERY
        # tick there is decodable work, however much prefill is pending
        launched = self._launches
        self._decode_active(self._prefill_step())
        if self._launches == launched:
            # nothing to launch (the last tokens are in flight, or memory
            # stalls every lane): read what is unread
            self._settle()

    def _dense_tick(self):
        """Score up to ``max_batch`` queued requests in ONE
        ``serve_dense`` forward. The id matrix is always
        (max_batch, width) — short batches ride zero rows — so jit
        compiles exactly one steady-state program. Each request emits a
        single score token (the [0, 1] score in basis points) and
        finishes; no engine state survives the tick."""
        if not self.queue:
            return
        batch = self.queue[:self.max_batch]
        del self.queue[:len(batch)]
        w = self.arch.width
        ids = np.zeros((self.max_batch, w), np.int32)
        for i, req in enumerate(batch):
            ids[i, :len(req.prompt)] = req.prompt
        was_training = getattr(self.model, "training", False)
        if was_training:
            self.model.eval()
        t0 = time.perf_counter()
        try:
            scores = self._dense_fn([p._data for p in self._params],
                                    jnp.asarray(ids))
            out = np.asarray(scores)  # tpulint: disable=TPU104 — host boundary by design: scores become outcome tokens
        finally:
            if was_training:
                self.model.train()
        self.scheduler.note_phase("decode", len(batch),
                                  time.perf_counter() - t0)
        now = self._clock()
        for i, req in enumerate(batch):
            bp = int(round(float(out[i]) * 10000.0))  # tpulint: disable=TPU103 — host boundary by design: the score token enters the python-side outcome
            req.generated.append(bp)
            self._rt_event(req.rid, "dense_score", t=now, score_bp=bp,
                           tick=self._ticks)
            self._record_token(req, now)
            self._finish_request(req, RequestStatus.FINISHED)

    def _decode_lanes(self) -> List[int]:
        """Slots holding a fully-prefilled request (mid-prefill slots
        stay out of the decode batch — their lanes run with the seq=0
        sentinel so the compiled shape never changes)."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and i not in self._prefilling]

    def _decode_active(self, chunk=None):
        """The tick's decode step over the lanes that decode. ``chunk``:
        the tick's last prefill chunk, still to launch
        (``_prefill_step``)."""
        active = self._decode_lanes()
        if chunk is not None and all(
                i == chunk[0].meta.get("first") for i in active):
            # no lane decodes but the one this chunk finishes: the chunk
            # goes alone, and the slot it finishes decodes after it
            self._launch_chunk(chunk)
            chunk = None
        if not active:
            return
        if self._spec is not None and self._spec_feasible(active):
            self._decode_speculative(active)
            return
        self._decode_plain(active, chunk)

    def _spec_feasible(self, active: List[int]) -> bool:
        """Speculate this tick only when every active slot has table
        room for the k draft positions — a slot whose sequence is
        within k of its ``max_blocks_per_seq`` ceiling must NOT feed a
        (seq+k)-length verify (the block-table lookup would clamp and
        corrupt another block's pages, and _ensure_blocks would raise
        out of the tick). Near-capacity ticks fall back to plain
        decode, which admission guarantees always fits."""
        cap = self.max_blocks_per_seq * self.block_size
        return all(self.slots[i].seq_len + self._spec_k <= cap
                   for i in active)

    def _decode_plain(self, active: List[int], chunk=None):
        """One decode step over ``active``. With ``chunk`` (the tick's last
        prefill chunk, still to launch) and a lane to feed, chunk and step
        go out as ONE program, ``paged_mixed_step``: the weights are
        streamed once for both. The slot a final chunk finishes takes its
        first token from that program, so it is not fed by it and joins the
        next tick's step. With no lane to feed the chunk goes alone, and
        the lanes are planned as they were: after it. So does one such
        tick in every ``share_window_ticks``: the window behind the
        phase-share gauge, and a device trace of a few seconds, then hold
        a chunk and a step that ran alone, the one place their own device
        time can be read where every step would ride a chunk."""
        if chunk is not None:
            self._mixable += 1
            if not self._mixable % self.scheduler.config.share_window_ticks:
                self._launch_chunk(chunk)
                chunk, active = None, self._decode_lanes()
        while True:
            # the slot whose first token the step's own program samples
            aboard = None if chunk is None else chunk[0].meta.get("first")
            with _trace.boundary("serving.plan"):
                plan = self._plan_decode(active, aboard)
            fed, skipped = plan[-2:] if plan is not None else ((), ())
            # every lane that would be fed is memory-stalled
            stalled = bool(skipped) and len(skipped) >= len(fed)
            if chunk is not None and (plan is None or stalled):
                self._launch_chunk(chunk)
                chunk, active = None, self._decode_lanes()
                continue
            if plan is None:
                return
            if not stalled:
                break
            if self._unread is None:
                # nobody can finish to free blocks, so this would
                # livelock. Preempt the slot with the most deadline slack
                # (vLLM recompute-preemption, deadline-aware) and retry
                # next tick with its blocks free.
                self._evict(max(skipped, key=self._eviction_key))
                return
            # the unread program may finish lanes and free their blocks:
            # read it, then plan on what the host knows now
            self._settle()
            active = self._decode_lanes()
        tokens, seq, temps, top_ps, rids, ngens = plan[:-2]
        rec = self._record(
            "decode", tokens.shape,
            lanes=[(i, int(self._tenancy[i])) for i in fed
                   if i not in skipped])
        for i, _tenancy in rec.lanes:
            self._inflight[i] += 1
            self.seq_lens[i] = int(seq[i])   # cached positions, launched
        self._tick_work["decode_slots"] += len(rec.lanes)
        rows = (tokens, seq, self.tables, temps, top_ps, rids, ngens)
        if chunk is None:
            self._run_chunk(rec, *rows)
            return
        # the step takes the chunk's record aboard: its rows, the lane of
        # the slot it finishes, the chunk event its read reports
        crec, chunk_rows, slot = chunk
        rec.kind = "mixed"
        rec.positions.update(crec.positions)
        rec.meta = crec.meta
        rec.lanes += crec.lanes
        self._run_chunk(rec, *rows, lanes=np.asarray([slot], np.int32),
                        aboard=chunk_rows)

    def _plan_decode(self, active: List[int], aboard: Optional[int] = None):
        """The decode call's rows ``(tokens, seq, temps, top_ps, rids,
        ngens, fed, skipped)``, counted on the tokens read plus the tokens
        in flight: ``fed`` are the active lanes that still have a token to
        sample (a lane whose last token by count is in flight is not fed
        again, nor is ``aboard``, the slot whose final chunk this step
        carries: its first token is this program's to sample), ``skipped``
        those of them that found no KV block. None when no lane is fed."""
        seq = self.seq_lens.copy()
        for i in self._prefilling:
            seq[i] = 0               # masked lane: no write, no attend
        temps = np.zeros((self.max_batch,), np.float32)
        top_ps = np.ones((self.max_batch,), np.float32)
        rids = np.zeros((self.max_batch,), np.int32)
        ngens = np.zeros((self.max_batch,), np.int32)
        fed, skipped = [], []
        for i in active:
            req = self.slots[i]
            pending = int(self._inflight[i])
            if (i == aboard
                    or len(req.generated) + pending >= req.max_new_tokens):
                seq[i] = 0           # its newest token is in flight
                continue
            fed.append(i)
            temps[i] = req.temperature
            top_ps[i] = req.top_p
            rids[i] = req.rid
            ngens[i] = len(req.generated) + pending
            # the cache holds seq_len-1 positions; the token being fed
            # (the newest sample) lands at position seq_len-1, so the
            # total INCLUDING it is exactly req.seq_len
            seq[i] = req.seq_len + pending
            if not self._ensure_blocks(i, int(seq[i])):
                # OOM: skip this slot's tick. Sentinel 0 — with seq=1
                # the op would write the token's K/V into position 0
                # of the slot's first REAL block, corrupting the
                # cached prompt; seq=0 puts the write at pos -1,
                # which the kernel drops and fully masks.
                seq[i] = 0
                skipped.append(i)
        if not fed:
            return None
        return (self._decode_feed(), seq, temps, top_ps, rids, ngens, fed,
                skipped)

    def _decode_feed(self):
        """The decode step's (B, 1) tokens: what the host has read, and
        for the lanes whose newest token the unread program sampled that
        program's output, left on the device."""
        on_host = self.last_token.astype(np.int32)
        rec = self._unread
        lanes = [slot for slot, tenancy in (rec.lanes if rec else ())
                 if self._holds(slot, tenancy) is not None]
        if not lanes:
            return on_host[:, None]
        from_host = np.ones((self.max_batch,), bool)
        from_host[lanes] = False
        return _feed_tokens(rec.outs[0], on_host, from_host)

    def _decode_speculative(self, active: List[int]):
        """Decode via the fused verify program: per active slot, feed
        [last_token, k n-gram draft tokens] in one (B, k+1) forward and
        take the accepted prefix + the model's own next token — up to
        k+1 tokens per slot per tick, greedy output identical to plain
        decode by construction (acceptance only keeps drafts the target
        model would have emitted itself)."""
        with _trace.boundary("serving.plan"):
            k = self._spec_k
            T = k + 1
            seq = self.seq_lens.copy()
            for i in range(self.max_batch):
                if i not in active:
                    seq[i] = 0           # idle / mid-prefill: masked lane
            tokens = np.zeros((self.max_batch, T), np.int32)
            temps = np.zeros((self.max_batch,), np.float32)
            top_ps = np.ones((self.max_batch,), np.float32)
            rids = np.zeros((self.max_batch,), np.int32)
            ngens = np.zeros((self.max_batch,), np.int32)
            max_accept = np.zeros((self.max_batch,), np.int32)
            skipped = []
            max_pos = getattr(self.arch, "max_positions", None)
            for i in active:
                req = self.slots[i]
                # draft positions extend to seq_len-1+k: allocate for the
                # whole verify up front (stale tail entries are masked by
                # the rolled-back seq_len and overwritten as the sequence
                # legitimately reaches them)
                if not self._ensure_blocks(i, req.seq_len + k):
                    seq[i] = 0
                    skipped.append(i)
                    continue
                draft: List[int] = []
                if req.temperature == 0:
                    draft = list(self._spec.propose(
                        req.prompt + req.generated))[:k]
                ma = len(draft)
                if max_pos is not None:
                    # drafts whose positions would clip-gather past the
                    # learned-position table can never be verified honestly
                    ma = max(0, min(ma, max_pos - req.seq_len))
                row = [int(self.last_token[i])] + draft
                row += [row[-1]] * (T - len(row))     # pad: always rejected
                tokens[i] = row
                seq[i] = req.seq_len + k
                temps[i] = req.temperature
                top_ps[i] = req.top_p
                rids[i] = req.rid
                ngens[i] = len(req.generated)
                max_accept[i] = ma
        if skipped and len(skipped) == len(active):
            victim = max(skipped, key=self._eviction_key)
            self._evict(victim)
            return
        if not skipped and not max_accept.any():
            # nothing speculates this tick (sampling-only batch, or the
            # proposer came up dry everywhere): the plain (B, 1) decode
            # emits the same tokens for (k+1)x less attention/logit
            # work — a dry proposer costs one ordinary decode step
            self._decode_plain(active)
            return
        self._tick_work["decode_slots"] += len(active) - len(skipped)
        # read in this tick: the accepted counts decide the next rows
        self._run_verify(
            self._record("decode", tokens.shape, kind="verify",
                         meta={"active": active, "seq": seq,
                               "max_accept": max_accept}),
            tokens, seq, self.tables, temps, top_ps, rids, ngens,
            max_accept)

    def _emit_verified(self, active, seq, emit, n_emit, max_accept):
        """Per-slot bookkeeping of one verify program's accepted tokens."""
        from ..serving import speculative as _spec_mod

        now = self._clock()
        proposed = accepted = 0
        for i in active:
            if seq[i] == 0:
                continue
            req = self.slots[i]
            ne = int(n_emit[i])
            proposed += int(max_accept[i])
            accepted += ne - 1
            self._rt_event(req.rid, "spec_verify", t=now,
                           tick=self._ticks,
                           proposed=int(max_accept[i]),
                           accepted=ne - 1, new_tokens=ne)
            for j in range(ne):
                tok = int(emit[i, j])
                req.generated.append(tok)
                self.last_token[i] = tok
                self._record_token(req, now)
                if (len(req.generated) >= req.max_new_tokens
                        or (self.eos_id is not None
                            and tok == self.eos_id)):
                    break            # _maybe_finish releases the slot
            # valid cached positions: everything up to (not including)
            # the newest sampled token — identical invariant to decode
            self.seq_lens[i] = req.seq_len - 1
            self._maybe_finish(i)
        if proposed:
            self.spec_proposed += proposed
            self.spec_accepted += accepted
            _spec_mod.M_SPEC_PROPOSED.inc(proposed)
            _spec_mod.M_SPEC_ACCEPTED.inc(accepted)
            _spec_mod.M_SPEC_ACCEPT_RATE.set(
                self.spec_accepted / max(self.spec_proposed, 1))

    def _on_tick_failure(self, exc: BaseException):
        """Contain an unexpected tick error: the in-flight requests are
        FAILED (their KV state is suspect), their blocks reclaimed, and
        the replica degrades — it keeps serving new requests, but the
        readiness probe goes red so the balancer backs off."""
        _res.M_TICK_FAILURES.inc()
        self.tick_failures += 1
        detail = f"tick {self._ticks} failed: {exc!r}"
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            try:
                self._release_slot(slot)
            except Exception:
                self.slots[slot] = None   # never mask the containment
            self._finish_request(req, RequestStatus.FAILED, detail=detail)
        # the decode call DONATES kc/vc/state: a crash inside the executable
        # may have invalidated those buffers with the new ones never
        # assigned. Reallocate fresh pages — every slot was discarded
        # above, so later admissions re-prefill from their prompts; a
        # stale-buffer engine would otherwise fail every future tick
        # while still admitting.
        self.kc, self.vc, self.state = self._fresh_caches()
        # an unread program ran on the suspect caches for requests that
        # are FAILED now: nobody reads it
        self._unread = None
        self._inflight[:] = 0
        self.lifecycle.degrade(detail)

    def _drain_done(self) -> Dict[int, List[int]]:
        """Hand completed requests to the caller and DROP them — a
        long-running server must not retain every request ever served."""
        out = dict(self._spillover)   # client traffic served mid-warmup
        self._spillover.clear()
        out.update((req.rid, req.generated) for req in self._done)
        self._done.clear()
        return out

    def run_to_completion(self, max_ticks: int = 10_000):
        """Tick until no work remains; returns {rid: generated_tokens}
        for FINISHED requests. Requests that ended SHED / DEADLINE_MISSED
        / CANCELLED / FAILED are absent here — read ``self.outcomes``
        (or ``drain_outcomes()``) for their terminal records; never-
        fitting submissions also appear in ``self.rejected``."""
        out = self._drain_done()    # what finished while warmup() ticked
        ticks = 0
        while self.has_work():
            out.update(self.step())
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("serving engine did not converge")
        return out

    # ------------------------------------------------ replica operations
    def request_status(self, rid: int) -> Optional[str]:
        """Current status of a submitted request (terminal statuses stay
        readable until ``drain_outcomes`` pops them); None = unknown."""
        oc = self.outcomes.get(rid)
        if oc is not None:
            return oc.status
        for req in self.queue:
            if req.rid == rid:
                return req.status
        for req in self.slots:
            if req is not None and req.rid == rid:
                return req.status
        return None

    def drain_outcomes(self) -> Dict[int, RequestOutcome]:
        """Hand terminal outcomes to the caller and drop them (same
        retention contract as ``_drain_done``: a long-running replica
        must not retain every request ever served)."""
        out, self.outcomes = self.outcomes, {}
        for rid in out:          # rejected mirrors submit-time FAILED
            self.rejected.pop(rid, None)
        return out

    def cancel(self, rid: int, reason: str = "cancelled by caller") -> bool:
        """Cancel a queued or in-flight request; its KV blocks return to
        the free list immediately, without waiting for the chip: a token
        of its that is still in flight is dropped when its program is
        read (the outcome and the stream hold the tokens read so far).
        False if ``rid`` is not live."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                self._finish_request(req, RequestStatus.CANCELLED,
                                     detail=reason)
                return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                self._release_slot(slot)
                self._finish_request(req, RequestStatus.CANCELLED,
                                     detail=reason)
                if not (self.queue or self.num_active):
                    # it was the last request: the unread program has no
                    # reader to come
                    self._flush()
                return True
        return False

    # --------------------------------------------------------- streaming
    def open_stream(self, rid: int) -> List[int]:
        """Attach (or fetch) the incremental token buffer for ``rid``;
        every token the request generates from now on is appended.
        Tokens generated before the stream opened are replayed first, so
        a late-attaching client still sees the whole completion. The
        buffer object stays valid after the request ends (the engine
        drops its own reference at terminal — the stream keeps the
        list)."""
        buf = self._stream_bufs.get(rid)
        if buf is not None:
            return buf
        buf = []
        oc = self.outcomes.get(rid)
        if oc is not None:               # already terminal: replay only
            buf.extend(oc.tokens)
            return buf
        for req in list(self.queue) + [s for s in self.slots
                                       if s is not None]:
            if req.rid == rid:
                buf.extend(req.generated)
                self._stream_bufs[rid] = buf
                return buf
        return buf                       # unknown rid: empty, terminal

    def stream(self, rid: int):
        """Incremental token stream for one request: iterate tokens as
        ticks produce them (the iterator pumps ``step()`` while the
        request is live); iteration ends at the terminal status, left on
        ``stream.status``. See ``paddle_tpu.serving.TokenStream``."""
        from ..serving.stream import TokenStream
        return TokenStream(
            rid, self.open_stream(rid), self.step,
            lambda: self.request_status(rid),
            lambda s: s is None or s in TERMINAL_STATUSES,
            trace_hook=lambda ev, **meta: self._rt_event(rid, ev, **meta))

    def warmup(self, prompt_len: Optional[int] = None,
               max_new_tokens: int = 3) -> "PagedEngine":
        """Compile the steady-state programs (the one (1, prefill_width)
        prefill chunk + the batched decode step, and the hand-over of the
        fed token on the device after a chunk and after a step: hence
        three tokens; where a decode step can take a chunk aboard, that
        program too: a second synthetic request is admitted while the first
        decodes) before real traffic: STARTING→WARMING→READY.
        Idempotent on a READY replica. Nothing is left unread.

        Traffic that arrived before READY (admission is open from
        STARTING — those requests wait for exactly these compiles) is
        served alongside the synthetic warmup request; its results are
        re-delivered by the next ``step()``/``run_to_completion``."""
        if self.lifecycle.state == ReplicaState.READY:
            return self
        self.lifecycle.to(ReplicaState.WARMING, "warmup")
        n = prompt_len if prompt_len is not None else self.block_size

        def synthetic(new_tokens):
            rid = self.add_request([1] * max(1, n),
                                   max_new_tokens=new_tokens)
            # the synthetic request is operator work: no SLO deadlines
            # (expiring it mid-compile would block READY), and it jumps to
            # the queue head so a pre-READY client burst can neither starve
            # nor shed it
            for i, req in enumerate(self.queue):
                if req.rid == rid:
                    req.ttft_deadline_s = req.deadline_s = None
                    self.queue.insert(0, self.queue.pop(i))
                    break
            return rid

        rids = [synthetic(max_new_tokens)]
        # a second one once the first decodes: its chunk rides that step
        rides = self._overlap and self.max_batch > 1 and max_new_tokens > 1
        while (any(self.outcomes.get(r) is None for r in rids)
               and self.has_work()):
            res = self.step()
            if rides and any(s is not None and s.rid == rids[0]
                             and i not in self._prefilling
                             for i, s in enumerate(self.slots)):
                rids.append(synthetic(1))
                rides = False
            for rid in rids:
                res.pop(rid, None)      # warmup is not traffic
            self._spillover.update(res)
        self._flush()       # client traffic's program, if one is unread
        self._spillover = self._drain_done()
        for rid in rids:
            self._spillover.pop(rid, None)
            oc = self.outcomes.pop(rid, None)
            if oc is None or oc.status != RequestStatus.FINISHED:
                # stay in WARMING (still admits): READY would advertise a
                # replica whose steady-state programs never compiled
                raise RuntimeError(
                    f"warmup request ended "
                    f"{oc.status if oc else '<missing>'}: "
                    f"{oc.detail if oc else ''}")
        _trace.startup_args(ticks=self._ticks, synthetic=len(rids))
        self.lifecycle.to(ReplicaState.READY, "warmup complete")
        return self

    def drain(self, max_ticks: int = 10_000) -> Dict[int, List[int]]:
        """Graceful shutdown: stop admission, finish in-flight decodes,
        then STOP. Queued requests that never got a slot are CANCELLED
        (their clients retry on another replica); running requests
        decode to completion and the last program is read: nothing is
        left unread. Returns {rid: tokens} finished during the drain."""
        if self.lifecycle.state == ReplicaState.STOPPED:
            return {}
        self.lifecycle.to(ReplicaState.DRAINING, "drain()")
        for req in self.queue:
            self._finish_request(req, RequestStatus.CANCELLED,
                                 detail="drained before admission")
        self.queue = []
        out: Dict[int, List[int]] = {}
        ticks = 0
        # loop on has_work(), not num_active: livelock preemption can
        # bounce an in-flight request back through the queue mid-drain,
        # and it still must reach a terminal status
        while self.has_work():
            out.update(self.step())
            ticks += 1
            if ticks > max_ticks:
                # fail whatever is still live rather than spin forever
                for req in self.queue:
                    self._finish_request(req, RequestStatus.FAILED,
                                         detail="drain did not converge")
                self.queue = []
                for slot, req in enumerate(self.slots):
                    if req is not None:
                        self._release_slot(slot)
                        self._finish_request(
                            req, RequestStatus.FAILED,
                            detail="drain did not converge")
                self._unread = None
                break
        self.lifecycle.to(ReplicaState.STOPPED, "drained")
        _res.M_QUEUE_DEPTH.set(0)
        _res.M_KV_BLOCKS.set(self._total_usable - self.bm.available)
        return out

    def recover(self, reason: str = "operator recover"):
        """DEGRADED → READY once the operator (or an orchestrator health
        check) has decided the stall/crash cause is gone. A program still
        unread is read first (a failure of it degrades the replica like a
        tick's would, before the transition)."""
        self._flush()
        self.lifecycle.to(ReplicaState.READY, reason)

    def attach_watchdog(self, watchdog) -> "PagedEngine":
        """Wire a :class:`~paddle_tpu.distributed.watchdog.Watchdog`
        into the tick loop: every tick brackets begin_work/end_work (so
        an idle engine stays quiet), and a tick stalled past the
        watchdog timeout flips this replica DEGRADED while the watchdog
        dumps thread stacks + the span-buffer tail."""
        self._watchdog = watchdog
        prev = watchdog.on_hang

        def _on_hang(wd):
            self.lifecycle.degrade(
                f"tick stalled > {wd.timeout}s (watchdog)")
            if prev is not None:
                prev(wd)

        watchdog.on_hang = _on_hang
        return self

    def health(self) -> dict:
        """Liveness/readiness probe payload (what an HTTP /healthz in
        front of this replica returns)."""
        lc = self.lifecycle
        h = {"state": lc.state, "ready": lc.ready(),
             "live": lc.live(),
             "queue_depth": len(self.queue),
             "active": self.num_active,
             # slots part-way through a prompt, the one whose final
             # chunk is launched and not read among them
             "prefilling": len(self._prefilling) + (
                 self._unread is not None
                 and "first" in self._unread.meta),
             "kv_blocks_free": self.bm.available,
             "kv_blocks_total": self._total_usable,
             "kv_dtype": str(self.kv_dtype),
             "kv_bytes_per_token": self.kv_bytes_per_token,
             "state_bytes_per_slot": self.state_bytes_per_slot,
             "window_bytes_per_slot": self.window_bytes_per_slot,
             "latent_bytes": self.latent_bytes,
             "decode_attention": self.decode_attention,
             "ticks": self._ticks,
             "tick_failures": self.tick_failures,
             # launches made while an earlier program was unread (the
             # host worked beside the chip), and reads that found their
             # program finished (the host, not the chip, set the pace)
             "overlap_share": (self._launches_overlapped / self._launches
                               if self._launches else None),
             "host_late_share": (self._reads_late / self._reads
                                 if self._reads else None),
             # decode steps launched with the tick's last prefill chunk
             # aboard (one program, one stream of the weights) over decode
             # steps launched
             "mixed_share": (self._steps_mixed / self._steps
                             if self._steps else None),
             "phase_share": self.scheduler.phase_share(),
             "prefill_fill": self.scheduler.prefill_fill(),
             # seconds from the OS's start of the process to this
             # replica's READY and where they went (the start-up record)
             "startup": _trace.startup_summary(lc.name),
             # the probe path doubles as the burn-rate decay poll: an
             # idle replica's windows age out here, so the gauges fall
             # back to 0 after an incident instead of pinning high
             "slo_burn_rate": self._slo.burn_rates(self._clock())}
        if self._spec is not None:
            h["spec_acceptance_rate"] = (
                self.spec_accepted / self.spec_proposed
                if self.spec_proposed else None)
        load = self.expert_load(max_age_s=_EXPERT_LOAD_POLL_S)
        if load is not None:
            h["expert_load"] = load
        return h


# Backward-compatible names: the generic engine picks the adapter itself.
LlamaPagedEngine = PagedEngine
GPTPagedEngine = PagedEngine
