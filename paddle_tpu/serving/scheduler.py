"""Phase-split tick scheduling: chunked prefill budgeted against decode.

The pathology (Sarathi-Serve names it): a continuous-batching engine
that prefills every admitted prompt to completion inside the admission
tick stalls the decode batch for the whole prompt length — one 2k-token
prompt freezes every in-flight stream's inter-token latency. The fix the
production stacks converged on (Sarathi chunked prefill, DistServe
prefill/decode disaggregation): prompts advance under a per-tick token
budget, and the batched decode step runs EVERY tick regardless of
pending prefill — decode has priority, prefill gets the leftover budget.
The engine spends the tick's budget as ONE chunk program as wide as the
budget (a whole number of ``block_size`` blocks, capped at the engine's
own widest chunk), on ONE slot: of two slots mid-prefill the one admitted
first gets all of the tick's tokens, the other waits for it to finish.

:class:`Scheduler` owns that budget arithmetic plus the phase
accounting; the engine asks it ``token_quota()`` each tick and reports
every chunk/decode program it runs. ``prefill_token_budget=None`` keeps
the round-3 behavior (drain all pending chunks in the admission tick) —
single-replica batch jobs that only care about completion throughput
lose nothing, while a router-fronted replica sets a budget and holds
inter-token latency through prompt bursts.

Metrics (stable rows, see README "Serving tier"):
``paddle_tpu_serving_prefill_tokens_total`` /
``paddle_tpu_serving_decode_tokens_total`` count scheduled tokens per
phase, ``paddle_tpu_serving_prefill_prompt_tokens_total`` the real
prompt tokens among the prefill rows (their ratio is
:meth:`Scheduler.prefill_fill`: how much of a chunk program was work);
``paddle_tpu_serving_tick_phase_share{phase=}`` is the sliding
share of device time each phase took over recent ticks — the signal a
capacity planner reads to split a fleet into prefill- and decode-heavy
replica pools (the DistServe topology) without re-instrumenting.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..observability import metrics as _metrics

__all__ = ["SchedulerConfig", "Scheduler"]


M_PREFILL_TOKENS = _metrics.counter(
    "paddle_tpu_serving_prefill_tokens_total",
    "Prompt tokens scheduled through chunked prefill (includes chunk "
    "padding — the tokens the chip actually processed).")
M_PREFILL_PROMPT_TOKENS = _metrics.counter(
    "paddle_tpu_serving_prefill_prompt_tokens_total",
    "Real prompt tokens advanced through chunked prefill (chunk padding "
    "left out); over paddle_tpu_serving_prefill_tokens_total it is the "
    "share of the prefill programs' rows that were work.")
M_DECODE_TOKENS = _metrics.counter(
    "paddle_tpu_serving_decode_tokens_total",
    "Tokens scheduled through the batched decode step (speculative "
    "verify positions count — they are decode compute).")
M_TICK_PHASE_SHARE = _metrics.gauge(
    "paddle_tpu_serving_tick_phase_share",
    "Sliding share of per-tick device time spent in each serving phase "
    "(prefill vs decode), over the last window of ticks.",
    labelnames=("phase",))
M_PREFILL_DEFERRED = _metrics.counter(
    "paddle_tpu_serving_prefill_chunks_deferred_total",
    "Prefill chunks ready to run but pushed to a later tick by the "
    "scheduler's token budget (decode-priority interleaving at work).")


@dataclass
class SchedulerConfig:
    """Knobs for the phase-split tick scheduler.

    ``prefill_token_budget``
        Upper bound on prompt tokens advanced per tick (whole
        ``block_size`` blocks; a chunk's left padding counts, it is
        rows the chip computes). ``None`` disables the split: admitted
        prompts prefill to completion in their admission tick (the
        round-3 behavior).
    ``min_prefill_chunks``
        Progress guarantee: even when the budget is smaller than one
        block, at least this many blocks of prompt run per tick while
        prefill work is pending — a budget can interleave, never
        livelock.
    ``share_window_ticks``
        Ticks in the sliding window behind the phase-share gauge. Of
        the engine's ticks that could launch a chunk and a decode step as
        one program, one in this many launches them apart, so that the
        window (and a device trace as long) holds each program alone.
    """

    prefill_token_budget: Optional[int] = None
    min_prefill_chunks: int = 1
    share_window_ticks: int = 32

    def __post_init__(self):
        if (self.prefill_token_budget is not None
                and self.prefill_token_budget < 1):
            raise ValueError("prefill_token_budget must be >= 1 or None")
        if self.min_prefill_chunks < 1:
            raise ValueError("min_prefill_chunks must be >= 1")
        if self.share_window_ticks < 1:
            raise ValueError("share_window_ticks must be >= 1")


class Scheduler:
    """Budgets each engine tick between chunked prefill and decode and
    keeps the per-phase accounting (tokens, device seconds, tick share).

    One scheduler belongs to one engine; the engine drives it:
    ``chunk_quota`` at the top of the prefill pass, ``note_phase`` after
    every compiled program, ``end_tick`` when the tick closes.
    """

    def __init__(self, config: Optional[SchedulerConfig] = None):
        self.config = config or SchedulerConfig()
        #: lifetime token totals per phase (mirrors the counters, local
        #: so health()/bench can read them without the metrics registry)
        self.prefill_tokens = 0
        self.prefill_prompt_tokens = 0
        self.decode_tokens = 0
        self.deferred_chunks = 0
        self._window = []          # (prefill_s, decode_s) per tick
        self._tick_s = {"prefill": 0.0, "decode": 0.0}

    # ------------------------------------------------------------ budget
    def token_quota(self, block_size: int) -> Optional[int]:
        """Prompt tokens (whole ``block_size`` blocks) this tick may
        spend on prefill; ``None`` = unbounded (no phase split
        configured)."""
        budget = self.config.prefill_token_budget
        if budget is None:
            return None
        return block_size * max(self.config.min_prefill_chunks,
                                budget // block_size)

    def note_deferred(self, chunks: int):
        if chunks > 0:
            self.deferred_chunks += chunks
            M_PREFILL_DEFERRED.inc(chunks)

    def note_prompt_tokens(self, tokens: int):
        """``tokens`` real prompt tokens rode the prefill program just
        noted (its other rows were left padding)."""
        self.prefill_prompt_tokens += tokens
        M_PREFILL_PROMPT_TOKENS.inc(tokens)

    def prefill_fill(self) -> Optional[float]:
        """Real prompt tokens over the rows the prefill programs
        computed, lifetime; None before the first chunk."""
        if not self.prefill_tokens:
            return None
        return self.prefill_prompt_tokens / self.prefill_tokens

    def tick_phase_seconds(self) -> dict:
        """The CURRENT tick's accumulated per-phase device seconds
        (before ``end_tick`` folds them into the window). The engine
        stamps this onto its ``serving.tick`` span, so the chrome view
        ``tools/request_trace.py`` merges shows each tick's
        prefill/decode split next to the request lanes."""
        return dict(self._tick_s)

    # -------------------------------------------------------- accounting
    def note_phase(self, phase: str, tokens: int, seconds: float):
        """One compiled program ran: ``tokens`` scheduled positions in
        ``phase`` took ``seconds`` of (blocking-read bracketed) time."""
        if phase == "prefill":
            self.prefill_tokens += tokens
            M_PREFILL_TOKENS.inc(tokens)
        else:
            self.decode_tokens += tokens
            M_DECODE_TOKENS.inc(tokens)
        self._tick_s[phase if phase in self._tick_s else "decode"] += \
            seconds

    def end_tick(self):
        """Close the tick: fold its phase seconds into the sliding
        window and export the share gauges."""
        cur = (self._tick_s["prefill"], self._tick_s["decode"])
        self._tick_s = {"prefill": 0.0, "decode": 0.0}
        if cur == (0.0, 0.0):
            return
        self._window.append(cur)
        if len(self._window) > self.config.share_window_ticks:
            self._window.pop(0)
        p = sum(w[0] for w in self._window)
        d = sum(w[1] for w in self._window)
        total = p + d
        if total > 0:
            M_TICK_PHASE_SHARE.set(p / total, phase="prefill")
            M_TICK_PHASE_SHARE.set(d / total, phase="decode")

    def phase_share(self) -> dict:
        """The gauge values as a dict (for ``health()`` / bench)."""
        p = sum(w[0] for w in self._window)
        d = sum(w[1] for w in self._window)
        total = p + d
        return {"prefill": (p / total) if total else None,
                "decode": (d / total) if total else None}
