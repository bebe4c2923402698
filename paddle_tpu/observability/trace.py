"""Span/event tracer — one host timeline across framework layers.

Collects complete-span events (dispatch ops, to_static/SOT compiles,
collectives, autotune probes, user RecordEvent ranges) into a bounded
in-memory buffer while a profiler session is recording; the profiler's
``export_chrome_tracing`` drains the buffer and merges every layer into a
single chrome trace (the role of the reference's HostTraceLevel event
collector in fluid/platform/profiler/host_tracer.cc). When no session is
active every instrumentation site costs one dict lookup.

The spans at the layer boundaries of the two hot paths (``BOUNDARY_SPANS``)
have a second sink: ``boundary`` also enters a
``jax.profiler.TraceAnnotation`` of the same name, so whoever records a
``jax.profiler`` trace finds them on the xplane's host plane, on the clock
of the device's ``XLA Ops`` line. Per-op sites stay buffer-only.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax import profiler as _profiler

__all__ = ["active", "activate", "deactivate", "add_complete", "span",
           "boundary", "BOUNDARY_SPANS", "drain", "clear", "MAX_EVENTS"]

#: buffer cap — a runaway loop must degrade to dropped spans, not OOM
MAX_EVENTS = 200_000

# Hot mirror, same contract as metrics.enabled(): dict-lookup cost off.
_active = {"on": False}
_lock = threading.Lock()
_events: List[Tuple[str, str, float, float, int, Optional[dict]]] = []
_dropped = {"n": 0}

_tid_lock = threading.Lock()
_tid_map: Dict[int, int] = {}


def _tid() -> int:
    """Small stable per-thread id for the chrome trace tid column."""
    ident = threading.get_ident()
    t = _tid_map.get(ident)
    if t is None:
        with _tid_lock:
            t = _tid_map.setdefault(ident, len(_tid_map))
    return t


def active() -> bool:
    return _active["on"]


def activate():
    _active["on"] = True


def deactivate():
    _active["on"] = False


def clear():
    with _lock:
        del _events[:]
        _dropped["n"] = 0


def add_complete(name: str, cat: str, t0: float, t1: float,
                 args: Optional[dict] = None):
    """Record one finished span (perf_counter seconds). Caller is expected
    to have checked ``active()`` before paying for the timestamps."""
    if not _active["on"]:
        return
    with _lock:
        if len(_events) >= MAX_EVENTS:
            _dropped["n"] += 1
            return
        _events.append((name, cat, t0, t1, _tid(), args))


class span:
    """Scoped span: ``with trace.span("compile:fn", "compile"): ...``.
    Near-free when inactive (one dict lookup, no timestamps)."""

    __slots__ = ("name", "cat", "args", "_t0")

    def __init__(self, name: str, cat: str = "framework",
                 args: Optional[dict] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = None

    def __enter__(self):
        if _active["on"]:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            add_complete(self.name, self.cat, self._t0,
                         time.perf_counter(), self.args)
        return False


#: THE list of boundary spans: name -> (buffer category, parent, what it
#: brackets). A parent's time outside its children is its own host work; a
#: span no other names as parent is a leaf. ``fit.*`` run on the thread that
#: called ``Engine.fit``, ``io.prefetch`` on the prefetcher's producer
#: thread, the rest on the thread that ticks the router. README
#: "Observability" and PERF.md section 3 say which metric reads which.
BOUNDARY_SPANS: Dict[str, Tuple[str, Optional[str], str]] = {
    "fit.setup": ("fit", None,
                  "entry of Engine.fit to the first wait for a batch: "
                  "optimizer state, replication over the mesh, the loader"),
    "fit.step": ("fit", None,
                 "one iteration of the training loop (a StepTraceAnnotation "
                 "with step_num)"),
    "fit.next_batch": ("fit", "fit.step",
                       "the wait on the prefetcher (or the synchronous "
                       "fetch and placement)"),
    "fit.dispatch": ("fit", "fit.step",
                     "the call of the compiled train step"),
    "fit.post_step": ("fit", "fit.step",
                      "LR scheduler, running loss sum, memory census, fleet "
                      "beacon, goodput ledger, sentinel"),
    "fit.epoch_sync": ("fit", None,
                       "the epoch's one host read of the loss sum"),
    "fit.writeback": ("fit", None,
                      "trained arrays and optimizer state back into the "
                      "eager objects (fit's finally)"),
    "io.prefetch": ("io", None,
                    "producer thread: fetch the next batch and place it on "
                    "the device"),
    "router.step": ("serving", None, "one tier tick"),
    "router.deliver": ("serving", "router.step",
                       "token deltas to the open streams, outcomes settled"),
    "serving.tick": ("serving", "router.step",
                     "one PagedEngine.step (args: tick, active, queued, "
                     "prompt_tokens, decode_slots, the scheduler's phase "
                     "seconds)"),
    "serving.admit": ("serving", "serving.tick",
                      "expire deadlines, admit from the queue, shed overload"),
    "serving.plan": ("serving", "serving.tick",
                     "before a program call: pick its lanes, ensure their KV "
                     "blocks, fill the call's host rows"),
    # the two brackets keep the category tools/loadgen.py, perf.attribute
    # and tools/request_trace.py key on; they are HOST clock over a launch
    # and the blocking read of the program launched BEFORE it (one program
    # stays in flight): back to back they cover the device's time, each is
    # one program late
    "serving.prefill": ("device", "serving.tick",
                        "one prefill-chunk program launch"),
    "serving.prefill.build": ("serving", "serving.prefill",
                              "eval mode on (a walk over every sublayer of "
                              "a model in training mode), the call's host "
                              "arrays to the device"),
    "serving.prefill.launch": ("serving", "serving.prefill",
                               "the call of the compiled program, the "
                               "training flag restored"),
    "serving.prefill.wait": ("serving", "serving.prefill",
                             "the blocking read of the oldest unread "
                             "program's tokens: the one launched before "
                             "this launch"),
    "serving.decode": ("device", "serving.tick",
                       "one decode (or speculative verify) program launch"),
    "serving.decode.build": ("serving", "serving.decode",
                             "eval mode on (a walk over every sublayer of "
                             "a model in training mode), the call's host "
                             "arrays to the device"),
    "serving.decode.launch": ("serving", "serving.decode",
                              "the call of the compiled program, the "
                              "training flag restored"),
    "serving.decode.wait": ("serving", "serving.decode",
                            "the blocking read of the oldest unread "
                            "program's tokens: the one launched before "
                            "this launch (a verify step's own; with "
                            "nothing to launch, directly under the tick)"),
    "serving.emit": ("serving", "serving.tick",
                     "per-slot token bookkeeping of the program just read"),
}


class boundary(span):
    """A span of ``BOUNDARY_SPANS`` (any other name is a KeyError): the
    buffer like ``span``, and a ``jax.profiler.TraceAnnotation`` of the same
    name (a ``StepTraceAnnotation`` when ``step_num`` is given) that carries
    ``args`` as the event's stats. The annotation exists only while a
    ``jax.profiler`` recording runs, the buffer entry only while ``active()``;
    ``args`` is read at exit, so a site may fill it while the span is open."""

    __slots__ = ("_step", "_ann")

    def __init__(self, name: str, args: Optional[dict] = None,
                 step_num: Optional[int] = None):
        self._step = step_num is not None
        if self._step:
            args = dict(args or (), step_num=step_num)
        span.__init__(self, name, BOUNDARY_SPANS[name][0], args)
        self._ann = None

    def __enter__(self):
        if _profiler.TraceAnnotation.is_enabled():
            self._ann = (_profiler.StepTraceAnnotation if self._step
                         else _profiler.TraceAnnotation)(self.name)
            self._ann.__enter__()
        return span.__enter__(self)

    def __exit__(self, *exc):
        span.__exit__(self, *exc)
        if self._ann is not None:
            if self.args:
                self._ann.set_metadata(**self.args)
            self._ann.__exit__(*exc)
        return False


def drain() -> List[Tuple[str, str, float, float, int, Optional[dict]]]:
    """Return and clear the collected spans (profiler export path)."""
    with _lock:
        out = list(_events)
        del _events[:]
    return out


def tail(n: int = 100) -> List[Tuple[str, str, float, float, int,
                                     Optional[dict]]]:
    """Newest ``n`` spans WITHOUT clearing the buffer — hang/crash
    diagnostics (the watchdog dumps this post-mortem; the profiler's
    export still sees everything)."""
    with _lock:
        return list(_events[-n:]) if n else []


def dropped() -> int:
    return _dropped["n"]
