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

What the process did BEFORE it served has a record of its own, always on
and bounded (``STARTUP_SPANS``, ``startup_record``): the phases of set-up
and one entry per trace / lower / backend compile of every program, which
JAX reports to the one listener registered here. Every producer fires at a
compile, a lifecycle transition or the entry and exit of a ``fit`` call,
never in a steady step or tick.

What stops every Python thread at once has a table of its own
(``HOST_SPANS``): the cyclic collector's passes, measured by one
``gc.callbacks`` hook that exists only while ``FLAGS_enable_metrics`` is on.
A pass is a ``host.gc`` annotation in a recording, two counters, and (a
full pass, or one of a millisecond or more) an entry of the bounded pause
record ``host_pauses()``. While a recording runs a boundary span also
carries ``cpu_s``, its thread's CPU seconds: wall time far above it was
spent off the CPU.
"""
from __future__ import annotations

import functools
import gc
import os
import threading
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

from jax import config as _jax_config
from jax import monitoring as _monitoring
from jax import profiler as _profiler
from jax._src import xla_bridge as _xla_bridge

from ..core import flags as _flags
from . import metrics as _metrics

__all__ = ["active", "activate", "deactivate", "add_complete", "span",
           "boundary", "BOUNDARY_SPANS", "drain", "clear", "MAX_EVENTS",
           "STARTUP_SPANS", "MAX_STARTUP_EVENTS", "startup_phase",
           "in_startup_phase", "startup_args", "compile_note",
           "note_import", "mark_backend", "note_backend", "startup_record",
           "startup_summary", "startup_clear", "DEVICE_SCOPES",
           "STEP_COUNTERS", "StepCounter", "counting_step", "count_in_step",
           "step_counters", "HOST_SPANS", "MAX_HOST_PAUSES", "host_pauses",
           "host_pauses_clear"]

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


#: THE list of the start-up record's entries: name -> (category, parent,
#: what it brackets). An entry's own ``parent`` is the phase that was open
#: on its thread when it began; the column gives the one it has on the two
#: hot paths ("*": whichever phase is open, else none). ``fit.setup`` and
#: ``fit.writeback`` are the boundary spans of those names, copied once a
#: ``fit`` call; no per-step or per-tick span is. README "Observability"
#: and PERF.md section 3 say which metric reads which.
STARTUP_SPANS: Dict[str, Tuple[str, Optional[str], str]] = {
    "startup.import": ("startup", None,
                       "OS process start (/proc/self/stat's starttime; the "
                       "package's first line where /proc has none) to the "
                       "last line of paddle_tpu/__init__.py (args: "
                       "before_package_s, the interpreter, `import jax` and "
                       "the caller's imports; source)"),
    "startup.backend": ("startup", None,
                        "backend initialisation: the program's first device "
                        "query where it is the process's first (args: "
                        "bracketed true), else from compile.cache."
                        "enable_jax_cache's stamp (the package's last line "
                        "without one) to the first program entry that finds "
                        "the backend up (bracketed false: an upper bound "
                        "that holds whatever the caller did between)"),
    "startup.engine_build": ("startup", None,
                             "PagedEngine.__init__: pools, tables, adapters "
                             "(args: replica, pool_bytes)"),
    "startup.warmup": ("startup", None,
                       "PagedEngine.warmup, stamped by ReplicaLifecycle at "
                       "WARMING and at the transition out of it; READY is "
                       "its end (args: replica, state, ticks, synthetic)"),
    "startup.fit_call": ("startup", None,
                         "one whole Engine.fit call (args: engine, call, "
                         "epochs, steps, state_placed_s: entry to the step "
                         "built and the state made and placed over the "
                         "mesh, first_step_s: entry to the return of its "
                         "first fit.dispatch)"),
    "fit.setup": ("fit", "startup.fit_call", BOUNDARY_SPANS["fit.setup"][2]),
    "fit.writeback": ("fit", "startup.fit_call",
                      BOUNDARY_SPANS["fit.writeback"][2]),
    "startup.prepare": ("startup", "fit.setup",
                        "Engine.prepare: building and jitting the step, not "
                        "its first call (no parent where the caller prepares "
                        "before fit)"),
    "compile.trace": ("compile", "*",
                      "/jax/core/compile/jaxpr_trace_duration of an "
                      "outermost trace (args: program; inner, the jitted "
                      "functions traced inside it, which get no entry)"),
    "compile.lower": ("compile", "*",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration "
                      "(args: program; inner, the functions a lowering "
                      "rule traced inside it, which get no entry)"),
    "compile.backend": ("compile", "*",
                        "/jax/core/compile/backend_compile_duration, the "
                        "persistent cache's read inside it (args: program; "
                        "cache hit / miss / off; retrieval_s and saved_s on "
                        "a hit)"),
}


#: THE list of the ``jax.named_scope``s a model's operations carry in their
#: ``op_name`` on the device plane, trained (``Engine``'s step) or served
#: (the paged engine's chunk, step and chunk-with-step programs): name ->
#: what runs under it. A dotted name sits inside its stem's scope. Forward
#: and backward alike (the backward's path wraps the name in
#: ``transpose(jvp(...))``); a fusion is billed to the one name the compiler
#: left on it. PERF.md section 3 says which metric reads which.
DEVICE_SCOPES: Dict[str, str] = {
    "embed": "the token embedding's gather (and a learned position table)",
    "attn": "a softmax-attention operator: projections, q/k norms, rotary "
            "angles, the flash kernels, the output projection",
    "attn.full": "a full-attention layer of a model that has windowed ones "
                 "too: served, it pages its K/V (projections, cache write, "
                 "scores, the output projection); trained (SmallThinker), "
                 "the causal flash kernels over every key",
    "attn.window": "a sliding-window attention layer: served, a ring of "
                   "window rows a lane; trained (SmallThinker), the flash "
                   "kernels with a window (projections, rotary embedding, "
                   "the K/V repeat, the kernels, the output projection)",
    "attn.full.gate": "Solar Open 2's output gate: sigmoid of its own "
                      "projection times the attention's output",
    "attn.linear": "served: a delta-rule linear-attention layer, all of it "
                   "(Olmo-Hybrid's scalar decay, Solar Open 2's decay a "
                   "channel)",
    "attn.linear.proj": "q / k / v, decay, beta and gate projections, and "
                        "the output projection",
    "attn.linear.conv": "the causal depthwise convolutions and their "
                        "carried windows",
    "attn.linear.rule": "q / k normalisation and the rule: the step kernel "
                        "``delta_rule_step`` (decode) or the chunked form",
    "attn.linear.norm": "the per-head RMSNorm of the read-out and its gate",
    "short_conv": "LFM2's gated short convolution: in-projection, the two "
                  "gates, the depthwise causal taps, out-projection",
    "mlp": "a dense feed-forward",
    "moe": "a routed expert layer, all of it",
    "moe.router": "float32 scores, top-k, renormalised weights",
    "moe.experts": "the held experts' product, either form",
    "moe.group": "grouped form only: sort of the (token, choice) pairs by "
                 "expert and, inside the layer's loop bodies (forward and "
                 "the hand-written backward, ``while/body/moe.group``), a "
                 "stride's gather of token rows, gate and write into the "
                 "result buffer; after the loop the un-sort and the sum "
                 "over a token's choices",
    "moe.grouped_matmul": "grouped form only: one grouped matmul a matrix "
                          "a stride, inside the loop bodies (XLA names the "
                          "kernel itself ``ragged-dot-*``, whatever scope "
                          "it was traced under)",
    "moe.shared": "the shared expert, where the layer has one",
    "lm_head": "the head's product where logits are returned",
    "loss": "cross entropy; with a fused head, the head's product too",
    "optimizer": "the update rule over every parameter",
}


class StepCounter(NamedTuple):
    """What a layer declares of a device counter its forward adds to while
    a compiled train step counts; ``export`` (or None) is called with the
    int64 host array of what an epoch added."""
    shape: Tuple[int, ...]
    dtype: Any
    export: Optional[Callable[[Any], None]] = None


#: THE list of device counters a compiled train step may carry in its
#: donated state: name -> what it counts. A Layer declares the ones it feeds
#: (``step_counters() -> {name: StepCounter}``); ``Engine.fit`` carries them
#: only under ``FLAGS_enable_metrics``, sums them inside the step, reads them
#: where it reads the epoch's loss and hands the epoch's count to ``export``.
STEP_COUNTERS: Dict[str, str] = {
    "moe.expert_load": "int32 (expert layers, E_held + 2): per routed layer "
                       "the tokens each held expert received, then the "
                       "(token, expert) pairs that landed on held experts "
                       "and the pairs selected in all "
                       "(nn.functional.experts.load_arrays); exported "
                       "through distributed.fleet.moe.stamp_expert_load, "
                       "and, from the landed and selected pairs and the "
                       "call's static stride, as the strides of the sorted "
                       "pair buffer the grouped product walked against the "
                       "strides of the whole buffer "
                       "(paddle_tpu_moe_pair_strides_total{kind=walked|"
                       "buffer}, distributed.fleet.moe.stamp_pair_strides: "
                       "no device counter of its own, the step's program "
                       "is unchanged)",
}

#: THE list of spans of what stops the whole host at once: name ->
#: (category, parent, what it brackets). "*": whichever span is open on the
#: thread the event ran on. Entered by ``_on_collector``, only while
#: ``FLAGS_enable_metrics`` is on. README "Observability" and PERF.md
#: section 3 say which metric reads which.
HOST_SPANS: Dict[str, Tuple[str, Optional[str], str]] = {
    "host.gc": ("host", "*",
                "one pass of the cyclic collector; every Python thread is "
                "stopped for its length, because the pass holds the GIL "
                "(stats: generation, collected, uncollectable)"),
}

_counting = threading.local()       # .sink: the step being traced, if any


class step_counters:
    """While open on this thread, ``count_in_step`` adds into ``values``
    (``Engine``'s step opens it around the traced forward, only where the
    step's state carries counters)."""

    def __init__(self):
        self.values: Dict[str, Any] = {}

    def __enter__(self):
        self._outer = getattr(_counting, "sink", None)
        _counting.sink = self
        return self

    def __exit__(self, *exc):
        _counting.sink = self._outer
        return False


def counting_step() -> bool:
    """Whether the forward being traced feeds a step's counters: a layer
    computes what it would count only then."""
    return getattr(_counting, "sink", None) is not None


def count_in_step(name: str, value) -> None:
    """Add ``value`` to the counter ``name`` of ``STEP_COUNTERS`` (any other
    name is a KeyError) of the step being traced; nothing where none is.
    Call it where the value belongs to the step's own trace, not from inside
    a ``jax.checkpoint``ed block."""
    STEP_COUNTERS[name]
    sink = getattr(_counting, "sink", None)
    if sink is not None:
        held = sink.values.get(name)
        sink.values[name] = value if held is None else held + value


_thread_time = time.thread_time     # cpu_s's clock (tests count its reads)


class boundary(span):
    """A span of ``BOUNDARY_SPANS`` (any other name is a KeyError): the
    buffer like ``span``, and a ``jax.profiler.TraceAnnotation`` of the same
    name (a ``StepTraceAnnotation`` when ``step_num`` is given) that carries
    ``args`` as the event's stats. The annotation exists only while a
    ``jax.profiler`` recording runs, the buffer entry only while ``active()``;
    ``args`` is read at exit, so a site may fill it while the span is open.
    The annotation also carries ``cpu_s``, the thread's CPU seconds inside
    the span (``time.thread_time``, read only where an annotation was made).
    The names ``STARTUP_SPANS`` lists too are phases of the start-up
    record, whatever is active."""

    __slots__ = ("_step", "_ann", "_phase", "_cpu0")

    def __init__(self, name: str, args: Optional[dict] = None,
                 step_num: Optional[int] = None):
        self._step = step_num is not None
        if self._step:
            args = dict(args or (), step_num=step_num)
        span.__init__(self, name, BOUNDARY_SPANS[name][0], args)
        self._ann = self._phase = None

    def __enter__(self):
        if self.name in STARTUP_SPANS:
            self._phase = startup_phase(self.name)
        if _profiler.TraceAnnotation.is_enabled():
            self._ann = (_profiler.StepTraceAnnotation if self._step
                         else _profiler.TraceAnnotation)(self.name)
            self._ann.__enter__()
            self._cpu0 = _thread_time()
        return span.__enter__(self)

    def __exit__(self, *exc):
        span.__exit__(self, *exc)
        if self._phase is not None:
            self._phase.end(self.args)
        if self._ann is not None:
            self._ann.set_metadata(cpu_s=_thread_time() - self._cpu0,
                                   **(self.args or {}))
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


# --------------------------------------------------------------------------
# The start-up record: what the process did before it served. Always on
# (the fit driver switches metrics on only just before its window, and an
# operator asks of untraced processes), bounded, on perf_counter's clock.
# --------------------------------------------------------------------------
#: record cap: a trainer that calls ``fit`` for ever meets it, not a leak
MAX_STARTUP_EVENTS = 4096

_perf_counter = time.perf_counter   # the record's one clock (tests count it)
_startup: List[Tuple[str, str, float, float, int, Optional[dict],
                     Optional[str]]] = []
_startup_dropped = {"n": 0}
_open = threading.local()           # .stack: this thread's open phases
_backend = {"seen": False, "mark": None}
_summaries: Dict[Any, Tuple[int, dict]] = {}


def _startup_add(name, t0, t1, args, parent):
    with _lock:
        if len(_startup) >= MAX_STARTUP_EVENTS:
            _startup_dropped["n"] += 1
            return
        _startup.append((name, STARTUP_SPANS[name][0], t0, t1, _tid(),
                         args, parent))


def _open_stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


class startup_phase:
    """One open phase of ``STARTUP_SPANS`` (any other name is a KeyError),
    begun where it is made: ``with startup_phase(name) as ph`` or, where
    begin and end are two calls (a lifecycle's transitions),
    ``ph = startup_phase(name)`` ... ``ph.end()``. Entries that begin on
    this thread while it is open name it as their parent. ``args`` (the
    caller's dict, not a copy) may be filled until the end; ``end`` may run
    on another thread, once."""

    __slots__ = ("name", "args", "parent", "t0", "_stack")

    def __init__(self, name: str, args: Optional[dict] = None):
        if name not in STARTUP_SPANS:
            raise KeyError(name)
        self.name, self.args = name, {} if args is None else args
        self._stack = _open_stack()
        self.parent = self._stack[-1].name if self._stack else None
        self._stack.append(self)
        self.t0 = _perf_counter()

    def mark(self, key: str):
        """``args[key]``: seconds from the phase's begin to now."""
        self.args[key] = _perf_counter() - self.t0

    def end(self, args: Optional[dict] = None):
        stack, self._stack = self._stack, None
        if stack is None:
            return
        t1 = _perf_counter()
        with _lock:
            if self in stack:
                stack.remove(self)
        if args:
            self.args.update(args)
        _startup_add(self.name, self.t0, t1, self.args or None, self.parent)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def in_startup_phase(name: str):
    """Decorator: the whole call is one ``startup_phase(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*a, **kw):
            with startup_phase(name):
                return fn(*a, **kw)
        return call
    return wrap


def startup_args(**args):
    """Add ``args`` to the innermost phase open on this thread (none: a
    no-op), from inside the work it brackets."""
    stack = _open_stack()
    if stack:
        stack[-1].args.update(args)


def _process_start() -> Optional[float]:
    """The OS's start of this process on ``perf_counter``'s clock: field 22
    of /proc/self/stat (clock ticks since boot) against CLOCK_BOOTTIME.
    None where /proc or the clock is not there."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return _perf_counter() - age if age >= 0 else None


def note_import(t_first_line: float):
    """``startup.import``, from the last line of ``paddle_tpu/__init__.py``
    (``t_first_line``: the stamp its first line took)."""
    t1 = _perf_counter()
    t0 = _process_start()
    source = "proc_stat"
    if t0 is None or t0 > t_first_line:
        t0, source = t_first_line, "package_first_line"
    _backend["imported"] = t1   # an unbracketed backend's last-resort mark
    _startup_add("startup.import", t0, t1,
                 {"before_package_s": t_first_line - t0, "source": source},
                 None)


def mark_backend():
    """The lower mark of an unbracketed ``startup.backend``: every chip
    entry point passes ``enable_jax_cache`` before its first compile."""
    if not _backend["seen"] and _backend["mark"] is None:
        _backend["mark"] = _perf_counter()


def note_backend(query: bool = False, at: Optional[float] = None):
    """``startup.backend``, from a program entry (or, ``at`` its begin, the
    first compile JAX reports): one check of a flag once it is seen.
    ``query``: the caller is about to ask for a device anyway, so an
    uninitialised backend is brought up HERE, bracketed; without it such
    an entry only looks. A backend the caller brought up first is bounded
    by two marks (``STARTUP_SPANS``)."""
    if _backend["seen"]:
        return
    up = _xla_bridge.backends_are_initialized()
    if not up and not query:
        return
    t1 = _perf_counter() if at is None else at
    if up:
        mark = _backend["mark"]
        t0 = min(t1, _backend.get("imported", t1) if mark is None else mark)
    else:
        t0 = t1
        _xla_bridge.get_backend()
        t1 = _perf_counter()
    if _backend["seen"]:        # another thread's entry got here first
        return
    _backend["seen"] = True
    _startup_add("startup.backend", t0, t1, {"bracketed": not up}, None)


_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
#: the brackets that hold traces of their own: a trace the jitted functions
#: it calls, a lowering the functions its rules trace (threefry's is
#: hundreds of ``jnp`` calls). Only the outermost gets an entry.
_NESTING = ("compile.trace", "compile.lower")
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


class _Compiling(threading.local):
    """This thread's compile in flight: how deep in ``_NESTING`` brackets
    it is (every ``jnp`` function traced inside a trace or a lowering is
    one), how many the outermost held, the cache's answer so far."""
    depth = 0
    inner = 0
    cache: Optional[dict] = None
    notes: Optional[dict] = None


_compiling = _Compiling()


def _on_compile_begin(event, _value, **_kw):
    if _COMPILE_EVENTS.get(event) in _NESTING:
        _compiling.depth += 1


def _on_compile_event(event, **_kw):
    """A compile that asks the persistent cache is a miss until the cache
    says hit; one that never asks, or asks a cache with no directory, has
    the cache off."""
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        if not _jax_config.jax_compilation_cache_dir:
            outcome = "off"
        if _compiling.cache is None:
            _compiling.cache = {}
        _compiling.cache["cache"] = outcome


def _on_compile_seconds(event, duration, fun_name=None, **_kw):
    """The listener of the durations JAX reports: an entry that ends now
    and began ``duration`` ago. Only the outermost trace or lowering gets
    an entry (the ones inside it are counted, ``inner``). The cache's events
    of a backend compile arrive inside its bracket, on its thread, before
    its duration."""
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        key = _CACHE_SECONDS.get(event)
        if key is not None and _compiling.cache is not None:
            _compiling.cache[key] = duration
        return
    args = {"program": fun_name}
    if name in _NESTING:
        _compiling.depth = max(_compiling.depth - 1, 0)
        if _compiling.depth:
            _compiling.inner += 1
            return
        args["inner"], _compiling.inner = _compiling.inner, 0
        if _compiling.notes:
            args.update(_compiling.notes)
        _compiling.notes = None
    else:
        args.update(_compiling.cache or {"cache": "off"})
    if name != "compile.trace":
        _compiling.cache = None     # a lowering starts a new lookup
    t1 = _perf_counter()
    if not _backend["seen"]:
        note_backend(at=t1 - duration)
    stack = _open_stack()
    _startup_add(name, t1 - duration, t1, args,
                 stack[-1].name if stack else None)


def compile_note(key: str, value: dict):
    """From code that runs while a program is traced (a kernel's wrapper):
    ``args[key] = value`` on the ``compile.trace`` or ``compile.lower``
    entry of the outermost bracket in flight on this thread, with
    ``calls``, how often the trace said so. Outside one: a no-op."""
    if _compiling.depth:
        if _compiling.notes is None:
            _compiling.notes = {}
        calls = _compiling.notes.get(key, {}).get("calls", 0)
        _compiling.notes[key] = dict(value, calls=calls + 1)


def compile_noted(key: str) -> Optional[dict]:
    """The note ``key`` of the bracket in flight as it stands (for a note
    that sums over a trace's calls); None where there is none."""
    return (_compiling.notes or {}).get(key) if _compiling.depth else None


_monitoring.register_scalar_listener(_on_compile_begin)
_monitoring.register_event_listener(_on_compile_event)
_monitoring.register_event_duration_secs_listener(_on_compile_seconds)


def startup_clear():
    """Empty the record (tests; the open phases stay open)."""
    with _lock:
        del _startup[:]
        _startup_dropped["n"] = 0
        _summaries.clear()


def _seconds(intervals) -> float:
    """Length of the union of ``(t0, t1)`` intervals."""
    total, end = 0.0, None
    for t0, t1 in sorted(intervals):
        if end is not None:
            t0 = max(t0, end)
        if t1 > t0:
            total += t1 - t0
            end = t1
    return total


#: the phases that are the program's own work (compiles whose parent is
#: one of them are the program's; under none, the caller's)
PROGRAM_PHASES = ("startup.engine_build", "startup.warmup",
                  "startup.fit_call", "startup.prepare", "fit.setup",
                  "fit.writeback")


def startup_record() -> dict:
    """The record: ``entries`` ``(name, cat, t0, t1, tid, args, parent)``
    in the order they ended, ``dropped`` (entries past the cap),
    ``process_start`` (``startup.import``'s begin; None without one) and
    ``ready``, the program's own marks ``(kind, who, t)``: a replica's
    transition to READY, the return of the first ``fit.dispatch`` of a
    ``fit`` call."""
    with _lock:
        entries = list(_startup)
        dropped = _startup_dropped["n"]
    start = next((e[2] for e in entries if e[0] == "startup.import"), None)
    ready = []
    for name, _cat, t0, t1, _tid_, args, _parent in entries:
        args = args or {}
        if name == "startup.warmup" and args.get("state") == "READY":
            ready.append(("replica", args.get("replica"), t1))
        elif name == "startup.fit_call" and args.get("first_step_s"):
            ready.append(("fit", args.get("engine"),
                          t0 + args["first_step_s"]))
    return {"entries": entries, "dropped": dropped, "process_start": start,
            "ready": sorted(ready, key=lambda r: r[2])}


def startup_summary(who: Optional[str] = None) -> dict:
    """What ``health()["startup"]`` and the ``paddle_tpu_startup_*`` gauges
    show: seconds from the OS's start of the process to ready, and where
    they went. ``who`` (a replica's or an Engine's name) keeps that one's
    build, warm-up and ready mark; None the whole process's, ready at its
    first mark. Compiles count while they began before ready under a
    program phase. Recomputed only when the record has grown."""
    with _lock:
        n = len(_startup)
        cached = _summaries.get(who)
    if cached is not None and cached[0] == n:
        return dict(cached[1])
    rec = startup_record()
    entries, start = rec["entries"], rec["process_start"]
    ready = next((t for _kind, name, t in rec["ready"]
                  if who is None or name == who), None)

    def mine(e):
        args = e[5] or {}
        return who is None or who in (args.get("replica"),
                                      args.get("engine"))

    def spans(*names, own=False):
        return [(e[2], e[3]) for e in entries if e[0] in names
                and (not own or mine(e))]

    compiles = [e for e in entries if e[0].startswith("compile.")
                and e[6] in PROGRAM_PHASES and (ready is None or e[2] < ready)]
    backend = [e for e in compiles if e[0] == "compile.backend"]
    fit = spans("fit.setup", "fit.writeback", "startup.prepare")
    compiling = [(e[2], e[3]) for e in compiles]
    out = {
        "ready_s": (ready - start if None not in (ready, start) else None),
        "import_s": _seconds(spans("startup.import")),
        "backend_s": _seconds(spans("startup.backend")),
        "build_s": _seconds(spans("startup.engine_build", own=True)),
        "warmup_s": _seconds(spans("startup.warmup", own=True)),
        # less the compiles inside: |A| - |A and B| = |A or B| - |B|
        "fit_setup_s": _seconds(fit + compiling) - _seconds(compiling),
        "trace_lower_s": _seconds([(e[2], e[3]) for e in compiles
                                   if e[0] != "compile.backend"]),
        "compile_s": _seconds([(e[2], e[3]) for e in backend]),
        "cache_misses": sum(1 for e in backend if e[5]["cache"] == "miss"),
        "programs": len(backend),
        "entries": n, "dropped": rec["dropped"],
    }
    with _lock:
        _summaries[who] = (n, out)
    return dict(out)


# --------------------------------------------------------------------------
# The collector's pauses (HOST_SPANS). The hook is in ``gc.callbacks`` only
# while FLAGS_enable_metrics is on: off, a pass reads no clock of ours.
# --------------------------------------------------------------------------
#: pause record cap; the passes past it are counted as ``dropped``
MAX_HOST_PAUSES = 4096
#: a pass of a younger generation enters the pause record from here on
HOST_PAUSE_MIN_S = 1e-3

_M_GC_SECONDS = _metrics.counter(
    "paddle_tpu_host_gc_pause_seconds_total",
    "Seconds every Python thread stood still for the cyclic collector",
    ("generation",))
_M_GC_COLLECTIONS = _metrics.counter(
    "paddle_tpu_host_gc_collections_total",
    "Passes of the cyclic collector", ("generation",))
_GC_KEYS = tuple(_M_GC_SECONDS.key(generation=g) for g in range(3))

_pauses: List[Tuple[float, float, int, int, int]] = []
_pauses_dropped = {"n": 0}
# the pass in flight: passes do not nest, and the GIL is held from the
# "start" callback to the "stop" one
_collecting = {"t0": 0.0, "ann": None}


def _on_collector(phase: str, info: dict):
    """The ``gc.callbacks`` hook. A young pass under ``HOST_PAUSE_MIN_S``
    allocates no container: two floats and two counter slots."""
    if phase == "start":
        if _profiler.TraceAnnotation.is_enabled():
            ann = _collecting["ann"] = _profiler.TraceAnnotation("host.gc")
            ann.__enter__()
        _collecting["t0"] = _perf_counter()
        return
    t1 = _perf_counter()
    t0 = _collecting["t0"]
    generation = info["generation"]
    ann = _collecting["ann"]
    if ann is not None:
        _collecting["ann"] = None
        ann.set_metadata(generation=generation, collected=info["collected"],
                         uncollectable=info["uncollectable"])
        ann.__exit__(None, None, None)
    key = _GC_KEYS[generation]
    _M_GC_SECONDS.inc_at(key, t1 - t0)
    _M_GC_COLLECTIONS.inc_at(key)
    if generation == 2 or t1 - t0 >= HOST_PAUSE_MIN_S:
        if len(_pauses) >= MAX_HOST_PAUSES:
            _pauses_dropped["n"] += 1
        else:
            _pauses.append((t0, t1, generation, info["collected"], _tid()))


def _watch_collector(on):
    """``FLAGS_enable_metrics``'s observer: the hook is in ``gc.callbacks``
    exactly while the flag is on."""
    if on and _on_collector not in gc.callbacks:
        gc.callbacks.append(_on_collector)
    elif not on and _on_collector in gc.callbacks:
        gc.callbacks.remove(_on_collector)


_flags.on_change("enable_metrics", _watch_collector)
_watch_collector(_metrics.enabled())


def host_pauses() -> dict:
    """The pause record: ``entries`` ``(t0, t1, generation, collected,
    tid)`` on ``perf_counter``'s clock (the start-up record's) in the order
    they ended, every full (generation 2) pass and any pass of
    ``HOST_PAUSE_MIN_S`` or more since ``FLAGS_enable_metrics`` went on,
    and ``dropped``, the passes past ``MAX_HOST_PAUSES``."""
    return {"entries": list(_pauses), "dropped": _pauses_dropped["n"]}


def host_pauses_clear():
    """Empty the pause record (tests; a window of one's own)."""
    del _pauses[:]
    _pauses_dropped["n"] = 0
