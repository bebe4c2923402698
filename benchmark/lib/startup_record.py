"""The readers' view of the program's start-up record
(``paddle_tpu.observability.trace.startup_record``: phases of set-up and one
entry per trace / lower / backend compile of every program, on
``time.perf_counter``, the harness's clock).

The record is cut at the window's start: the first fetch of the window's
first epoch (fit), the replica's own READY stamp (serving). Up to the cut

    cut - OS process start = import + backend + program phases + unattributed

by construction: ``import`` and ``backend`` are the record's two entries of
those names, the program phases the union of ``PagedEngine``'s build and
warm-up and of every ``Engine.fit`` call, and what no entry covers is the
caller's own time (the benchmark's plan, weights and, on the fit cells, its
reference) plus whatever the record does not name. Compiles are billed to
the program where the phase open on their thread was the program's; the
reference's and the weight maker's run under none. A program without the
record (the parent of the PR that brought it) gives every reader None.
"""
from __future__ import annotations

import sys

from benchmark.lib import harness
from benchmark.lib.program_spans import merged, overlap_seconds
from benchmark.lib.trace_reduce import union_seconds as _length

#: phases that are the program's own work; a compile whose parent is one
#: of them is the program's
PROGRAM_PHASES = ("startup.engine_build", "startup.warmup",
                  "startup.fit_call", "startup.prepare", "fit.setup",
                  "fit.writeback")
FIT_SETUP = ("fit.setup", "fit.writeback", "startup.prepare")
ENGINE_WARMUP = ("startup.engine_build", "startup.warmup")


def record():
    """The program's record, or None where it keeps none."""
    try:
        from paddle_tpu.observability import trace
    except ImportError:
        return None
    read = getattr(trace, "startup_record", None)
    return read() if read is not None else None


def cut_of(ctx, rec):
    """Where the window starts on the record's clock, or None."""
    if ctx["kind"] == "fit":
        return ctx["epoch_starts"][0]
    ready = [t for kind, _who, t in rec["ready"] if kind == "replica"]
    return ready[-1] if ready else None


def split_of(rec, cut) -> dict:
    """The split of [OS process start, ``cut``]; see the module's text."""
    start = rec["process_start"]

    def clipped(entries):
        return merged([(max(e[2], start), min(e[3], cut)) for e in entries
                       if max(e[2], start) < min(e[3], cut)])

    def named(*names):
        return [e for e in rec["entries"] if e[0] in names]

    imported = clipped(named("startup.import"))
    backend = clipped(named("startup.backend"))
    phases = clipped(named(*PROGRAM_PHASES))
    mine = [e for e in rec["entries"] if e[0].startswith("compile.")
            and e[6] in PROGRAM_PHASES and e[2] < cut]
    compiles = clipped(mine)
    backend_compiles = [e for e in mine if e[0] == "compile.backend"]
    fit_setup = clipped(named(*FIT_SETUP))
    named_time = merged(imported + backend + phases)
    import_s = _length(imported)
    backend_s = _length(backend) - overlap_seconds(backend, imported)
    out = {
        "process_start": start, "cut": cut, "total_s": cut - start,
        "import_s": import_s, "backend_s": backend_s,
        "phases_s": _length(named_time) - import_s - backend_s,
        "unattributed_s": (cut - start) - _length(named_time),
        "trace_lower_s": _length(clipped(
            [e for e in mine if e[0] != "compile.backend"])),
        "compile_s": _length(clipped(backend_compiles)),
        "cache_misses": sum(1 for e in backend_compiles
                            if e[5].get("cache") == "miss"),
        "fit_setup_s": _length(fit_setup) - overlap_seconds(fit_setup,
                                                            compiles),
        "engine_warmup_s": _length(clipped(named(*ENGINE_WARMUP))),
        "programs": _programs(mine),
        "fit_calls": [_fit_call(rec, e, cut)
                      for e in named("startup.fit_call") if e[2] < cut],
    }
    out["own_compiles"], out["gaps"] = _unnamed(
        rec, named("startup.import", "startup.backend", *PROGRAM_PHASES),
        start, cut)
    return out


def _fit_call(rec, call, cut) -> dict:
    """One ``fit`` call's seconds up to the cut: its wall, its entry to its
    state made and placed and to the return of its first dispatch, its
    set-up and writeback; the rest is its steps (and its compiles, billed
    by program)."""
    inside = {name: sum(min(e[3], cut) - e[2] for e in rec["entries"]
                        if e[0] == name and call[2] <= e[2] < min(call[3],
                                                                  cut))
              for name in FIT_SETUP}
    args = call[5]
    return {"call": args.get("call"), "steps": args.get("steps"),
            "wall": round(min(call[3], cut) - call[2], 3),
            "state_placed_s": round(args.get("state_placed_s") or 0.0, 3),
            "first_step_s": round(args.get("first_step_s") or 0.0, 3),
            **{name: round(v, 3) for name, v in inside.items()}}


def _programs(mine) -> list:
    """Per program: seconds traced, lowered and compiled, and the cache's
    answer, longest first."""
    table = {}
    for name, _cat, t0, t1, _tid, args, _parent in mine:
        program = (args.get("program") or "?")
        if program.startswith("jit(") and program.endswith(")"):
            program = program[4:-1]
        row = table.setdefault(program.removeprefix("jit_"), {
            "trace": 0.0, "lower": 0.0, "backend": 0.0, "n": 0, "cache": []})
        row[name.split(".")[1]] += t1 - t0
        if name == "compile.backend":
            row["n"] += 1
            row["cache"].append(args.get("cache"))
    rows = sorted(table.items(), key=lambda kv: -sum(
        kv[1][k] for k in ("trace", "lower", "backend")))
    return [{"program": program,
             **{k: round(row[k], 3) for k in ("trace", "lower", "backend")},
             "compiles": row["n"],
             "cache": {c: row["cache"].count(c) for c in set(row["cache"])}}
            for program, row in rows]


def _unnamed(rec, phases, start, cut):
    """What sits in the time no phase names: the seconds of the compiles
    that ran under no program phase (the reference's, the weight maker's),
    and the longest stretches between two entries of any kind, each with
    the entries on either side."""
    loose = [e for e in rec["entries"] if e[0].startswith("compile.")
             and e[6] not in PROGRAM_PHASES and e[2] < cut]
    seconds = {kind: round(sum(min(e[3], cut) - e[2] for e in loose
                               if e[0] == "compile." + kind), 3)
               for kind in ("trace", "lower", "backend")}
    seconds["programs"] = sum(1 for e in loose if e[0] == "compile.backend")
    marks = sorted([(e[2], -min(e[3], cut), e[0]) for e in phases]
                   + [(e[2], -min(e[3], cut), e[5].get("program"))
                      for e in loose])       # at one begin, the outermost
    gaps, at, before = [], start, "process start"
    for s, e, what in [(s, -e, what) for s, e, what in marks if s < cut] + [
            (cut, cut, "the window")]:
        if s > at:
            gaps.append((round(s - at, 3), before, what))
        if e > at:
            at, before = e, what
    return seconds, sorted(gaps, reverse=True)[:5]


def split(ctx):
    """The run's split, computed and logged once, or None."""
    if "startup_split" not in ctx:
        rec = record()
        cut = cut_of(ctx, rec) if rec else None
        if not rec or cut is None or rec["process_start"] is None:
            ctx["startup_split"] = None
            return None
        got = ctx["startup_split"] = split_of(rec, cut)
        t_process = getattr(sys.modules.get("__main__"), "T_PROCESS", None)
        show = {k: round(v, 3) for k, v in got.items()
                if isinstance(v, float)}
        harness.log(
            f"start-up record: {len(rec['entries'])} entries "
            f"({rec['dropped']} dropped); seconds from the OS's start of "
            f"the process to the cut {show}; run.py's first line "
            + (f"{t_process - got['process_start']:.3f} s after the start, "
               f"the cut {got['cut'] - t_process:.3f} s after it"
               if t_process is not None else "not seen"))
        harness.log("start-up record, the program's compiles by program "
                    f"(the longest 12 of {len(got['programs'])}):",
                    got["programs"][:12], "; fit calls:", got["fit_calls"])
        harness.log("start-up record, unattributed: compiles under no "
                    f"program phase {got['own_compiles']}; longest stretches "
                    f"with no entry (s, after, before) {got['gaps']}")
    return ctx["startup_split"]


def read(ctx, key: str):
    """One number of the run's split (a reader's whole body)."""
    got = split(ctx)
    return None if got is None else got[key]
