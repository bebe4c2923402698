"""Micro-batch pipeline schedules over partitioned stages.

Three schedule families, expressed as explicit per-stage step tables
(the IR-level complement of the fleet runtime's tick rings in
``fleet/meta_parallel/pipeline_schedules.py``):

* ``fthenb`` (GPipe) — every stage runs all m forwards, then all m
  backwards. Peak activation residency m per stage; bubble fraction
  (S-1)/(m+S-1).
* ``1f1b`` — each stage warms up with ``S-1-s`` forwards then
  alternates one-forward-one-backward. Same bubble as GPipe but peak
  residency ``min(m, S-s)`` — the memory win that makes m >> S viable.
* ``zb`` (ZBH1-style) — the backward is split into a B step (produce
  the input gradient, unblocking the upstream stage immediately) and a
  deferred W step (the weight-gradient work) that fills what would be
  bubble slots. The analytical bubble shrinks toward (S-1)/(3m+S-1) on
  the three-phase clock.

:func:`build_schedule` emits ``[[ScheduleStep, ...], ...]`` (one
ordered list per stage); :func:`simulate` runs the earliest-start
event simulation under the dataflow dependencies (F(s,µ) after
F(s-1,µ); B(s,µ) after B(s+1,µ) and F(s,µ); W after its B; per-stage
serialization in table order) and reports the makespan + per-stage
busy time — with unit costs that IS the analytical bubble fraction,
and with measured per-step durations it is the measured one.
"""
from __future__ import annotations

from collections import namedtuple
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["ScheduleStep", "SCHEDULES", "build_schedule", "simulate",
           "analytical_bubble", "peak_inflight"]

#: one slot of a stage's timetable: kind F (forward), B (backward /
#: input-grad), W (deferred weight-grad; zb only), mb = microbatch
ScheduleStep = namedtuple("ScheduleStep", ["kind", "stage", "mb"])

SCHEDULES = ("fthenb", "1f1b", "zb")


def _norm(name: str) -> str:
    n = str(name).lower().replace("-", "").replace("_", "")
    aliases = {"gpipe": "fthenb", "fthenb": "fthenb", "fb": "fthenb",
               "1f1b": "1f1b", "zb": "zb", "zbh1": "zb",
               "zerobubble": "zb"}
    if n not in aliases:
        raise ValueError(f"unknown schedule {name!r} "
                         f"(one of {SCHEDULES})")
    return aliases[n]


def build_schedule(name: str, num_stages: int,
                   num_microbatches: int) -> List[List[ScheduleStep]]:
    """Per-stage ordered step tables for ``name`` (see module doc)."""
    S, m = int(num_stages), int(num_microbatches)
    if S < 1 or m < 1:
        raise ValueError(f"need S >= 1 and m >= 1, got S={S} m={m}")
    name = _norm(name)
    table: List[List[ScheduleStep]] = []
    for s in range(S):
        steps: List[ScheduleStep] = []
        if name == "fthenb":
            steps += [ScheduleStep("F", s, i) for i in range(m)]
            steps += [ScheduleStep("B", s, i) for i in range(m)]
        else:
            # 1F1B skeleton: warmup forwards, steady 1F1B, cooldown.
            # zb defers every W out of the steady F/B alternation (the
            # ZBH1 move: B unblocks upstream, W fills cooldown slots).
            warm = min(m, S - 1 - s)
            pending: List[int] = []
            for i in range(warm):
                steps.append(ScheduleStep("F", s, i))
            for k in range(m - warm):
                steps.append(ScheduleStep("F", s, warm + k))
                steps.append(ScheduleStep("B", s, k))
                if name == "zb":
                    pending.append(k)
            for k in range(m - warm, m):
                steps.append(ScheduleStep("B", s, k))
                if name == "zb":
                    pending.append(k)
                    # interleave one deferred W per cooldown backward
                    steps.append(ScheduleStep("W", s, pending.pop(0)))
            for k in pending:
                steps.append(ScheduleStep("W", s, k))
        table.append(steps)
    return table


def peak_inflight(table: List[List[ScheduleStep]]) -> List[int]:
    """Per-stage peak number of microbatches whose forward activations
    are resident at once (F opens a slot, B closes it) — the
    double-buffering depth the runtime must provision."""
    peaks = []
    for steps in table:
        live = peak = 0
        for st in steps:
            if st.kind == "F":
                live += 1
                peak = max(peak, live)
            elif st.kind == "B":
                live -= 1
        peaks.append(peak)
    return peaks


def simulate(table: List[List[ScheduleStep]],
             durations: Optional[Dict[tuple, float]] = None,
             default_costs: Optional[Dict[str, float]] = None) -> dict:
    """Earliest-start simulation of a schedule table under the pipeline
    dataflow dependencies.

    ``durations`` maps ``(kind, stage, mb) -> seconds`` (measured per
    step); missing entries fall back to ``default_costs[kind]``
    (default F=1, B=2, W=0 — B covers dX+dW except under zb, where
    B=1 and W=1 split the backward). Returns makespan, per-stage busy
    seconds, and the bubble fraction
    ``1 - sum(busy) / (S * makespan)``."""
    S = len(table)
    zb = any(st.kind == "W" for steps in table for st in steps)
    costs = {"F": 1.0, "B": 1.0 if zb else 2.0, "W": 1.0 if zb else 0.0}
    costs.update(default_costs or {})
    durations = durations or {}

    done: Dict[tuple, float] = {}
    busy = [0.0] * S
    cursor = [0] * S          # next step index per stage
    clock = [0.0] * S         # stage-local time front

    def dur(st: ScheduleStep) -> float:
        return float(durations.get((st.kind, st.stage, st.mb),
                                   costs.get(st.kind, 1.0)))

    def deps_ready(st: ScheduleStep):
        k, s, mb = st
        need = []
        if k == "F" and s > 0:
            need.append(("F", s - 1, mb))
        if k == "B":
            need.append(("F", s, mb))
            if s < S - 1:
                need.append(("B", s + 1, mb))
        if k == "W":
            need.append(("B", s, mb))
        ts = [done.get(n) for n in need]
        if any(t is None for t in ts):
            return None
        return max(ts, default=0.0)

    total = sum(len(steps) for steps in table)
    executed = 0
    while executed < total:
        progressed = False
        for s in range(S):
            while cursor[s] < len(table[s]):
                st = table[s][cursor[s]]
                ready = deps_ready(st)
                if ready is None:
                    break
                start = max(clock[s], ready)
                d = dur(st)
                clock[s] = start + d
                done[tuple(st)] = clock[s]
                busy[s] += d
                cursor[s] += 1
                executed += 1
                progressed = True
        if not progressed:
            stuck = [(s, table[s][cursor[s]]) for s in range(S)
                     if cursor[s] < len(table[s])]
            raise RuntimeError(
                f"schedule deadlock — steps with unsatisfiable "
                f"dependencies: {stuck}")
    makespan = max(clock) if clock else 0.0
    bubble = 0.0
    if makespan > 0 and S > 0:
        bubble = max(0.0, 1.0 - sum(busy) / (S * makespan))
    return {"makespan": makespan, "busy": busy, "bubble": bubble,
            "steps": total}


def analytical_bubble(name: str, num_stages: int,
                      num_microbatches: int) -> float:
    """Analytical bubble fraction on the unit-cost clock.

    For fthenb/1f1b this is PipeDream's closed form ``(S-1)/(m+S-1)``
    — exactly what :func:`simulate` reports at unit costs, which the
    tests pin. The static ZBH1 table has no simple closed form (its
    bubble depends on how far the deferred W slots reach into the
    cooldown), so zb's analytical estimate IS the unit-cost
    simulation; it is strictly below the 1f1b figure for S > 1."""
    S, m = int(num_stages), int(num_microbatches)
    if S <= 1:
        return 0.0
    name = _norm(name)
    if name == "zb":
        return simulate(build_schedule("zb", S, m))["bubble"]
    return (S - 1) / float(m + S - 1)
