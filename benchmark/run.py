"""One run of one benchmark cell: build, warm every shape the window uses,
measure for ``--seconds``, compare with the plain reference, print one JSON
line last on stdout.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: BENCHMARK.json names the
configuration (``benchmark/configs/<config>.json``) and the traffic mix
(``benchmark/traffic/<mix>.json``); the mix's ``kind`` names the driver
(``benchmark/drivers/<kind>.py``); each per-layer metric has a reader
(``benchmark/layer_metrics/<metric>.py``). There is no CPU fallback: without
a TPU, or with fewer chips than the cell asks for, the run exits 2 and
prints no result. ``--control 1`` prints the control's readings (the
reference in the next lower precision) instead of running the program.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, overrides=None) -> str:
    """The run's last stdout line. ``overrides`` (a sweep's rate, say)
    replace keys of the traffic file for this process only."""
    from benchmark.lib import harness

    cell = harness.load_cell(workload)
    for key, value in (overrides or {}).items():
        where = cell["traffic"]
        if key.startswith("config."):       # e.g. config.engine.max_batch
            where, key = cell["config"], key[len("config."):]
        *path, leaf = key.split(".")
        for part in path:
            where = where.setdefault(part, {})
        where[leaf] = value
    driver = harness.load_driver(cell["traffic"]["kind"])
    harness.setup_jax_cache()
    devices = harness.require_chips(cell["cell"]["chips"])
    harness.log(f"{len(devices)} x {devices[0].device_kind} ready")
    if control:
        return json.dumps({"control": driver.control(cell, seed, devices),
                           "device": harness.device_report(devices)})
    out = driver.run(cell, seed, seconds, trace, devices, T_PROCESS)
    return finish(cell, out, trace)


def finish(cell: dict, out: dict, trace: bool) -> str:
    """The result line: the cell's end-to-end metrics without a trace, its
    per-layer metrics (and the device's busy time) with one."""
    from benchmark.lib import harness, trace_reduce

    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    device, breakdown = dict(out["device"]), None
    if not trace:
        values = {m["name"]: out["metrics"][m["name"]]
                  for m in cell["end_to_end"]}
    else:
        values = {}
        for m in cell["per_layer"]:
            v = harness.read_layer_metric(m["name"], out["ctx"])
            if v is not None:
                values[m["name"]] = v
        summary = out["ctx"].get("trace")
        if summary and summary["busy_s"] > 0:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = trace_reduce.breakdown(summary)
    metrics = {k: {"value": float(v), "unit": units[k]}
               for k, v in values.items()}
    harness.log("numbers compared:", json.dumps(out.get("numbers", {})))
    return harness.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], metrics=metrics, device=device,
        breakdown=breakdown)


def main(argv=None) -> int:
    from benchmark.lib import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the traffic file, or with a "
                         "config. prefix of the configuration (sweeps only)")
    args = ap.parse_args(argv)
    overrides = {k: json.loads(v)
                 for k, v in (kv.split("=", 1) for kv in args.set)}
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), control=bool(args.control),
                        overrides=overrides)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
