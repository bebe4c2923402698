"""The comparisons that decide ``correct``. Every number compared is printed
beside its limit; the limits live in the configuration's file under
``check`` with the readings they were set from in PERF.md."""
from __future__ import annotations

import math
import statistics

from benchmark.lib.harness import log


class Verdict:
    def __init__(self):
        self.rows = []          # (name, value, limit, ok)

    def compare(self, name: str, value: float, limit: float):
        ok = bool(math.isfinite(value) and value <= limit)
        self.rows.append((name, float(value), float(limit), ok))
        log(f"check {name}: {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if ok else 'FAILED'}")
        return ok

    def require(self, name: str, ok: bool, detail=""):
        self.rows.append((name, 0.0 if ok else 1.0, 0.0, bool(ok)))
        log(f"check {name}: {'ok' if ok else 'FAILED'} {detail}")
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)

    def numbers(self) -> dict:
        return {name: value for name, value, _l, _ok in self.rows}


def worst_leaf_gap(got: dict, ref: dict) -> float:
    """Worst leaf by the gap between the program's norm and the
    reference's (not the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger: some
    gradients are all but zero."""
    floor = statistics.median(ref.values())
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in ref)


def train_numbers(got: dict, ref: dict) -> dict:
    """The numbers a training cell compares, program against reference."""
    out = {f"loss_gap_step{i + 1}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]))}
    out["grad_norm_gap"] = worst_leaf_gap(got["grad_norms"],
                                          ref["grad_norms"])
    out["delta_norm_gap"] = worst_leaf_gap(got["delta_norms"],
                                           ref["delta_norms"])
    return out
