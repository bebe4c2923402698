"""paddle_tpu.observability.perf — device-time performance attribution.

The layer that turns round-8 host telemetry into actionable performance
truth (reference analogue: the profiler subsystem's device-event +
memory-profiling half; XLA lineage: ``Compiled.cost_analysis()`` /
``memory_analysis()``):

- :mod:`.costmodel` — analytical per-op-class FLOPs/bytes formulas
  attached to the op registry (``OpDef.cost_fn``), cross-checkable
  against XLA's own cost analysis.
- :mod:`.device` — ``block_until_ready``-bracketed timed sections,
  compiled-program cost/memory capture at to_static/SOT compile time
  (``FLAGS_perf_capture``), and the step-time attribution pass that
  decomposes each step into compute / collective / host / idle.
- :mod:`.memory` — live-HBM census attributed as params / grads /
  optimizer state / KV cache / activations via holder providers, with
  per-phase high-water tracking (``paddle_tpu_hbm_*`` metrics).

Reporting rides in ``tools/perf_report.py`` (roofline table + attribution
breakdown).
"""
from __future__ import annotations

from . import costmodel, device, memory
from .costmodel import (OpCost, attach_cost_models, collective_cost,
                        cost_of, xla_cost)
from .device import (attribute, capture_enabled, compiled_programs,
                     measure, record_compiled, step_attribution,
                     timed_section)
from .memory import census, high_water, update_high_water

__all__ = ["costmodel", "device", "memory", "OpCost", "cost_of",
           "attach_cost_models", "collective_cost", "xla_cost",
           "attribute", "capture_enabled", "compiled_programs", "measure",
           "record_compiled", "step_attribution", "timed_section",
           "census", "high_water", "update_high_water", "PEAK_FLOPS",
           "PEAK_HBM_BW", "HBM_CAPACITY", "chip_peak_flops",
           "chip_peak_bw", "chip_hbm_bytes"]

#: peak dense bf16 FLOPs/s per chip (public spec sheets) — the roofline's
#: compute ceiling
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}

#: peak HBM bandwidth (bytes/s) per chip — public spec sheets; the
#: roofline's second ceiling
PEAK_HBM_BW = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
    "TPU7x": 7400e9,
}


#: Nominal figures for the CPU platform ONLY. Dev hosts and the tier-1
#: tests need finite planner / roofline arithmetic; these are not the
#: peaks of any device and no number derived from them is a device metric.
CPU_NOMINAL_FLOPS = 1e12
CPU_NOMINAL_MEM_BW = 100e9
CPU_NOMINAL_MEM_BYTES = 16e9


def _chip_lookup(table, device_obj, cpu_nominal: float) -> float:
    """``table`` entry for the device's ``device_kind``. A kind the
    table does not know is an error, not a default: a roofline or MFU
    computed against another chip's peak is a wrong number under the
    right name."""
    import jax

    d = device_obj if device_obj is not None else jax.devices()[0]
    if d.platform == "cpu":
        return cpu_nominal
    kind = d.device_kind
    for name, v in table.items():
        if kind.lower().startswith(name.lower()):
            return v
    raise KeyError(
        f"no published peak for device_kind {kind!r} (platform "
        f"{d.platform!r}); add it to the tables in "
        f"paddle_tpu/observability/perf/__init__.py with its source")


def chip_peak_flops(device_obj=None) -> float:
    """Peak dense bf16 FLOPs/s of the chip (``CPU_NOMINAL_FLOPS`` on the
    CPU platform; unknown accelerator kinds raise)."""
    return _chip_lookup(PEAK_FLOPS, device_obj, CPU_NOMINAL_FLOPS)


def chip_peak_bw(device_obj=None) -> float:
    """Peak HBM bytes/s of the chip (``CPU_NOMINAL_MEM_BW`` on the CPU
    platform; unknown accelerator kinds raise)."""
    return _chip_lookup(PEAK_HBM_BW, device_obj, CPU_NOMINAL_MEM_BW)


#: HBM capacity (bytes) per chip — public spec sheets; the placement
#: planner's hard memory ceiling (a plan whose per-device high-water
#: exceeds this is rejected, not ranked)
HBM_CAPACITY = {
    "TPU v2": 8e9,
    "TPU v3": 16e9,
    "TPU v4": 32e9,
    "TPU v5 lite": 16e9,
    "TPU v5e": 16e9,
    "TPU v5": 95e9,
    "TPU v5p": 95e9,
    "TPU v6 lite": 32e9,
    "TPU v6e": 32e9,
    "TPU7x": 192e9,
}


def chip_hbm_bytes(device_obj=None) -> float:
    """HBM capacity in bytes of one chip (``CPU_NOMINAL_MEM_BYTES`` on
    the CPU platform; unknown accelerator kinds raise)."""
    return _chip_lookup(HBM_CAPACITY, device_obj, CPU_NOMINAL_MEM_BYTES)
