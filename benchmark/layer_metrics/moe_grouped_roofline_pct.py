"""The grouped matmuls' share of their roofline, from the device trace: the
least time the chip could take for the grouped-matmul calls the trace shows
(operations and bytes of the pairs that LANDED on the held experts,
``lib/flops_lfm2_moe.py``, against the published peaks) over the summed
device time of those calls. Whichever kernel implements the grouped product
is read: today XLA's ``ragged-dot-*`` Mosaic kernel behind
``jax.lax.ragged_dot``.

A call's sizes are told by its result: ``[pairs buffer, out]`` is a product
with the experts' matrices or their transposes (``in`` is the other of the
hidden size and the expert width), ``[experts, in, out]`` a weight gradient.
Its group sizes are the step's own counter's over the TRACED steps, averaged
over them and the routed layers (``ctx["expert_load_traced"]``, the load of
the epoch the trace covers: the window's mean would bill fewer pairs than the
traced calls multiplied, since the router of a one-chip share drifts towards
the held experts). Without the counter nothing is read."""
from benchmark.lib import flops, flops_lfm2_moe, peaks, train_scopes


def group_sizes(ctx):
    """Mean pairs a held expert received in one layer of one traced step,
    or None without the traced steps' counter."""
    load, steps = ctx.get("expert_load_traced"), ctx.get("traced_steps")
    if not load or not steps:
        return None
    held = len(load[0]) - 2
    return [sum(row[e] for row in load) / (len(load) * steps)
            for e in range(held)]


def read(ctx):
    if ctx["kind"] != "fit" or ctx["config"].get("arch") != "lfm2_moe":
        return None
    calls, sizes = train_scopes.grouped_calls(ctx), group_sizes(ctx)
    if not calls or not sizes:
        return None
    cfg = ctx["config"]
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    pk = peaks.peaks_for(ctx["device_kind"])
    ideal = spent = 0.0
    for shape, secs in calls:
        n_out = shape[-1]
        k_in = shape[-2] if len(shape) == 3 else (
            width if n_out == hidden else hidden)
        ideal += flops.roofline_seconds(
            flops_lfm2_moe.grouped_call_flops(sizes, k_in, n_out),
            flops_lfm2_moe.grouped_call_bytes(sizes, k_in, n_out), pk)
        spent += secs
    return 100.0 * ideal / spent if spent else None
