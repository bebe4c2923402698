"""The flash kernels' share of their roofline in the WINDOW layers of a
``smallthinker`` training cell, from the device trace: the least time the
chip could take for the flash calls whose ``op_name`` lies under the scope
``attn.window`` (forward, dQ, dK/dV; operations over the (query, key) pairs
inside the window's band, ``lib/flops_smallthinker.py``, against the
published peaks) over the summed device time of those calls. The model
repeats each K/V head to the query heads it serves before the call, so a
call is billed as the kernel gets it: ``num_attention_heads`` sequences."""
from benchmark.lib import flash_scopes


def read(ctx):
    window = ctx["config"].get("sliding_window_size")
    return flash_scopes.smallthinker_roofline_pct(ctx, "attn.window", window)
