"""The flash kernels' share of their roofline in the FULL (causal, all keys)
layers of a ``smallthinker`` training cell, from the device trace:
``window_flash_roofline_pct``'s reading over the calls under the scope
``attn.full``, billed over the causal triangle: the causal kernels at head
width 128 and the model's whole context."""
from benchmark.lib import flash_scopes


def read(ctx):
    return flash_scopes.smallthinker_roofline_pct(ctx, "attn.full", None)
