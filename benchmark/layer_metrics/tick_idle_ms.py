"""Milliseconds a tick leaves the first chip idle inside ``router.step``:
over the traced ticks that launched at least one program (a prefill chunk,
a decode step, or both) and lie wholly inside the traced window. It is
``tick_host_exposed_ms`` without the filter that keeps decode-only ticks, so
it reads a number in the cells whose every tick carries a chunk. The split
by span and, for the five longest gaps, the span open at the gap's start
with its wall and CPU seconds and the collector's passes under the gap are
logged beside it."""
from benchmark.lib import host_pauses


def read(ctx):
    return host_pauses.tick_idle_ms(ctx)
