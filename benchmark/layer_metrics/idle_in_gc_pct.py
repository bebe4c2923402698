"""Share of the first chip's idle time in the traced window that lies under
a ``host.gc`` event of the program (one pass of the cyclic collector, on any
thread: the pass holds the GIL, so the thread that drives the device stands
still too). None where the recording holds no such event: a program from
before the hook (one that has it shows young passes many times a second)."""
from benchmark.lib import host_pauses


def read(ctx):
    return host_pauses.idle_in_gc_pct(ctx)
