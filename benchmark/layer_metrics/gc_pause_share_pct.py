"""Share of the window every Python thread stood still for the cyclic
collector: 100 x the seconds of the program's pause record
(``paddle_tpu.observability.trace.host_pauses``: every full pass and any
pass of a millisecond or more, on ``perf_counter``'s clock) that began in
the ``window_s`` seconds after the replica's READY stamp, over ``window_s``.
The count and seconds by generation and the five longest passes are logged
beside it. None where the program keeps no pause record."""
from benchmark.lib import host_pauses


def read(ctx):
    return host_pauses.gc_pause_share_pct(ctx)
