"""Seconds of ``PagedEngine.__init__`` (``startup.engine_build``) and of its
warm-up, WARMING to READY (``startup.warmup``), tracing, lowering and cache
loads of the serving programs included."""
from benchmark.lib import startup_record


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return startup_record.read(ctx, "engine_warmup_s")
