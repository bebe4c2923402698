"""Seconds the ``Engine.fit`` calls before the window spent outside their
steps: the union of ``fit.setup`` (optimizer state, replication over the
mesh, the loader), ``startup.prepare`` and ``fit.writeback`` of every call,
less the compiles inside them (``startup_trace_lower_s`` and
``startup_compile_s`` bill those)."""
from benchmark.lib import startup_record


def read(ctx):
    if ctx["kind"] != "fit":
        return None
    return startup_record.read(ctx, "fit_setup_s")
