"""Seconds from the OS's start of the process to the last line of
``paddle_tpu/__init__.py`` (the record's ``startup.import``): the
interpreter, ``import jax``, the caller's imports and the package's own."""
from benchmark.lib import startup_record


def read(ctx):
    return startup_record.read(ctx, "import_s")
