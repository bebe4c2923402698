"""Share of the window the training loop waited for a batch:
``paddle_tpu_prefetch_stall_seconds_total`` over the window."""


def read(ctx):
    if ctx["kind"] != "fit":
        return None
    return 100.0 * ctx["stall_s"] / ctx["window_s"]
