"""Host clock over the window divided by its steps, in milliseconds (every
epoch ends in a host read of a value the step produced)."""


def read(ctx):
    if ctx["kind"] != "fit":
        return None
    return 1e3 * ctx["window_s"] / (ctx["epochs"] * ctx["steps_per_epoch"])
