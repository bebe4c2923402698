"""How late the load generator sent requests (send time minus due time,
95th percentile): a starved generator must not read as a fast server."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return ctx["reduced"].get("gen_late_p95_ms")
