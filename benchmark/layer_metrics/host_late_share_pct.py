"""Share of the reads of a launched program's tokens that found the program
already finished (``paddle_tpu_serving_reads_total{host_late}``, the counter
behind ``health()["host_late_share"]``), over the whole process, warm-up
included: near 100 the host, not the chip, sets the pace. It carries what
``overlap_share`` would: a launch made with an earlier program unread only
helps where the read that follows still has to wait."""
from benchmark.lib import serving_counters


def read(ctx):
    return serving_counters.share_pct(
        ctx, "paddle_tpu_serving_reads_total",
        lambda labels: labels["host_late"] == "true")
