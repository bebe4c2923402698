"""Share of the decode steps launched with the tick's last prefill chunk
aboard, one program and one stream of the weights
(``paddle_tpu_serving_launches_total{kind}``: mixed over decode + verify +
mixed, the counter behind ``health()["mixed_share"]``), over the whole
process, warm-up included."""
from benchmark.lib import serving_counters


def read(ctx):
    return serving_counters.share_pct(
        ctx, "paddle_tpu_serving_launches_total",
        lambda labels: labels["kind"] == "mixed",
        lambda labels: labels["kind"] in ("decode", "verify", "mixed"))
