"""Shares of the serving engine's own launch and read counters, read from
the in-process metrics registry (on only in a traced run, which switches
``FLAGS_enable_metrics`` on before the engine is built)."""
from __future__ import annotations


def share_pct(ctx, counter: str, counted, among=lambda labels: True):
    """100 x the series of ``counter`` that ``counted`` picks over those
    ``among`` picks (both take a series' ``{label: value}``), or None where
    the run is no serving run, the program has no such counter or label, or
    it counted nothing."""
    if ctx["kind"] != "serve":
        return None
    from paddle_tpu.observability import metrics
    metric = metrics.REGISTRY.snapshot().get(counter)
    if metric is None:
        return None
    series = [(dict(zip(metric["labelnames"], s["labels"])), s["value"])
              for s in metric["series"]]
    try:
        total = sum(n for labels, n in series if among(labels))
        picked = sum(n for labels, n in series
                     if among(labels) and counted(labels))
    except KeyError:
        return None
    return 100.0 * picked / total if total else None
