"""Median, over the window's whole answers, of send to answer (client)."""
from benchmark import metrics

UNIT = "ms"


def read(ctx):
    return metrics.percentile(metrics.latencies_ms(ctx["records"]), 50)
