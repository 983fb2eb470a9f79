"""Median, over the window's whole answers, of send to answer per output
token (client)."""
from benchmark import metrics

UNIT = "ms/token"


def read(ctx):
    return metrics.percentile(metrics.norm_latencies_ms(ctx["records"]), 50)
