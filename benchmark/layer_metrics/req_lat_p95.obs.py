"""95th percentile, over the window's whole answers, of send to answer."""
from benchmark import metrics

UNIT = "ms"


def read(ctx):
    return metrics.percentile(metrics.latencies_ms(ctx["records"]), 95)
