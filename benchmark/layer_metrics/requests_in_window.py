"""Requests sent inside the window, as the client counted them."""
from benchmark import metrics

UNIT = "count"


def read(ctx):
    return float(len(metrics.window(ctx["records"])))
