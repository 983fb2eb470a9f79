"""The median time to first token at the gateway, receipt to the first
streamed token, over the window (``server.ttft_seconds``, observed in
``runtime/server.py``; read by ``bucket_quantile`` from the window's
``server_ttft_seconds_le_us_*`` and ``_count``): beside ``gw_ttft_mean``,
which a few long queue waits pull up.  Nothing at a program without the
series."""
from benchmark import bucket_quantile

UNIT = "ms"


def read(ctx):
    return bucket_quantile.quantile_ms(
        ctx["counters"], "server_ttft_seconds", 0.50)
