"""The 90th percentile of the time to first token at the gateway over the
window (``server.ttft_seconds``; see ``gw_ttft_p50_ms``).  150-500 requests
a window leave 15-50 samples beyond it; brumby's 36 leave 3.  Nothing at a
program without the series."""
from benchmark import bucket_quantile

UNIT = "ms"


def read(ctx):
    return bucket_quantile.quantile_ms(
        ctx["counters"], "server_ttft_seconds", 0.90)
