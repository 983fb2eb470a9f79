"""The 99th percentile of the interval between two deliveries of tokens to
one request, over the window (``batcher.row.gap_seconds``; see
``row_gap_p50_ms``): what a resident request waits when other requests'
admission rounds fall between its chunks.  4,000-20,000 deliveries a
window leave 40-200 samples beyond it.  Nothing at a program without the
series."""
from benchmark import bucket_quantile

UNIT = "ms"


def read(ctx):
    return bucket_quantile.quantile_ms(
        ctx["counters"], "batcher_row_gap_seconds", 0.99)
