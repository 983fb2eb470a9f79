"""The median interval between two deliveries of tokens to one request, over
the window: from its first token, or a chunk that brought it tokens, to the
next chunk that did (``batcher.row.gap_seconds``, stamped in the batcher's
``_collect`` on its own clock; read by ``bucket_quantile`` from the window's
``batcher_row_gap_seconds_le_us_*`` and ``_count``).  Near ``chunk_steps`` x
``engine_step_ms`` where fewer than half the chunks follow an admission
round.  Nothing at a program without the series."""
from benchmark import bucket_quantile

UNIT = "ms"


def read(ctx):
    return bucket_quantile.quantile_ms(
        ctx["counters"], "batcher_row_gap_seconds", 0.50)
