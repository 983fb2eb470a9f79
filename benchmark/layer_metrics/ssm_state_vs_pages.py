"""What a model whose rows hold a recurrent state BESIDE a page pool keeps
for its rows, the one against the other: the bytes of every slot's states
and taps (gauge ``batcher_ssm_state_bytes``, whatever the rows hold) over the
bytes of the pages the rows held at their most (gauge
``batcher_pool_peak_held``, the pool's watermark: at the window's end the
deadline has cut every row and the pool holds none, so the count of that
moment would read nothing) x ``kernel_bytes_nemotron.page_bytes``.  Above 1
the state is the larger part of a row's memory: at 64 rows of a few
thousand tokens a row's 42 MB of state is worth 20,800 tokens of its keys.
Another configuration, or a pool that holds no page, reads nothing."""
from benchmark import kernel_bytes_nemotron as kb

UNIT = "x"


def read(ctx):
    g, config = ctx["gauges"], ctx["config"]
    if config.get("model_type") != "nemotron_h":
        return None
    state, pages = (g.get("batcher_ssm_state_bytes"),
                    g.get("batcher_pool_peak_held"))
    if not state or not pages:
        return None
    return state / (pages * kb.page_bytes(config))
