"""What a model whose rows hold a delta-rule state BESIDE a page pool keeps
for its rows, the one against the other: the bytes of every slot's states and
taps (gauge ``batcher_gdn_state_bytes``, whatever the rows hold) over the
bytes of the pages the rows held at their most (gauge
``batcher_pool_peak_held``, the pool's watermark: at the window's end the
deadline has cut every row and the pool holds none) x
``kernel_bytes_qwen3next.page_bytes``.  Under 1 the pages are the larger part
of a row's memory: a row's 19 MB of state is worth 3,144 tokens of its keys at
6,144 bytes a token, where nemotron's is worth 20,800.  Another configuration,
or a pool that holds no page, reads nothing."""
from benchmark import kernel_bytes_qwen3next as kb

UNIT = "x"


def read(ctx):
    g, config = ctx["gauges"], ctx["config"]
    if config.get("model_type") != "qwen3_next":
        return None
    state, pages = (g.get("batcher_gdn_state_bytes"),
                    g.get("batcher_pool_peak_held"))
    if not state or not pages:
        return None
    return state / (pages * kb.page_bytes(config))
