"""The interval between decode chunks a step, by the engine thread's clock:
its time outside admission rounds (``batcher.loop.wait_device`` + ``plan`` +
``dispatch`` + ``deliver`` + ``grow``) over the steps the dispatched chunks
ran (``batcher.decode.chunks`` x the configuration's ``chunk_steps``).  The
device's step and whatever starved it between chunks, over the whole window;
beside ``decode_step_ms``, which reads 6 traced seconds.  Nothing at a
program without the chunk counter."""
UNIT = "ms"
SPANS = tuple(f"batcher_loop_{s}_seconds_sum" for s in (
    "wait_device", "plan", "dispatch", "deliver", "grow"))


def read(ctx):
    c = ctx["counters"]
    chunks = c.get("batcher_decode_chunks", 0.0)
    if not chunks:
        return None
    steps = chunks * ctx["config"]["serve"]["chunk_steps"]
    return 1e3 * sum(c.get(n, 0.0) for n in SPANS) / steps
