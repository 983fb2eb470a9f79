"""The share of the engine thread's time in which the device had nothing to
run: the five ``batcher.starved.*_seconds`` counters (from a blocking fetch
that returned the newest program's output to the next call that dispatches
one, charged to the ``batcher.loop.*`` span it fell in) over the six loop
spans' sums, which partition that thread's time.  Whole-window counters; a
lower bound of the device's idle share by construction.  A program without
the counters (before PR 36) gives nothing: a span that was never starved has
no counter yet and counts as 0, none at all reads as absent."""
UNIT = "%"
STARVED = tuple(f"batcher_starved_{s}_seconds"
                for s in ("admit", "grow", "plan", "dispatch", "deliver"))
LOOP = tuple(f"batcher_loop_{s}_seconds_sum" for s in (
    "admit", "grow", "plan", "dispatch", "wait_device", "deliver"))


def read(ctx):
    c = ctx["counters"]
    loop = sum(c.get(n, 0.0) for n in LOOP)
    if not loop or not any(n in c for n in STARVED):
        return None
    return 100.0 * sum(c.get(n, 0.0) for n in STARVED) / loop
