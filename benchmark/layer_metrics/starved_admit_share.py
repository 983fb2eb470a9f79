"""Of the time the device had nothing to run (``device_starved_share``'s
five counters), the share that fell inside admission rounds
(``batcher.starved.admit_seconds``): host work before an admission's
dispatch and after its fetch.  The rest is the turn into a decode span
(grow, plan, dispatch) and delivery after a span's sync.  Whole-window
counters; nothing at a program without them."""
UNIT = "%"
STARVED = tuple(f"batcher_starved_{s}_seconds"
                for s in ("admit", "grow", "plan", "dispatch", "deliver"))


def read(ctx):
    c = ctx["counters"]
    total = sum(c.get(n, 0.0) for n in STARVED)
    if not total:
        return None
    return 100.0 * c.get(STARVED[0], 0.0) / total
