"""The host's part of an admission: ``batcher.admit.row`` less its child
``batcher.admit.wait_device`` (the ONE blocking fetch of the admission's
outputs: the program on the chip), over the row span.  Reserving pages,
hashing, building the operands, the dispatch call and the row's activation
are the numerator.  Whole-window histogram sums; nothing at a program whose
admissions have no fetch span."""
UNIT = "%"
ROW = "batcher_admit_row_seconds_sum"
WAIT = "batcher_admit_wait_device_seconds_sum"


def read(ctx):
    c = ctx["counters"]
    if WAIT not in c or not c.get(ROW):
        return None
    return 100.0 * (c[ROW] - c[WAIT]) / c[ROW]
