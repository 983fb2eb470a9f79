"""Trips through the compiler (a compile or a load from the persistent
cache) that the child's compiler log shows inside the window."""
UNIT = "count"


def read(ctx):
    return float(len(ctx["compiled_in_window"]))
