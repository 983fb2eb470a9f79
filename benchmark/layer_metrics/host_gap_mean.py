"""Mean idle gap between two device operations in the traced window."""
UNIT = "us"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["gap_count"]:
        return None
    return 1e6 * t["gap_total_s"] / t["gap_count"]
