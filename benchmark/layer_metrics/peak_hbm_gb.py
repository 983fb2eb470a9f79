"""``peak_bytes_in_use`` of the fullest device after the window."""
import re

UNIT = "GB"


def read(ctx):
    peaks = [v for k, v in ctx["gauges"].items()
             if re.fullmatch(r"device\d+_peak_bytes_in_use", k)]
    return max(peaks) / 1e9 if peaks else None
