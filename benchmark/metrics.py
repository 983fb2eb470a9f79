"""From client records, counters and the trace to named metrics.

End-to-end metrics are arithmetic on the client's own records (host clock).
Per-layer metrics are files under ``layer_metrics/``, found by name: a
``<name>.json`` is a ratio of counter differences over the window,
``{"num": [...], "den": [...], "scale": s, "unit": u}``; a ``<name>.py``
defines ``UNIT`` and ``read(ctx)``.  A reader that finds nothing to read
returns None and the metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_DIR = os.path.join(HERE, "layer_metrics")


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; NaN for no values."""
    if not values:
        return math.nan
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def window(records) -> list:
    return [r for r in records if r.phase == "window"]


def tokens_in_window(records) -> int:
    """Output tokens the server had produced for the window's requests by
    the window's end: whole answers, and the part of each answer that the
    deadline cut.  Requests of the pre-roll are cut at the window's start
    and none of their tokens fall inside."""
    return sum(r.n_tokens for r in window(records) if r.status == 200)


def window_close(records) -> float:
    """When the window's last answer arrived.  The requests that the
    deadline cuts are answered when the server acknowledges the cut, at the
    end of whatever program was running at the deadline: up to a second and
    a half after it."""
    return max(r.t_done for r in window(records))


def out_tok_s(records, t_open: float) -> float:
    """Tokens the callers received for the window's requests over the time
    from the window's opening to its last answer.  The tokens are those
    delivered by the deadline, so the program in flight at the deadline is
    paid for and not counted (1-2% of a window, in every run alike).
    Dividing by the nominal length instead makes a staircase: all rows
    deliver a chunk at once, so the count moves in steps of rows x
    chunk_steps tokens (1.8% of a chat window), and a change of a thousandth
    in speed can move a step across the deadline."""
    return tokens_in_window(records) / (window_close(records) - t_open)


def latencies_ms(records) -> list[float]:
    """Send to whole answer, of the window's requests answered whole."""
    return [(r.t_done - r.t_send) * 1e3 for r in window(records) if r.complete]


def norm_latencies_ms(records) -> list[float]:
    """The same per output token."""
    return [(r.t_done - r.t_send) * 1e3 / r.n_tokens
            for r in window(records) if r.complete and r.n_tokens]


def attempted_failed(records) -> tuple[int, int]:
    w = window(records)
    return len(w), sum(r.failed for r in w)


END_TO_END = {
    "out_tok_s": lambda ctx: out_tok_s(ctx["records"], ctx["t_open"]),
    "setup_s": lambda ctx: ctx["setup_s"],
}


def counter_ratio(spec: dict, delta: dict[str, float]) -> float | None:
    """``scale * sum(num) / sum(den)`` over counter differences; a missing
    ``den`` means 1.  None where a counter is absent (unless the file gives
    the value an ``absent`` counter stands for: the registry exports a
    counter only once it has been incremented) or the ratio is 0/0."""
    names = spec["num"] + spec.get("den", [])
    if "absent" in spec:
        delta = {**dict.fromkeys(names, spec["absent"]), **delta}
    if any(n not in delta for n in names):
        return None
    num = sum(delta[n] for n in spec["num"])
    if "den" not in spec:
        return spec.get("scale", 1.0) * num
    den = sum(delta[n] for n in spec["den"])
    if den == 0:
        return None
    return spec.get("scale", 1.0) * num / den


def read_layer_metric(name: str, ctx: dict) -> tuple[float, str] | None:
    """(value, unit) of per-layer metric ``name``, or None."""
    base = os.path.join(LAYER_DIR, name)
    if os.path.exists(base + ".json"):
        with open(base + ".json") as f:
            spec = json.load(f)
        value = counter_ratio(spec, ctx["counters"])
        unit = spec["unit"]
    elif os.path.exists(base + ".py"):
        mod_spec = importlib.util.spec_from_file_location(
            "layer_metric_" + name.replace(".", "_").replace("-", "_"),
            base + ".py")
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        value, unit = mod.read(ctx), mod.UNIT
    else:
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}")
    if value is None or not math.isfinite(value):
        return None
    return float(value), unit
