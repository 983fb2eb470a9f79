"""A percentile over the WINDOW from a histogram the server exports by
cumulative counts: ``<series>_le_us_<edge in whole microseconds>`` is how
many observations were no longer than the edge, a label-free line an edge
beside ``<series>_count``, neither ever reset, so the difference of two
scrapes (``ctx["counters"]``) is the window's distribution.  The edges are
read from the names: no copy of the server's ladder lives here.

The q-th value lies in the first bucket whose cumulative count reaches
``q x count``; inside it the observations are taken as evenly spread
(linear between the bucket's edges; the first bucket starts at 0): good
to a few percent where the values spread, and to half a bucket's width
where they are all alike, which reads as the bucket's middle.  What lies
over the top edge is counted by ``_count`` alone: a percentile that falls
there reads the top edge, a floor.  None for a program without the series,
or a window without an observation.
"""

from __future__ import annotations

import re


def window_buckets(counters: dict[str, float],
                   series: str) -> list[tuple[int, float]]:
    """(edge in microseconds, observations <= edge) in ascending order."""
    name = re.compile(re.escape(series) + r"_le_us_(\d+)")
    return sorted((int(m.group(1)), v) for k, v in counters.items()
                  if (m := name.fullmatch(k)))


def quantile_ms(counters: dict[str, float], series: str,
                q: float) -> float | None:
    """The ``q``-quantile (0..1) of ``series`` over the window, in ms."""
    buckets = window_buckets(counters, series)
    total = counters.get(series + "_count", 0.0)
    if not buckets or total <= 0:
        return None
    want = q * total
    below_us, below = 0.0, 0.0
    for edge_us, upto in buckets:
        if upto >= want:
            inside = upto - below
            share = (want - below) / inside if inside else 1.0
            return (below_us + (edge_us - below_us) * share) / 1e3
        below_us, below = edge_us, upto
    return buckets[-1][0] / 1e3
