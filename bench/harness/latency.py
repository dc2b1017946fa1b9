"""Latency statistics over the requests of a window.

Every request submitted in the window (not in the pre-roll) counts, however late its answer
came; one that failed, was shed or never came lies beyond every limit.
Percentiles are nearest-rank over that whole sample.
"""
from __future__ import annotations

import math
from typing import List


def latencies_ms(run) -> List[float]:
    """Each request's latency in ms, ``inf`` where it got no answer."""
    return sorted(math.inf if r.error is not None or r.done is None
                  else r.latency * 1e3 for r in run.in_window)


def percentile_ms(run, q: float):
    """Nearest-rank ``q``-th percentile of the window's latencies, or
    ``None`` with no request; a percentile that lands on a request
    without an answer reads as the longest wait seen."""
    vals = latencies_ms(run)
    if not vals:
        return None
    value = vals[max(math.ceil(q / 100.0 * len(vals)) - 1, 0)]
    if math.isinf(value):
        value = max((r.done or run.window[1]) - r.submitted
                    for r in run.in_window) * 1e3
    return value

