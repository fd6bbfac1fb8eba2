"""Clocks and percentiles.  One clock for everything the host times:
`time.perf_counter_ns`, which is also what the program's StageClock and
FRAME_TRACE stamps use, so stamps from both sides subtract."""

from __future__ import annotations

import time
from typing import Sequence

now_ns = time.perf_counter_ns


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks: over ALL the samples given, never a trimmed set."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Sequence[float]) -> float:
    xs = list(values)
    return sum(xs) / len(xs)
