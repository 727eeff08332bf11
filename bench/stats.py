"""Order statistics of the benchmark, kept with it so that no PR can change
how a tail is computed."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (``numpy.percentile`` semantics);
    ``[] -> nan``, ``[x] -> x``."""
    xs = sorted(float(x) for x in xs)
    if not xs:
        return math.nan
    rank = (len(xs) - 1) * (q / 100.0)
    lo, hi = math.floor(rank), math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)
