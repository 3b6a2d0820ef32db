"""Order statistics the benchmark reports."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile (the smallest value with at least q%
    of the sample at or below it).  A missing sample is passed as
    math.inf and ranks above every measured one."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[rank - 1]
