"""Order statistics the benchmark reports. Kept here so that no later PR can change
how a percentile is taken.

`pctl` is a copy of `bench_load.py`'s `_pctl` (nearest rank on the sorted values);
the original is listed in PERF.md for deletion with the old bench scripts.
"""

from __future__ import annotations

import statistics
from typing import Optional, Sequence


def pctl(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, q in [0, 1]; None where there is nothing to rank."""
    xs = sorted(values)
    if not xs:
        return None
    idx = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[idx]


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None
