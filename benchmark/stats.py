"""Percentiles and lateness. Copied in spirit from bench.py::_pctl (nearest
rank on the sorted sample), with the sample-count rule of the
choosing-metrics guide: a percentile stands only with at least ten samples
beyond it."""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence


def pctl(xs: Sequence[float], p: float) -> Optional[float]:
    if not xs:
        return None
    s = sorted(xs)
    k = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
    return s[k]


def samples_needed(p: float, beyond: int = 10) -> int:
    """Smallest sample in which `beyond` values lie above percentile p."""
    return int(math.ceil(beyond / (1.0 - p / 100.0)))


def supported(xs: Sequence[float], p: float) -> bool:
    return len(xs) >= samples_needed(p)


def summary(xs: Sequence[float], p: float = 95.0) -> Dict[str, object]:
    return {"n": len(xs), "median": pctl(xs, 50), f"p{p:g}": pctl(xs, p),
            "supported": supported(xs, p)}
