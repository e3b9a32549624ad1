"""Percentiles, rates and the window arithmetic every metric shares."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (linear between order statistics)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_window(t: float, lo: float, hi: float) -> bool:
    """Half-open window [lo, hi)."""
    return lo <= t < hi


def rate(count: float, seconds: float) -> float:
    return count / seconds


def per_token(spans) -> float:
    """Seconds per token pooled over ``spans`` [(seconds, tokens)]: all the
    time over all the tokens, so long requests weigh by their length."""
    secs = sum(s for s, _ in spans)
    return secs / max(sum(n for _, n in spans), 1)
