"""Deciding ``correct``: what every deployment family's check shares.

A family (``bench/families/<family>.py``) compares what the timed window
produced with its plain reference, number by number, and gives each
number's limit in its ``LIMITS``; ``check_lines`` sets the numbers beside
their limits for the result and standard error.
"""
from __future__ import annotations

import numpy as np

HUGE = 1e300   # a gap that cannot be measured (an answer missing or infinite)


def _rel(prog, ref) -> np.ndarray:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    both_inf = np.isinf(prog) & np.isinf(ref)
    with np.errstate(invalid="ignore"):
        gap = np.abs(prog - ref) / np.maximum(np.abs(ref), 1.0)
    gap = np.where(both_inf, 0.0, gap)
    return np.where(np.isfinite(gap), gap, HUGE)


def _events_off(n, lo, slack) -> int:
    return int(max(lo - n, 0) + max(n - (lo + slack), 0))


def check_lines(worst: dict, limits: dict) -> dict:
    """``{number: {"value": v, "limit": l}}`` in the order of ``worst``."""
    return {k: {"value": worst[k], "limit": limits[k]} for k in worst}
