"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Times are seconds from the window's start on the host clock; a request is
in the window when it is due before the window closes.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts_s(run) -> List[float]:
    """First-token time minus due time of every request due in the window.
    A request still without a first token counts with the time it waited
    until the run gave up on it."""
    end = run.seconds + run.counters.get("ttft_grace_s", 0.0)
    out = []
    for r in run.served:
        if r.due >= run.seconds:
            continue
        out.append((r.token_times[0] if r.token_times else end) - r.due)
    return out


def token_gaps_s(run) -> List[float]:
    """Every gap between consecutive output tokens inside the window."""
    gaps = []
    for r in run.served:
        t = [x for x in r.token_times if 0.0 <= x <= run.seconds]
        gaps.extend(np.diff(t).tolist())
    return gaps


def kernel_seconds(run, prefixes) -> Optional[float]:
    """Device seconds of the traced kernels whose names start with any of
    ``prefixes``, in whatever program they ran; None when the trace holds
    none."""
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.kernels.items()
            if k.startswith(tuple(prefixes)))
    return t if t > 0 else None


def share(bound: float, seconds: Optional[float]) -> Optional[float]:
    if seconds is None or bound <= 0:
        return None
    return 100.0 * bound / seconds
