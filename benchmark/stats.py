"""Metric arithmetic on plain samples, so that it can be checked on hand-made
ones.  Times are seconds on one clock (``time.perf_counter``); a window is
``(t0, t1)`` and holds ``t0 <= t < t1``.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Window = Tuple[float, float]


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default); None for no samples."""
    xs = sorted(samples)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t: float, window: Window) -> bool:
    return window[0] <= t < window[1]


def emitted_tokens(token_times: Iterable[Sequence[float]], window: Window) -> int:
    """Tokens emitted inside the window, whether or not their request
    completes inside it."""
    return sum(1 for times in token_times for t in times if in_window(t, window))


def token_gaps(token_times: Iterable[Sequence[float]], window: Window) -> List[float]:
    """Gaps between consecutive tokens of one request, pooled over requests;
    a gap belongs to the window when the token that closes it was emitted
    inside, so a request in flight at either edge gives what it has."""
    gaps: List[float] = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if in_window(b, window):
                gaps.append(b - a)
    return gaps


def first_token_delays(requests: Iterable[Dict], window: Window) -> Tuple[List[float], int]:
    """(samples, failed) over the requests *due* inside the window: a sample
    is first token - due instant; a request that was shed, failed or never
    answered counts as failed, not as a sample.  ``requests`` have ``due``,
    ``token_times`` and ``status``."""
    samples: List[float] = []
    failed = 0
    for r in requests:
        if not in_window(r["due"], window):
            continue
        if r["token_times"] and r["status"] not in ("shed", "failed"):
            samples.append(r["token_times"][0] - r["due"])
        else:
            failed += 1
    return samples, failed


def steps_per_s(step_ends: Sequence[float], start: float) -> Optional[float]:
    """The rate of the steps that began inside a window that opened at
    ``start``: their count over the time from ``start`` to the end of the last
    one.  Continuous (a count over a fixed time moves in whole steps), and
    still all the work over all the time: a stall anywhere lengthens it."""
    if not step_ends or step_ends[-1] <= start:
        return None
    return len(step_ends) / (step_ends[-1] - start)


def ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else x * 1e3
