"""Window arithmetic: the end-to-end metrics from request timestamps.

A request is a :class:`Rec`: when it was due (open loop: its scheduled
send time), when it was admitted, and the time of every token it got.
Only what happens between the window's opening and its close counts.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field


@dataclass
class Rec:
    rid: int
    due: float
    admitted: float = math.nan
    token_times: list = field(default_factory=list)  # first token first


def percentile(xs, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(xs)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def due_in(recs, t0: float, t1: float):
    return [r for r in recs if t0 <= r.due <= t1]


def tokens_in(recs, t0: float, t1: float) -> int:
    """Tokens emitted inside [t0, t1], by any request."""
    return sum(1 for r in recs for t in r.token_times if t0 <= t <= t1)


def ttfts(recs, t0: float, t1: float) -> list:
    """Time to first token of every request due in the window, from its
    due time; one still waiting at the close enters at its wait so far."""
    out = []
    for r in due_in(recs, t0, t1):
        first = r.token_times[0] if r.token_times else math.inf
        out.append(min(first, t1) - r.due)
    return out


def tpots(recs, t0: float, t1: float) -> list:
    """Each request's mean gap between its tokens inside the window, for
    requests with at least two tokens there."""
    out = []
    for r in recs:
        ts = [t for t in r.token_times if t0 <= t <= t1]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def end_to_end(recs, t0: float, t1: float) -> dict:
    """tokens_per_s, ttft_p95_ms and tpot_p95_ms over the window."""
    return {
        "tokens_per_s": tokens_in(recs, t0, t1) / (t1 - t0),
        "ttft_p95_ms": percentile(ttfts(recs, t0, t1), 95) * 1e3,
        "tpot_p95_ms": percentile(tpots(recs, t0, t1), 95) * 1e3,
    }
