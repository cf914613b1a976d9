"""Exact statistics over one window's raw samples."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest sample with at least q% of the
    samples at or below it.  Always a value that was measured."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(math.ceil(q / 100.0 * len(xs)), 1)
    return float(xs[rank - 1])


@dataclass
class Sent:
    """One request sent in the window, on the engine's clock."""

    rid: int
    cls: str
    due: float
    prompt_len: int
    max_new_tokens: int
    dispatched: Optional[float] = None
    first_token: Optional[float] = None
    finished: Optional[float] = None


def censored(start: float, end: Optional[float], close: float) -> float:
    """Seconds from ``start`` to ``end``; a request whose ``end`` had not
    come when the window closed counts at its wait so far."""
    if end is None or end > close:
        return close - start
    return end - start


def ttfts(sent: list[Sent], close: float, cls: Optional[str] = None):
    return [censored(s.due, s.first_token, close) for s in sent
            if cls is None or s.cls == cls]


def queue_waits(sent: list[Sent], close: float, cls: Optional[str] = None):
    return [censored(s.due, s.dispatched, close) for s in sent
            if cls is None or s.cls == cls]
