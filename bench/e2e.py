"""End-to-end arithmetic on host timelines: tails, time per output token and
window rates. Times are seconds on one host clock.

A request's timeline is its scheduled arrival and its deliveries: (time,
tokens) pairs, one per host drain that brought it tokens. Tokens reach the
host in blocks, so many tokens share one time.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Timeline:
    arrival: float
    deliveries: list = field(default_factory=list)   # [(t, n_tokens)]


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics (numpy's
    default)."""
    return float(np.percentile(np.asarray(values, float), q))


def ttft(timelines, t_open: float, t_close: float) -> list[float]:
    """Time to first token of every request due in [t_open, t_close), from
    its scheduled arrival. A request with no token by t_close enters with
    its wait so far."""
    out = []
    for tl in timelines:
        if not t_open <= tl.arrival < t_close:
            continue
        first = tl.deliveries[0][0] if tl.deliveries else t_close
        out.append(min(first, t_close) - tl.arrival)
    return out


def tpot(timelines, t_open: float, t_close: float) -> list[float]:
    """Time per output token of each request, from the deliveries inside
    the window: (last delivery - first delivery) / tokens delivered after
    the first one. A request needs two deliveries in the window to count.
    The block size alone does not change it: a block of n tokens adds n to
    the count and one block's time to the span."""
    out = []
    for tl in timelines:
        inside = [(t, n) for t, n in tl.deliveries if t_open <= t <= t_close]
        if len(inside) < 2:
            continue
        after = sum(n for _, n in inside[1:])
        out.append((inside[-1][0] - inside[0][0]) / after)
    return out


def tokens_in(timelines, t_open: float, t_close: float) -> int:
    return sum(n for tl in timelines for t, n in tl.deliveries
               if t_open <= t <= t_close)
