"""The arithmetic of the end-to-end metrics, apart from any run."""

from __future__ import annotations

import statistics
from typing import Iterable, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the contract's measure of how far runs of one code differ."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def two_groups(values: Sequence[float]) -> tuple:
    """The largest gap between neighbouring sorted readings as a share of
    the median, and how many readings lie below it: far above the spread
    where the readings are of two kinds, as when a percentile sits on the
    step between two plateaus."""
    ordered = sorted(values)
    below = max(range(1, len(ordered)),
                key=lambda i: ordered[i] - ordered[i - 1])
    return ((ordered[below] - ordered[below - 1])
            / statistics.median(ordered), below)


def finished_by(requests: Iterable[dict], end: float) -> int:
    """Requests whose last token had arrived at ``end``, none missing."""
    return sum(1 for r in requests
               if not r["error"] and len(r["arrivals"]) == r["asked"]
               and r["arrivals"][-1] <= end)


def ttfts_ms(requests: Iterable[dict], since: str = "due") -> list:
    """First token's arrival minus the time the request was due (or
    ``sent``), for every request that got a token."""
    return [(r["arrivals"][0] - r[since]) * 1e3
            for r in requests if r["arrivals"]]


def token_gaps_ms(requests: Iterable[dict]) -> list:
    """Every gap between consecutive streamed tokens of every request."""
    return [(b - a) * 1e3 for r in requests
            for a, b in zip(r["arrivals"], r["arrivals"][1:])]


def served_tokens(requests: Iterable[dict], start: float, end: float
                  ) -> int:
    """Tokens served in [start, end]: a prompt's tokens count when its
    first output token arrives, an output token when it arrives.  A
    request still in flight at ``end`` counts as far as it got."""
    total = 0
    for r in requests:
        inside = [t for t in r["arrivals"] if start <= t <= end]
        total += len(inside)
        if r["arrivals"] and start <= r["arrivals"][0] <= end:
            total += r["prompt_tokens"]
    return total


def live_kv_tokens_per_step(requests: Iterable[dict], steps: int
                            ) -> Optional[float]:
    """Mean number of cached positions one decode step reads: each
    output token after a request's first came from a step that read that
    request's whole context."""
    if steps <= 0:
        return None
    read = sum(r["prompt_tokens"] + i
               for r in requests for i in range(1, len(r["arrivals"])))
    return read / steps
