"""Median, over the window's requests, of the first token's arrival minus
the time the request was due."""

from benchmark import stats


def read(run):
    ttfts = stats.ttfts_ms(run["requests"])
    return stats.percentile(ttfts, 50) if ttfts else None
