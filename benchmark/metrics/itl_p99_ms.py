"""99th percentile of the gap between consecutive streamed tokens, over
all gaps of all requests, at the client."""

from benchmark import stats


def read(run):
    gaps = stats.token_gaps_ms(run["requests"])
    return stats.percentile(gaps, 99) if gaps else None
