"""Mean gap between consecutive streamed tokens, over all gaps of all
requests, at the client: the time per output token that a reader of a
stream feels as its speed.  A sum over every gap of the window, where
``itl_p99_ms`` is one rank of them."""

from benchmark import stats


def read(run):
    gaps = stats.token_gaps_ms(run["requests"])
    return sum(gaps) / len(gaps) if gaps else None
