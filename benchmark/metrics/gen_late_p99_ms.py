"""How late the load generator sent: send time minus due time."""

from benchmark import stats


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run["requests"]]
    return stats.percentile(late, 99) if late else None
