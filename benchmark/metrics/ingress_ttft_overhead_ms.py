"""What the ingress, the router and the hops add to the first token:
median of the client's time from send to first token minus the replica's
own time from the request's entry to its first yield."""

from benchmark import stats


def read(run):
    inside = run["replica"]["replica_ttft_s"]
    extra = [(r["arrivals"][0] - r["sent"] - inside[r["id"]]) * 1e3
             for r in run["requests"]
             if r["arrivals"] and r["id"] in inside]
    return stats.percentile(extra, 50) if extra else None
