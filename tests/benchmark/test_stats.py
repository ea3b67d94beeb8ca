"""The arithmetic of the end-to-end metrics."""

import pytest

from benchmark import stats


def request(prompt, due, sent, arrivals):
    return {"prompt_tokens": prompt, "due": due, "sent": sent,
            "arrivals": arrivals}


def test_percentile():
    values = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(values, 0) == 1.0
    assert stats.percentile(values, 50) == 3.0
    assert stats.percentile(values, 100) == 5.0
    assert stats.percentile(values, 25) == 2.0
    assert stats.percentile([1.0, 2.0], 99) == pytest.approx(1.99)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_the_contracts():
    import statistics
    values = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == (q3 - q1) / statistics.median(values)


def test_latency_counts_from_the_due_time():
    late = request(10, due=1.0, sent=1.5, arrivals=[2.0, 2.1, 2.3])
    assert stats.ttfts_ms([late]) == [pytest.approx(1000.0)]
    assert stats.ttfts_ms([late], since="sent") == [pytest.approx(500.0)]
    assert stats.token_gaps_ms([late]) == [pytest.approx(100.0),
                                           pytest.approx(200.0)]
    assert stats.ttfts_ms([request(10, 1.0, 1.0, [])]) == []


def test_served_tokens_counts_what_is_in_flight():
    requests = [request(100, 0, 0, [1.0, 2.0, 3.0]),        # done inside
                request(200, 0, 0, [4.0, 5.0, 11.0, 12.0]),  # cut by the end
                request(300, 0, 0, [10.5, 11.5]),            # first is late
                request(50, 0, 0, [-1.0, 0.5])]              # began before
    # the second request counts its prompt and two of its four outputs, the
    # third nothing, the fourth the one output inside
    assert stats.served_tokens(requests, 0.0, 10.0) == 103 + 202 + 0 + 1


def test_served_tokens_moves_by_tokens_not_by_requests():
    requests = [request(64, 0, 0, [1.0 + 0.1 * i for i in range(50)])]
    counts = [stats.served_tokens(requests, 0.0, 1.05 + 0.1 * i)
              for i in range(49)]
    assert [b - a for a, b in zip(counts, counts[1:])] == [1] * 48


def test_live_kv_tokens_per_step():
    requests = [request(10, 0, 0, [1.0, 2.0, 3.0]),
                request(20, 0, 0, [1.0])]
    # two decode steps read contexts of 11 and 12 positions
    assert stats.live_kv_tokens_per_step(requests, 2) == 11.5
    assert stats.live_kv_tokens_per_step(requests, 0) is None


@pytest.mark.parametrize("values,gap,below", [
    # PR 31's readings of chat's tail lay on two plateaus
    ([23.992, 24.109, 24.507, 25.647, 28.886, 29.226], 28.886 - 25.647, 4),
    ([100.0, 100.5, 101.0, 101.6], 0.6, 3),
])
def test_two_groups_is_the_largest_gap_over_the_median(values, gap, below):
    import statistics
    share, cut = stats.two_groups(values[::-1])
    assert share == pytest.approx(gap / statistics.median(values))
    assert cut == below


def test_finished_by_counts_whole_answers_inside():
    def asked(n, arrivals, error=None):
        return {"asked": n, "arrivals": arrivals, "error": error}
    requests = [asked(2, [1.0, 2.0]),            # whole and inside
                asked(2, [1.0, 12.0]),           # its last token is late
                asked(3, [1.0, 2.0]),            # a token short
                asked(2, [1.0, 2.0], "closed")]  # failed
    assert stats.finished_by(requests, 10.0) == 1
    assert stats.finished_by(requests, 12.0) == 2
