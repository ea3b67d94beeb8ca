"""Prompt and output tokens served inside the window, per second."""

from benchmark import stats


def read(run):
    start, end = run["window"]
    return stats.served_tokens(run["requests"], start, end) / (end - start)
