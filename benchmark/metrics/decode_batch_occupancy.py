"""Mean of the engine's stats()["active"] over max_batch, polled at 10 Hz."""


def read(run):
    polls = run["replica"]["polls"]
    if not polls:
        return None
    return 100.0 * sum(a for a, _ in polls) / len(polls) \
        / run["replica"]["max_batch"]
