"""Mean of the engine's stats()["waiting"], polled at 10 Hz."""


def read(run):
    polls = run["replica"]["polls"]
    return sum(w for _, w in polls) / len(polls) if polls else None
