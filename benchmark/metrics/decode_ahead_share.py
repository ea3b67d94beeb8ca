"""How often the engine's loop ran ahead of its own tokens: decode steps
dispatched while the step before them was still in flight (``ahead == 1`` on
``rt:engine.decode.dispatch``) over the window's decode steps.  The rest were
dispatched on a drained pipe (behind an admission, after the last token of a
batch), where the device waits for the per-token host round trip.  ``None``
where no dispatch carries ``ahead``: the program before PR 38."""

from benchmark import host_regions


def read(run):
    found = [r["ahead"] for r in host_regions.rows(
        run, "engine.decode.dispatch") or [] if "ahead" in r]
    return 100.0 * sum(found) / len(found) if found else None
