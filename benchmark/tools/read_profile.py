#!/usr/bin/env python3
"""Any per-layer reader, listed in BENCHMARK.json or not, over the profile
that a cell's last traced run left under ``.bench_trace/<cell>``:

    python3 benchmark/tools/read_profile.py --workload CELL [--root DIR] NAME...

``BENCHMARK.json``'s ``per_layer`` is full (128 entries), so a reader that a
later PR brings is a file under ``benchmark/metrics/unlisted/`` with no entry
(``benchmark/metrics/`` itself holds a file for every entry and no other),
and a cell's line does not carry it.  This rebuilds ``run`` as far as a
profile holds it (``trace``, reduced anew as ``run.py`` does; ``cell``;
``peaks``, those of the device that the profile itself names) and prints one
JSON object, reader name to value: ``null`` where the reader finds nothing to
read, a string naming the key where it needs something only the live run had
(``decode_steps``, ``load``).  A name may be split as ``<quantity>.<cells>``;
with no NAME, the cell's listed per-layer metrics.  ``--root`` is the
checkout whose ``.bench_trace`` is read (the parent's, unpacked beside this
one); the readers are always this checkout's.  Run it where the traced run
ran: the profile is not copied back from the chip's machine.
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import replica, spec, trace_reduce   # noqa: E402

UNLISTED = os.path.join(spec.BENCH_DIR, "metrics", "unlisted")


def reader(name: str):
    """The reader of a metric, by its name or its stem as
    ``spec.metric_reader`` finds one: among the unlisted readers, else among
    the listed."""
    unlisted = {f[:-3] for f in os.listdir(UNLISTED) if f.endswith(".py")}
    if {name, name.split(".")[0]} & unlisted:
        return spec.metric_reader("unlisted/" + name)
    return spec.metric_reader(name)


def device_peaks(path: str) -> dict:
    """The published peaks of the device that made the profile: the
    ``device_type_string`` of the xplane's device planes, which the profiler
    spells 'TPU v5 Lite' where jax's ``device_kind`` (the key of
    ``peaks.json``) says 'TPU v5 lite': the letters' case apart, one name.
    A profile that names no device, or two, or one that ``peaks.json``
    lacks, is an error: no peak is assumed."""
    from jax.profiler import ProfileData
    said = {str(value) for plane in ProfileData.from_file(path).planes
            if re.fullmatch(r"/device:\w+:\d+", plane.name)
            for key, value in plane.stats if key == "device_type_string"}
    if len(said) != 1:
        raise SystemExit(f"{path}: its device planes name {sorted(said)} "
                         "as the device; one is needed")
    known = {kind.casefold(): kind for kind in spec.load_json("peaks.json")}
    kind, = said
    return spec.peaks_for(known.get(kind.casefold(), kind))


def rebuilt_run(workload: str, root: str) -> dict:
    """What ``run.py`` hands the readers, as far as the profile under
    ``root`` holds it."""
    cell = spec.load_cell(spec.load_benchmark(), workload)
    spec.ROOT = root          # where host_regions.profile looks
    path = replica.find_xplane(os.path.join(root, ".bench_trace", workload))
    return {"cell": cell, "peaks": device_peaks(path), "profile": path,
            "trace": trace_reduce.reduce_events(
                trace_reduce.read_xplane(path))}


def read_all(run: dict, names: list) -> dict:
    out = {}
    for name in names:
        try:
            out[name] = reader(name)(run)
        except KeyError as e:
            out[name] = f"needs run[{e.args[0]!r}], which a profile lacks"
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("names", nargs="*", metavar="NAME")
    args = parser.parse_args()
    names = args.names or [
        m["name"] for m in spec.metrics_of(
            spec.load_benchmark(), "per_layer", args.workload)]
    run = rebuilt_run(args.workload, os.path.abspath(args.root))
    print(json.dumps(read_all(run, names)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
