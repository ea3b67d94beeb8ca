"""Where everything that BENCHMARK.json names is found.

A workload names a configuration and a traffic mix; those, and every
generator, family and metric, are files of their own under this
directory, found by name.  A later PR adds files and entries and edits
nothing here.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str) -> dict:
    """The workload's entry with its configuration and traffic read in."""
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        raise SystemExit(f"BENCHMARK.json has {len(entries)} workloads "
                         f"named {workload!r}")
    cell = dict(entries[0])
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        cell["config"] = {"name": cell["config"], **json.load(f)}
    cell["traffic"] = {"name": cell["traffic"],
                       **load_json("traffic", cell["traffic"] + ".json")}
    return cell


def metrics_of(bench: dict, kind: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this workload reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def load_part(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, importable in a worker as well."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def metric_reader(name: str):
    """The reader of a metric: ``metrics/<name>.py`` or, for a quantity
    split by cells as ``<quantity>.<cells>``, ``metrics/<quantity>.py``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "benchmark_metric_" + re.sub(r"\W", "_", stem), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    raise SystemExit(f"no reader for metric {name!r} under "
                     f"{os.path.join(BENCH_DIR, 'metrics')}")


def peaks_for(kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error,
    never a default."""
    table = load_json("peaks.json")
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in benchmark/"
                         f"peaks.json ({sorted(table)}): add it with its "
                         "source")
    return table[kind]
