#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts a cluster with ``ray_tpu.init()``, lets the cell's generator drive
the system through its public entry points, and prints as the last line
of stdout one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, a serve cell's ``load`` (requests offered
and finished inside the window), traced ``breakdown``, and last ``checks``:
each number that ``correct`` compared, beside its limit.

This process never initialises a JAX backend: the chips belong to the
worker that leased them, and the device is what that worker reports.
Without a TPU, or on a device kind that ``peaks.json`` does not list, it
exits non-zero and prints no result.
"""

import time

T0 = time.time()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
# Workers are started cold, as those that lease a chip always are.  The
# raylet's fork server tells whether a forked worker lives by the start time
# in /proc/<pid>/stat, which in the first seconds of a sandbox's life reads
# differently from one look to the next: the first runs on a fresh machine
# then lose their serve controller (PR 23, PERF.md Open questions).
os.environ.setdefault("RT_DISABLE_FORKSERVER", "1")
# the cluster's workers import ``benchmark`` and ``ray_tpu`` from here too
os.environ["PYTHONPATH"] = os.pathsep.join(
    [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
              if p])


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args()

    from benchmark import spec, trace_reduce
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, args.workload)
    generator = spec.load_part("generators", cell["traffic"]["generator"])
    kind = "per_layer" if args.trace else "end_to_end"
    readers = {m["name"]: (m["unit"], spec.metric_reader(m["name"]))
               for m in spec.metrics_of(bench, kind, cell["name"])}

    import ray_tpu
    from ray_tpu._private import jaxutil
    ray_tpu.init()
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        if advertised < cell["chips"]:
            raise SystemExit(f"{cell['name']} needs {cell['chips']} TPU "
                             f"chip(s), the cluster advertises "
                             f"{advertised:g}")
        run = generator.run({"cell": cell, "seed": args.seed,
                             "seconds": args.seconds,
                             "trace": bool(args.trace)})
    finally:
        ray_tpu.shutdown()
    if jaxutil.initialized_backends():
        raise SystemExit("the benchmark's own process initialised a JAX "
                         "backend")

    if run.get("profile"):
        # A serve cell hands back where its replica's profile lies, and it
        # is reduced here, with the cluster gone: the replica has to answer
        # the controller's pings, and this takes tens of seconds.
        started = time.perf_counter()
        run["trace"] = trace_reduce.reduce_events(
            trace_reduce.read_xplane(run["profile"]))
        run["phases"]["reduce_profile_s"] = time.perf_counter() - started

    device = dict(run["device"])
    peaks = spec.peaks_for(device["kind"])
    if device["platform"] != peaks["platform"] or \
            device["count"] != cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} "
                         f"{peaks['platform']} chip(s); the worker that "
                         f"leased them found {device}")
    run.update(cell=cell, peaks=peaks,
               setup_s=run["window_start_epoch"] - T0)
    metrics = {}
    for name, (unit, read) in readers.items():
        value = read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    line = {"correct": run["correct"], "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    if run.get("load"):
        line["load"] = run["load"]
    if args.trace and run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = trace_reduce.breakdown(run["trace"])
    line["checks"] = run["checks"]
    for key in ("numerics", "loss_check", "errors", "phases", "gaps_ms",
                "checks"):
        if run.get(key):
            print(json.dumps({key: run[key]}), file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
