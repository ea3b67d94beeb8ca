#!/usr/bin/env python3
"""Run one cell with one number of its traffic mix set to each of several
values: how the rate that a traffic file freezes is found.

    python3 benchmark/tools/sweep.py serve-chat-steady rate_per_s 2.5,3.5,4.5 \\
        --seconds 51 [--runs 2] [--trace 0,1]

Each value gets a copy of BENCHMARK.json and ``benchmark/`` under
``.bench_sweep/`` (git-ignored) with the one number changed, and the
copy's own ``run.py`` is run: nothing committed changes.  In the copy the
cell's end-to-end and per-layer metrics are one list, so that every run's
line carries whatever its readers find (a traced run the judged tail beside
``engine_waiting_mean``; an untraced one ``gen_late_p99_ms`` too).
``--trace`` is taken in turn by a value's runs.  Prints each run's last
line and, after it, what a knee is read from: requests finished in the
window over requests offered, failures, the queue for slots, how late the
generator sent, the tails and the device's idle share.  stderr of the runs
goes to ``chiprun_out/``.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KNEE_READS = ("itl_mean_ms", "itl_p99_ms", "engine_waiting_mean",
              "gen_late_p99_ms", "ttft_p50_ms", "served_tokens_per_s")


def one_list(bench: dict, workload: str) -> None:
    """Every metric the cell reports, under both kinds."""
    mine = {m["name"]: m for kind in ("end_to_end", "per_layer")
            for m in bench[kind]
            if workload in m.get("workloads", [workload])}
    for kind in ("end_to_end", "per_layer"):
        there = {m["name"] for m in bench[kind]}
        bench[kind] += [{**m, "workloads": [workload]}
                        for name, m in mine.items() if name not in there]


def knee_reads(line: dict) -> str:
    """The numbers of one result line that say whether the rate was
    sustained."""
    load = line.get("load", {})
    out = [f"finished/offered={load.get('finished_in_window')}/"
           f"{load.get('offered')}", f"failed={line['failed']}"]
    for name, metric in line["metrics"].items():
        if name.split(".")[0] in KNEE_READS:
            out.append(f"{name}={metric['value']:.6g}")
    device = line["device"]
    if device.get("window_s"):
        out.append(f"idle_share={1 - device['busy_s'] / device['window_s']:.4f}")
    return " ".join(out)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workload")
    parser.add_argument("key")
    parser.add_argument("values")
    parser.add_argument("--seconds", type=float, default=30)
    parser.add_argument("--runs", type=int, default=1)
    parser.add_argument("--trace", default="0")
    parser.add_argument("--seed", type=int, default=2 ** 31 + 23)
    args = parser.parse_args()
    traces = args.trace.split(",")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    traffic = next(w["traffic"] for w in bench["workloads"]
                   if w["name"] == args.workload)
    one_list(bench, args.workload)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for value in args.values.split(","):
        copy = os.path.join(ROOT, ".bench_sweep", value)
        shutil.rmtree(copy, ignore_errors=True)
        shutil.copytree(os.path.join(ROOT, "benchmark"),
                        os.path.join(copy, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        path = os.path.join(copy, "benchmark", "traffic", traffic + ".json")
        with open(path) as f:
            mix = json.load(f)
        mix[args.key] = type(mix[args.key])(value)
        with open(path, "w") as f:
            json.dump(mix, f)
        for run in range(args.runs):
            trace = traces[run % len(traces)]
            tag = f"sweep_{args.workload}_{args.key}_{value}_{run}"
            with open(os.path.join(ROOT, "chiprun_out", tag + ".err"),
                      "w") as err:
                done = subprocess.run(
                    [sys.executable,
                     os.path.join(copy, "benchmark", "run.py"),
                     "--workload", args.workload,
                     "--seed", str(args.seed + run),
                     "--seconds", str(args.seconds),
                     "--trace", trace],
                    cwd=copy, stdout=subprocess.PIPE, stderr=err, text=True,
                    env={**os.environ, "PYTHONPATH": os.pathsep.join(
                        [ROOT, os.environ.get("PYTHONPATH", "")])})
            last = (done.stdout.strip().splitlines() or ["no result"])[-1]
            print(f"{args.key}={value} run={run} rc={done.returncode} "
                  f"{last}", flush=True)
            if not done.returncode and last.startswith("{"):
                print(f"{args.key}={value} run={run} trace={trace} "
                      f"{knee_reads(json.loads(last))}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
