#!/usr/bin/env python3
"""Run one cell with one number of its traffic mix set to each of several
values: how PR 23 found the rate that ``traffic/chat-steady.json`` freezes.

    python3 benchmark/tools/sweep.py serve-chat-steady rate_per_s 1.0,1.4,1.8 \\
        --seconds 30 [--runs 2] [--trace 0]

Each value gets a copy of BENCHMARK.json and ``benchmark/`` under
``.bench_sweep/`` (git-ignored) with the one number changed, and the
copy's own ``run.py`` is run: nothing committed changes.  Prints each
run's last line; stderr of the runs goes to ``chiprun_out/``.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workload")
    parser.add_argument("key")
    parser.add_argument("values")
    parser.add_argument("--seconds", type=float, default=30)
    parser.add_argument("--runs", type=int, default=1)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 23)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    traffic = next(w["traffic"] for w in bench["workloads"]
                   if w["name"] == args.workload)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for value in args.values.split(","):
        copy = os.path.join(ROOT, ".bench_sweep", value)
        shutil.rmtree(copy, ignore_errors=True)
        shutil.copytree(os.path.join(ROOT, "benchmark"),
                        os.path.join(copy, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
        path = os.path.join(copy, "benchmark", "traffic", traffic + ".json")
        with open(path) as f:
            mix = json.load(f)
        mix[args.key] = type(mix[args.key])(value)
        with open(path, "w") as f:
            json.dump(mix, f)
        for run in range(args.runs):
            tag = f"sweep_{args.workload}_{args.key}_{value}_{run}"
            with open(os.path.join(ROOT, "chiprun_out", tag + ".err"),
                      "w") as err:
                done = subprocess.run(
                    [sys.executable,
                     os.path.join(copy, "benchmark", "run.py"),
                     "--workload", args.workload,
                     "--seed", str(args.seed + run),
                     "--seconds", str(args.seconds),
                     "--trace", str(args.trace)],
                    cwd=copy, stdout=subprocess.PIPE, stderr=err, text=True,
                    env={**os.environ, "PYTHONPATH": ROOT})
            last = (done.stdout.strip().splitlines() or ["no result"])[-1]
            print(f"{args.key}={value} run={run} rc={done.returncode} "
                  f"{last}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
