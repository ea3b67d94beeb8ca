#!/usr/bin/env python3
"""Run a cell as the contract asks before a bound is set: sets of runs with
the same seeds in every set, each run its own process, and for each
end-to-end metric each set's spread (distance between the quartiles over
the median), the bound that five times the widest gives, whether a set
spreads over half of that bound, and whether the readings fall in two
separated groups (the largest gap between sorted readings over the median).

    python3 benchmark/tools/spreads.py serve-chat-steady [--sets 2] [--runs 6]

Every run's last line is appended to ``chiprun_out/<workload>.runs.jsonl``.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stats   # noqa: E402


def verdict(name: str, sets: list) -> str:
    """One metric's medians and spreads, the bound that five times the
    widest gives inside the contract's 1% to 10%, and what makes a bound
    unfit: a set that spreads over half of it, or readings of two kinds."""
    spreads = [stats.spread(v) for v in sets if len(v) >= 2]
    medians = [statistics.median(v) for v in sets if v]
    bound = min(10.0, max(1.0, 500 * max(spreads, default=0)))
    out = (f"{name}: medians {[round(m, 4) for m in medians]} spreads "
           f"{[round(100 * x, 3) for x in spreads]}% -> bound {bound:.2f}%")
    if any(200 * x > bound for x in spreads):
        out += " OVER HALF THE BOUND: no admissible bound holds this cell"
    pooled = sorted(v for one in sets for v in one)
    if len(pooled) >= 4:
        gap, cut = stats.two_groups(pooled)
        out += f"; largest gap between sorted readings {100 * gap:.3f}%"
        # one far-off run is a stalled machine's; two on each side of a gap
        # wider than the admitted spread are a step between two plateaus
        if 200 * gap > bound and 2 <= cut <= len(pooled) - 2:
            out += (f" TWO GROUPS: {cut} readings up to {pooled[cut - 1]:.6g}"
                    f", {len(pooled) - cut} from {pooled[cut]:.6g}")
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workload")
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 2300)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", args.workload + ".runs.jsonl")
    values = {}    # metric -> one list per set
    for s in range(args.sets):
        for r in range(args.runs):
            done = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", args.workload,
                 "--seed", str(args.seed + 7919 * r),
                 "--seconds", str(seconds), "--trace", "0"],
                cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            lines = done.stdout.strip().splitlines()
            if done.returncode or not lines:
                print(f"set {s} run {r}: rc={done.returncode}\n"
                      f"{done.stderr[-2000:]}", flush=True)
                continue
            line = json.loads(lines[-1])
            notes = [json.loads(x) for x in done.stderr.splitlines()
                     if x.startswith('{"')]
            with open(log, "a") as f:
                f.write(json.dumps({"set": s, "run": r, **line,
                                    "stderr": notes}) + "\n")
            print(f"set {s} run {r}: correct={line['correct']} "
                  f"failed={line['failed']}/{line['attempted']} " + " ".join(
                      f"{k}={v['value']:.6g}"
                      for k, v in line["metrics"].items()), flush=True)
            for name, metric in line["metrics"].items():
                values.setdefault(name, [[] for _ in range(args.sets)])[
                    s].append(metric["value"])
    for name, sets in values.items():
        # the first run of the first set is the one that may compile
        if name == "setup_s":
            sets = [sets[0][1:]] + sets[1:]
        print(verdict(name, sets), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
