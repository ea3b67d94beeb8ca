"""The builder's tools: what ``spreads.py`` says of a metric's readings and
what ``sweep.py`` asks of a run and reads from its line."""

import importlib.util
import os

import pytest

from benchmark import spec


def tool(name):
    path = os.path.join(spec.BENCH_DIR, "tools", name + ".py")
    found = importlib.util.spec_from_file_location("bench_tool_" + name, path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


# my chip runs, PR 31: the tail on the 256 rung's plateau or the 512 rung's
PR31 = [[23.992, 25.647, 29.226, 28.886, 24.507, 24.109],
        [24.488, 24.116, 24.086, 23.866, 24.214, 28.955]]
STEADY = [[95.1, 95.2, 95.3, 95.25, 95.4, 95.0],
          [95.15, 95.2, 95.35, 95.25, 95.4, 95.05]]


@pytest.mark.parametrize("sets,bound,over_half,two_groups", [
    (PR31, "10.00%", True, True),
    (STEADY, "1.31%", False, False),
    # one run beside a stalled machine widens a set, and is no second group
    ([STEADY[0], STEADY[1][:5] + [99.0]], "5.84%", False, False),
])
def test_spreads_verdict(sets, bound, over_half, two_groups):
    said = tool("spreads").verdict("itl_p99_ms", sets)
    assert f"-> bound {bound}" in said
    assert ("OVER HALF THE BOUND" in said) == over_half
    assert ("TWO GROUPS" in said) == two_groups
    if two_groups:
        assert "9 readings up to 25.647, 3 from 28.886" in said


def test_spreads_bound_stays_inside_the_contract():
    spreads = tool("spreads")
    wide = [[100.0, 120.0, 140.0, 160.0], [100.0, 101.0, 102.0, 103.0]]
    assert "-> bound 10.00% OVER HALF" in spreads.verdict("m", wide)
    assert "-> bound 1.00%;" in spreads.verdict("m", [[7.0] * 6, [7.0] * 6])


@pytest.mark.parametrize("workload", [
    w["name"] for w in spec.load_benchmark()["workloads"]])
def test_sweep_lists_a_cells_metrics_under_both_kinds(workload):
    bench = spec.load_benchmark()
    before = {kind: {m["name"] for m in spec.metrics_of(bench, kind, workload)}
              for kind in ("end_to_end", "per_layer")}
    others = {w["name"]: {kind: [m["name"] for m in spec.metrics_of(
        bench, kind, w["name"])] for kind in ("end_to_end", "per_layer")}
        for w in bench["workloads"] if w["name"] != workload}
    tool("sweep").one_list(bench, workload)
    both = before["end_to_end"] | before["per_layer"]
    for kind in ("end_to_end", "per_layer"):
        listed = [m["name"] for m in spec.metrics_of(bench, kind, workload)]
        assert set(listed) == both and len(listed) == len(both)
    for name, kinds in others.items():   # no other cell reports more
        for kind, listed in kinds.items():
            assert [m["name"] for m in spec.metrics_of(
                bench, kind, name)] == listed


def test_sweep_reads_the_knee_from_a_line():
    line = {"correct": True, "attempted": 204, "failed": 0,
            "metrics": {"itl_p99_ms": {"value": 44.25, "unit": "ms"},
                        "ttft_p50_ms.chat": {"value": 31.5, "unit": "ms"},
                        "engine_waiting_mean": {"value": 0.25,
                                                "unit": "requests"},
                        "decode_device_ms.chat": {"value": 11.6,
                                                  "unit": "ms"}},
            "device": {"busy_s": 4.0, "window_s": 5.0},
            "load": {"offered": 204, "finished_in_window": 190}}
    said = tool("sweep").knee_reads(line)
    assert said == ("finished/offered=190/204 failed=0 itl_p99_ms=44.25 "
                    "ttft_p50_ms.chat=31.5 engine_waiting_mean=0.25 "
                    "idle_share=0.2000")
    untraced = {**line, "device": {}, "load": {}}
    assert "idle_share" not in tool("sweep").knee_reads(untraced)
