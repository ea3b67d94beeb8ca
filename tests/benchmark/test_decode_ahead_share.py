"""``decode_ahead_share``: the share of the window's decode steps that the
engine dispatched behind a step still in flight (``ahead`` on
``rt:engine.decode.dispatch``, PR 38), read by ``benchmark/metrics/
decode_ahead_share.py`` for chat and, as ``.decode``, for the four
``served_tokens_per_s`` cells."""

import os
import sys

import pytest

from benchmark import host_regions as hr
from benchmark import spec

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))     # tests/engine_trace.py

NAMES = ("decode_ahead_share", "decode_ahead_share.decode")
D = "rt:engine.decode.dispatch"


def run_of(name, trace=None):
    bench = spec.load_benchmark()
    metric, = [m for m in bench["per_layer"] if m["name"] == name]
    return {"cell": spec.load_cell(bench, metric["workloads"][0]),
            "trace": {"window_s": 1.0} if trace is None else trace}


def dispatches(ahead):
    """Decode dispatches 10 ms apart; ``None`` leaves the attribute out (the
    parent's regions)."""
    return [(D, i * 0.01, i * 0.01 + 0.001,
             {"active": 2, **({} if a is None else {"ahead": a})})
            for i, a in enumerate(ahead)]


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("ahead, want", [
    ([0, 1, 1, 1], 75.0), ([1] * 8, 100.0), ([0, 0], 0.0),
    ([None, None, None], None), ([], None),
    # a step dispatched before the program carried the attribute is left out
    ([None, 0, 1], 50.0)])
def test_known_rows_give_the_known_share(monkeypatch, name, ahead, want):
    monkeypatch.setattr(hr, "profile",
                        lambda run: {"regions": dispatches(ahead)})
    assert spec.metric_reader(name)(run_of(name)) == want


@pytest.mark.parametrize("name", NAMES)
def test_no_trace_gives_none(name):
    for trace in ({}, None):
        run = run_of(name)
        run["trace"] = trace
        assert spec.metric_reader(name)(run) is None


def test_the_engines_own_trace_reads_its_three_steps_of_five(monkeypatch):
    import engine_trace
    traced = engine_trace.run()
    profile = hr.read_profile(traced["path"])
    monkeypatch.setattr(hr, "profile", lambda run: profile)
    grown = {k: traced["stats"][k] - traced["stats_before"][k]
             for k in ("steps", "decode_ahead_steps")}
    assert grown == {"steps": 5, "decode_ahead_steps": 3}
    for name in NAMES:
        assert spec.metric_reader(name)(run_of(name)) == pytest.approx(60.0)


def test_the_benchmark_lists_it_for_chat_and_for_the_decode_cells():
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    plain, split = (entries[name] for name in NAMES)
    assert plain["workloads"] == ["serve-chat-steady"] and \
        plain["moves"] == "itl_mean_ms"
    assert split["workloads"] == entries["host_loop_cpu_ms.decode"][
        "workloads"] and split["moves"] == "served_tokens_per_s"
    for entry in (plain, split):
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "higher", "program_span",
                                    "serve/engine scheduler")
