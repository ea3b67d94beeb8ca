"""The readers of the engine's three clocks (``benchmark/host_threads.py``):
known sums of attributes give known metrics, regions without the attributes
(the program before PR 36) give ``None``, and the tiny engine's own trace
gives every reader a number."""

import os
import sys

import pytest

from benchmark import host_regions as hr
from benchmark import host_threads as ht
from benchmark import spec

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))     # tests/engine_trace.py

QUANTITIES = ("host_dispatch_blocked_ms", "host_dispatch_loop_cpu_ms",
              "host_resume_loop_cpu_ms", "host_loop_cpu_ms",
              "host_loop_busy_share", "host_loop_hidden_share",
              "gc_pause_share", "gc_pause_max_ms")
CHAT = "serve-chat-steady"
DECODE_CELLS = ["serve-longprompt-batch", "serve-olmoe-decode-heavy",
                "serve-ouro-cot-batch", "serve-xing-reasoning-batch"]
MS = 1e-3


def recorded(with_clocks=True):
    """Two decode steps and a prefill between them as the engine marks
    them, 20 ms apart, and two collector passes that overlap: (name, start,
    end, attributes), seconds.  Without the clocks: the parent's regions."""
    def attrs(old, new):
        return {**old, **(new if with_clocks else {})}
    regions = []
    for at, step_us, step_cpu in ((0.0, 20000, 5000), (20 * MS, 20000, 3000)):
        regions += [
            ("rt:engine.decode.dispatch", at, at + 2 * MS, attrs(
                {"active": 2, "submit_us": 200},
                {"step_us": step_us, "step_loop_cpu_us": step_cpu})),
            ("rt:engine.decode.fetch", at + 2 * MS, at + 12 * MS, attrs(
                {}, {"dispatch_us": 2000, "dispatch_cpu_us": 500,
                     "dispatch_loop_cpu_us": 1200})),
            ("rt:engine.deliver", at + 13 * MS, at + 13.1 * MS, attrs(
                {"tokens": 2, "resume_us": 900},
                {"fetch_loop_cpu_us": 1000, "resume_loop_cpu_us": 600}))]
    # the delivery of a prefill's token: a fetch phase and a resume of its
    # own, no dispatch
    regions.append(("rt:engine.deliver", 17 * MS, 17.1 * MS, attrs(
        {"tokens": 1, "resume_us": 300},
        {"fetch_loop_cpu_us": 0, "resume_loop_cpu_us": 200})))
    if with_clocks:
        regions += [("rt:gc", 5 * MS, 9 * MS, {"generation": 2}),
                    ("rt:gc", 8 * MS, 8.5 * MS, {"generation": 0}),
                    ("rt:gc", 30 * MS, 31 * MS, {"generation": 1})]
    return sorted(regions, key=lambda r: r[1])


def run_of(cell, decode_calls=2, window_s=0.05):
    bench = spec.load_benchmark()
    return {"cell": spec.load_cell(bench, cell),
            "trace": {"window_s": window_s, "programs": {
                hr.DECODE: {"calls": decode_calls, "device_s": 0.02}}}}


def reader(name):
    return spec.metric_reader(name)


# (2000 - 500) x 2 / 2 calls; 1200 x 2 / 2; (600 + 600 + 200) / 2;
# (5000 + 3000) / 2; 8000 / 40000; (1000 + 1000 + 0) / 8000; the passes
# cover 5-9 and 30-31 ms of a 50 ms window; the longest is 4 ms
KNOWN = {"host_dispatch_blocked_ms": 1.5, "host_dispatch_loop_cpu_ms": 1.2,
         "host_resume_loop_cpu_ms": 0.7, "host_loop_cpu_ms": 4.0,
         "host_loop_busy_share": 20.0, "host_loop_hidden_share": 25.0,
         "gc_pause_share": 10.0, "gc_pause_max_ms": 4.0}


@pytest.mark.parametrize("name", QUANTITIES)
def test_known_sums_give_the_known_metric(monkeypatch, name):
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": recorded()})
    assert reader(name)(run_of(CHAT)) == pytest.approx(KNOWN[name])
    # the split name finds the same reader by its stem
    assert reader(name + ".decode")(run_of(DECODE_CELLS[0])) == \
        pytest.approx(KNOWN[name])


@pytest.mark.parametrize("name", QUANTITIES)
def test_the_parents_regions_give_none(monkeypatch, name):
    monkeypatch.setattr(hr, "profile",
                        lambda run: {"regions": recorded(with_clocks=False)})
    assert reader(name)(run_of(CHAT)) is None


@pytest.mark.parametrize("name", QUANTITIES)
def test_no_trace_gives_none(name):
    bench = spec.load_benchmark()
    for trace in ({}, None):
        assert reader(name)({"cell": spec.load_cell(bench, CHAT),
                             "trace": trace}) is None


def test_a_window_without_a_decode_call_gives_none(monkeypatch):
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": recorded()})
    run = run_of(CHAT)
    run["trace"]["programs"] = {}
    for name in QUANTITIES[:4]:
        assert reader(name)(run) is None
    # the shares need no call count
    assert reader("host_loop_busy_share")(run) == pytest.approx(20.0)


def test_a_coarse_cpu_clock_is_compared_by_its_sums(monkeypatch):
    """A host whose thread CPU clock ticks in 10 ms units gives one region
    nothing or a whole tick: only the window's sums mean anything, and a
    sum of CPU that passes its wall is handed on as it is, under 0."""
    def fetches(cpu):
        return [("rt:engine.decode.fetch", float(i), i + 1.0,
                 {"dispatch_us": 3000, "dispatch_cpu_us": c,
                  "dispatch_loop_cpu_us": 0}) for i, c in enumerate(cpu)]
    monkeypatch.setattr(
        hr, "profile", lambda run: {"regions": fetches([0, 10000, 0, 0])})
    assert ht.total_us(run_of(CHAT), ht.FETCH, "dispatch_us",
                       less="dispatch_cpu_us") == 2000
    monkeypatch.setattr(
        hr, "profile", lambda run: {"regions": fetches([10000, 10000])})
    assert ht.total_us(run_of(CHAT), ht.FETCH, "dispatch_us",
                       less="dispatch_cpu_us") == -14000
    assert reader("host_dispatch_blocked_ms")(run_of(CHAT)) == \
        pytest.approx(-7.0)


def test_the_benchmark_lists_each_quantity_for_chat_and_for_the_decode_cells():
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in QUANTITIES:
        plain, split = entries[name], entries[name + ".decode"]
        assert plain["workloads"] == [CHAT] and \
            plain["moves"] == "itl_mean_ms"
        assert split["workloads"] == DECODE_CELLS and \
            split["moves"] == "served_tokens_per_s"
        assert plain["source"] == split["source"] == "program_span"
        assert (plain["unit"], plain["layer"], plain["better"]) == \
            (split["unit"], split["layer"], split["better"])


# ---------------------------------- on a trace the engine itself wrote

@pytest.fixture(scope="module")
def engine_run():
    import engine_trace
    traced = engine_trace.run()
    profile = hr.read_profile(traced["path"])
    steps = sum(name == "rt:engine.decode.dispatch"
                for name, _, _, _ in profile["regions"])
    spans = [(s, e) for _, s, e, _ in profile["regions"]]
    # the CPU has no device plane: the calls and the window are the host's
    run = run_of(CHAT, decode_calls=steps,
                 window_s=max(e for _, e in spans) - min(s for s, _ in spans))
    return run, profile


@pytest.mark.parametrize("name", QUANTITIES)
def test_the_engines_own_trace_gives_every_reader_a_number(
        monkeypatch, engine_run, name):
    run, profile = engine_run
    monkeypatch.setattr(hr, "profile", lambda run: profile)
    value = reader(name)(run)
    # (a difference of two sums of truncated microseconds: a hair under 0
    # where the exec thread ran the whole of its phase)
    assert isinstance(value, float) and value > -0.01
    if name.endswith("_share"):
        assert value <= 100


def test_the_engines_own_numbers_hold_together(monkeypatch, engine_run):
    run, profile = engine_run
    monkeypatch.setattr(hr, "profile", lambda run: profile)
    fetches = hr.rows(run, ht.FETCH)
    mean_dispatch_ms = sum(r["dispatch_us"] for r in fetches) \
        / len(fetches) * MS
    assert reader("host_dispatch_blocked_ms")(run) <= mean_dispatch_ms
    # what the loop ran inside two phases of its steps is part of what it
    # ran over the whole steps (but for the last step's, which no later
    # step counts: a millisecond of room on the CPU)
    assert reader("host_dispatch_loop_cpu_ms")(run) \
        + reader("host_resume_loop_cpu_ms")(run) \
        <= reader("host_loop_cpu_ms")(run) + 1.0 / len(fetches)
    # the forced full pass that opens the traced stretch
    assert reader("gc_pause_max_ms")(run) > 0
