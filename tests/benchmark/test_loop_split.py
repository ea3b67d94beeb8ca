"""The readers that split ``host_loop_cpu_ms`` (``benchmark/loop_split.py``,
PR 53): on made-up regions the four parts are what was put in and add up to
the whole, the parent's regions give ``None``, a split name finds the reader
by its stem, and ``benchmark/tools/read_profile.py`` prints any of them,
listed in ``BENCHMARK.json`` or not, from a profile, against the peaks of the
device that the profile names."""

import json
import os
import sys
import types

import pytest

from benchmark import host_regions as hr
from benchmark import loop_split, spec
from benchmark.tools import read_profile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))     # tests/engine_trace.py

KIMI = "serve-kimi-linear-reasoning-wide"
CHAT = "serve-chat-steady"
MS = 1e-3
# files of ``metrics/unlisted/``, in no entry of BENCHMARK.json: ``per_layer``
# is full
UNLISTED = ("host_loop_engine_ms", "host_loop_stream_ms", "host_loop_rpc_ms",
            "host_loop_unnamed_ms", "host_loop_us_per_slot_step",
            "rpc_msgs_per_token", "rpc_msgs_per_frame", "stream_ack_out_ms",
            "stream_ack_in_ms", "stream_ack_held_ms", "stream_ack_back_ms")
PARTS = UNLISTED[:4]


def recorded(with_sums=True, coarse=False):
    """Two decode steps as the engine marks them, 20 ms apart, each with a
    delivery and a schedule on the loop thread, and three yields' regions:
    (name, start, end, attributes), seconds.  Without the sums: the
    parent's regions.  ``coarse``: a CPU clock that read one step as 0."""
    def attrs(old, new):
        return {**old, **(new if with_sums else {})}
    regions = []
    for n, (at, loop_cpu) in enumerate(((0.0, 0 if coarse else 9000),
                                        (20 * MS, 7000))):
        regions += [
            ("rt:engine.schedule", at, at + 0.5 * MS,
             {"active": 2, "waiting": 0}),
            ("rt:engine.decode.dispatch", at + 1 * MS, at + 3 * MS, attrs(
                {"active": 4 + 4 * n, "step_us": 20000,
                 "step_loop_cpu_us": loop_cpu},
                {"yields": 4, "stream_store_us": 100 + 100 * n,
                 "stream_after_us": 900, "rpc_out_us": 1500,
                 "rpc_in_us": 500 + 1000 * n, "msgs_out": 12, "msgs_in": 12,
                 "frames_out": 2 + 2 * n, "frames_in": 3})),
            ("rt:engine.deliver", at + 13 * MS, at + 14 * MS,
             {"tokens": 4, "resume_us": 100})]
    for at, (ack, out, in_, held) in enumerate((
            (1000, 100, 300, 50), (1200, 20, 700, 60), (5000, 900, 400, 40))):
        regions.append(("rt:stream.yield", at * MS, at * MS, attrs(
            {"ack_us": ack},
            {"out_us": out, "in_us": in_, "held_us": held, "wait_us": 10000,
             "store_us": 3, "after_us": 30})))
    return sorted(regions, key=lambda r: r[1])


def run_of(cell=KIMI, decode_calls=2):
    return {"cell": spec.load_cell(spec.load_benchmark(), cell),
            "trace": {"window_s": 0.05, "programs": {
                hr.DECODE: {"calls": decode_calls, "device_s": 0.02}}}}


# engine: two schedules of 0.5 ms and two delivers of 1 ms over two calls;
# stream: (100 + 900 + 200 + 900) / 2; rpc: (1500 + 500 + 1500 + 1500) / 2;
# the whole (9000 + 7000) / 2 = 8 ms, so 8 - 1.5 - 1.05 - 2.5 is unnamed;
# 16000 us over 4 + 8 live slots; 48 messages over 8 yields; 24 sent in 6
# frames; the medians of out_us, in_us, held_us and of what is left of ack_us
KNOWN = {"host_loop_engine_ms": 1.5, "host_loop_stream_ms": 1.05,
         "host_loop_rpc_ms": 2.5, "host_loop_unnamed_ms": 2.95,
         "host_loop_us_per_slot_step": 16000 / 12,
         "rpc_msgs_per_token": 6.0, "rpc_msgs_per_frame": 4.0,
         "stream_ack_out_ms": 0.1, "stream_ack_in_ms": 0.4,
         "stream_ack_held_ms": 0.05, "stream_ack_back_ms": 0.55}


@pytest.mark.parametrize("name", UNLISTED)
def test_known_regions_give_the_known_metric(monkeypatch, name):
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": recorded()})
    assert read_profile.reader(name)(run_of()) == pytest.approx(KNOWN[name])
    # a name split by cells finds the same reader by its stem
    for cells in (".kimi", ".hybrid", ".decode"):
        assert read_profile.reader(name + cells)(run_of()) == \
            pytest.approx(KNOWN[name])


def test_the_four_parts_add_up_to_the_loops_time(monkeypatch):
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": recorded()})
    run = run_of()
    parts = [read_profile.reader(name)(run) for name in PARTS]
    assert sum(parts) == pytest.approx(
        read_profile.reader("host_loop_cpu_ms.kimi")(run))
    assert loop_split.parts_us(run) == {"engine": 3000, "stream": 2100,
                                        "rpc": 5000, "unnamed": 5900}


def test_the_remainder_is_signed(monkeypatch):
    """A thread CPU clock that ticks in 10 ms steps may read a window's
    loop time under the walls of its named sections: the remainder is
    handed on as it is, under 0, and the parts still add up."""
    monkeypatch.setattr(hr, "profile",
                        lambda run: {"regions": recorded(coarse=True)})
    run = run_of()
    assert read_profile.reader("host_loop_unnamed_ms")(run) == \
        pytest.approx((7000 - 3000 - 2100 - 5000) / 2 * MS)
    assert sum(read_profile.reader(name)(run) for name in PARTS) == \
        pytest.approx(read_profile.reader("host_loop_cpu_ms")(run))


@pytest.mark.parametrize("name", UNLISTED)
def test_the_parents_regions_give_none(monkeypatch, name):
    """Before PR 53 a dispatch carries no sums and a yield's ack tells
    nothing; only what a live slot costs reads attributes of PR 36."""
    monkeypatch.setattr(hr, "profile",
                        lambda run: {"regions": recorded(with_sums=False)})
    value = read_profile.reader(name)(run_of())
    if name == "host_loop_us_per_slot_step":
        assert value == pytest.approx(KNOWN[name])
    else:
        assert value is None


@pytest.mark.parametrize("name", UNLISTED)
def test_no_trace_and_no_decode_call_give_none(monkeypatch, name):
    cell = spec.load_cell(spec.load_benchmark(), KIMI)
    for trace in ({}, None):
        assert read_profile.reader(name)({"cell": cell, "trace": trace}) \
            is None
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": recorded()})
    run = run_of()
    run["trace"]["programs"] = {}
    if name in PARTS:                    # the others need no call count
        assert read_profile.reader(name)(run) is None


def test_a_yield_whose_owner_is_on_another_host_is_left_out(monkeypatch):
    regions = recorded() + [("rt:stream.yield", 9 * MS, 9 * MS,
                             {"ack_us": 90000, "wait_us": 1, "store_us": 1,
                              "after_us": 1})]
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": regions})
    assert read_profile.reader("stream_ack_in_ms")(run_of()) == \
        pytest.approx(KNOWN["stream_ack_in_ms"])
    # the accepted reader takes every yield, as before
    assert read_profile.reader("stream_yield_ack_ms")(run_of(CHAT)) == \
        pytest.approx(3.1)


def test_the_unlisted_readers_are_in_no_entry_and_all_are_found():
    """``per_layer`` is at the contract's 128, so the readers are files of
    ``metrics/unlisted/``: ``metrics/`` itself keeps a file for every entry
    and no other (``test_itl_mean.py::test_a_reader_for_every_entry_and_an_
    entry_for_every_reader`` stands), and the tool finds both kinds by name
    and by stem."""
    bench = spec.load_benchmark()
    assert len(bench["per_layer"]) == 128
    names = {m["name"] for kind in ("end_to_end", "per_layer")
             for m in bench[kind]}
    files = {f[:-3] for f in os.listdir(read_profile.UNLISTED)
             if f.endswith(".py")}
    assert files == set(UNLISTED)
    assert not files & (names | {n.split(".")[0] for n in names})
    for name in sorted(names | files):
        assert callable(read_profile.reader(name)), name
    with pytest.raises(SystemExit, match="no reader"):
        read_profile.reader("host_loop_nothing_ms")


# ------------------------------------------------------------ the tool

def _profile_of(*kinds):
    """What ``ProfileData.from_file`` gives of a profile whose device
    planes name those kinds: only what ``device_peaks`` reads."""
    planes = [types.SimpleNamespace(
        name=f"/device:TPU:{n}", lines=[],
        stats=[("peak_teraflops_per_second", 202.7),
               ("device_type_string", kind)])
        for n, kind in enumerate(kinds)]
    host = types.SimpleNamespace(name="/host:CPU", lines=[],
                                 stats=[("device_type_string", "a host")])
    return types.SimpleNamespace(planes=[host, *planes])


@pytest.mark.parametrize("kinds, found", [
    (("TPU v5 Lite",), "TPU v5 lite"),      # the profiler's spelling
    (("TPU v5 lite", "TPU v5 lite"), "TPU v5 lite"),
    ((), "one is needed"),                  # the CPU's profile: no device
    (("TPU v5 Lite", "TPU v4"), "one is needed"),
    (("TPU v9",), "not in benchmark/peaks.json")])
def test_the_peaks_are_those_of_the_device_the_profile_names(
        monkeypatch, kinds, found):
    """No device is assumed: the profile's device planes say what made it,
    and a profile that says nothing, or names a device without published
    peaks, is refused."""
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "ProfileData", types.SimpleNamespace(
        from_file=lambda path: _profile_of(*kinds)))
    if found in spec.load_json("peaks.json"):
        assert read_profile.device_peaks("a.xplane.pb") == \
            spec.peaks_for(found)
    else:
        with pytest.raises(SystemExit, match=found):
            read_profile.device_peaks("a.xplane.pb")


def test_the_tool_reads_any_reader_from_a_profile(tmp_path, monkeypatch,
                                                  capsys):
    """The tiny engine's own trace, laid where a traced run of the cell
    leaves its profile: the tool rebuilds ``run`` from it and prints a
    listed reader, an unlisted one and one that needs the live run."""
    import shutil

    import engine_trace
    import jax.profiler
    trace = engine_trace.run()["path"]
    under = tmp_path / ".bench_trace" / KIMI / "plugins"
    under.mkdir(parents=True)
    shutil.copy(trace, under / "host.xplane.pb")
    monkeypatch.setattr(spec, "ROOT", spec.ROOT)       # the tool moves it
    # the CPU's profile names no device program: the five decode steps the
    # tiny engine took are said here, the regions are read from the file
    steps = max(engine_trace.NEW_TOKENS) - 1
    monkeypatch.setattr(
        read_profile.trace_reduce, "reduce_events",
        lambda events: {"window_s": 1.0, "programs": {
            hr.DECODE: {"calls": steps, "device_s": 0.01}}})
    names = ["host_loop_cpu_ms.kimi", "host_loop_us_per_slot_step",
             "stream_ack_in_ms", "decode_batch_occupancy.kimi", *PARTS]
    monkeypatch.setattr(sys, "argv", [
        "read_profile.py", "--workload", KIMI, "--root", str(tmp_path),
        *names])
    # the CPU's profile names no device, and the tool assumes none
    with pytest.raises(SystemExit, match="one is needed"):
        read_profile.main()
    monkeypatch.setattr(spec, "ROOT", read_profile.ROOT)
    real = jax.profiler.ProfileData
    monkeypatch.setattr(jax.profiler, "ProfileData", types.SimpleNamespace(
        from_file=lambda path: types.SimpleNamespace(planes=[
            *real.from_file(path).planes,
            *_profile_of("TPU v5 Lite").planes[1:]])))
    assert read_profile.main() == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert set(out) == set(names)
    assert out["host_loop_cpu_ms.kimi"] > 0
    assert out["host_loop_us_per_slot_step"] > 0
    # no cluster around the tiny engine: no stream, no frame, and the parts
    # still add up
    assert out["host_loop_engine_ms"] > 0
    assert out["host_loop_stream_ms"] == out["host_loop_rpc_ms"] == 0
    assert sum(out[name] for name in PARTS) == \
        pytest.approx(out["host_loop_cpu_ms.kimi"])
    assert out["stream_ack_in_ms"] is None
    # what only the live run had is said, not raised
    assert "a profile lacks" in out["decode_batch_occupancy.kimi"]


def test_the_tool_reads_listed_and_unlisted_side_by_side(monkeypatch):
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": recorded()})
    listed = ["host_loop_cpu_ms.kimi", "host_loop_busy_share.kimi"]
    assert {m["name"] for m in spec.metrics_of(
        spec.load_benchmark(), "per_layer", KIMI)} >= set(listed)
    out = read_profile.read_all(run_of(), listed + list(UNLISTED))
    assert out["host_loop_cpu_ms.kimi"] == pytest.approx(8.0)
    assert out["host_loop_busy_share.kimi"] == pytest.approx(40.0)
    assert {name: out[name] for name in UNLISTED} == pytest.approx(KNOWN)
