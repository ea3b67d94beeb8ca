"""``tracing.region``: host intervals in the JAX profiler's own trace, and
the serving engine's use of them (ISSUE 24)."""

import os
import subprocess
import sys

import pytest

from ray_tpu.serve.engine.engine import prefill_rungs, rung_for
from ray_tpu.util import tracing

import engine_trace

RUNGS = prefill_rungs(engine_trace.MAX_PROMPT_LEN, engine_trace.PAGE)
# From ``rt:engine.schedule``'s end to the step's submission the loop builds
# the batch's three arrays: 38-96 us over 35 steps of seven runs, four of
# them beside eight busy processes on eight cores (PR 36; 74-86 before it).
# The tiny engine's shortest step is 570 us, so 300 still tells a step's
# submission from its neighbours'.
SUBMIT_SLACK_NS = 300e3


def _host_regions(path):
    """``(name, start_ns, end_ns, stats)`` of the ``rt:`` events of the
    trace's host plane, in order of start."""
    from jax.profiler import ProfileData
    plane, = [p for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU"]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for line in plane.lines for e in line.events
              if e.name.startswith("rt:")]
    return sorted(events, key=lambda e: e[1])


def _trace(tmp_path, body, keep_gc=False):
    """The regions of a session around ``body()``.  Once any engine of this
    process has called ``watch_gc()`` the collector's passes are regions
    too, wherever they fall: left out unless asked for."""
    import glob
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    return [r for r in _host_regions(path) if keep_gc or r[0] != "rt:gc"]


def test_region_lands_in_the_host_plane_with_its_attributes(tmp_path):
    def body():
        with tracing.region("outer", count=3, what="x"):
            with tracing.region("inner"):
                pass
    outer, inner = _trace(tmp_path, body)
    assert outer[0] == "rt:outer" and inner[0] == "rt:inner"
    assert outer[3] == {"count": 3, "what": "x"} and inner[3] == {}
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_region_outside_a_session_records_nothing(tmp_path):
    with tracing.region("before", n=1):
        pass
    assert _trace(tmp_path, lambda: None) == []


def test_region_is_a_noop_where_jax_is_not_imported():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "a, b = tracing.region('x', n=1), tracing.region('y')\n"
        "with a, b:\n"
        "    pass\n"
        "assert a is b, (a, b)\n"
        "assert 'jax' not in sys.modules, 'region() imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_span_still_builds_its_tree_and_shows_in_a_profile(tmp_path,
                                                           monkeypatch):
    recorded = []
    monkeypatch.setattr(tracing, "_record", recorded.append)
    monkeypatch.setattr(tracing, "_enabled", True)

    def body():
        with tracing.span("step") as (trace_id, root):
            with tracing.span("part", {"k": 1}) as (same, _):
                assert same == trace_id

    regions = _trace(tmp_path, body)
    part, step = recorded
    assert (step["name"], part["name"]) == ("step", "part")
    assert part["parent_id"] == step["span_id"] and \
        step["parent_id"] is None and part["trace_id"] == step["trace_id"]
    assert part["attributes"] == {"k": 1}
    assert [r[0] for r in regions] == ["rt:step", "rt:part"]


# ------------------------------------------------------ the serving engine

@pytest.fixture(scope="module")
def engine_run():
    run = engine_trace.run()
    return {**run, "regions": _host_regions(run["path"])}


def test_engine_regions_follow_the_decode_step(engine_run):
    # a pass that finds nothing to do (a finished stream wakes the loop
    # once more) is a ``schedule`` alone: left out here
    # (``rt:gc`` regions lie wherever the collector ran)
    names = [name.removeprefix("rt:engine.")
             for name, _, _, stats in engine_run["regions"]
             if name.startswith("rt:engine.")
             and not (name == "rt:engine.schedule"
                      and stats["active"] == stats["waiting"] == 0)]
    steps = max(engine_trace.NEW_TOKENS) - 1
    assert names.count("prefill") == len(engine_trace.PROMPTS)
    assert names.count("decode.dispatch") == names.count("decode.fetch") \
        == steps
    # every call of the exec lane follows a ``schedule`` and ends in a
    # ``deliver``.  A prefill's delivers its first token.  A step
    # dispatched on a drained pipe has nothing to deliver yet (the pass
    # that prefilled builds its batch in a ``schedule`` of its own); a
    # step dispatched behind the one in flight is followed, in the same
    # call, by the fetch of that one; a drain fetches with nothing
    # dispatched.
    admit = ["schedule", "prefill", "deliver"]
    alone = ["schedule", "decode.dispatch", "deliver"]
    ahead = ["schedule", "decode.dispatch", "decode.fetch", "deliver"]
    drain = ["schedule", "decode.fetch", "deliver"]
    # the second prompt arrives while step 1 is in flight: the pipe drains
    # for its prefill, fills again, and drains at the last token
    assert names == admit + alone + drain + admit + alone \
        + ahead * (steps - 2) + drain, names


def test_the_trace_holds_a_drained_stretch_and_an_ahead_stretch(engine_run):
    """Drained: a dispatch, then its own fetch.  Ahead: step N+1's dispatch
    ends before step N's fetch begins, on the one exec lane."""
    dispatches = [(start, end, stats) for n, start, end, stats
                  in engine_run["regions"] if n == D]
    fetches = [(start, end) for n, start, end, _ in engine_run["regions"]
               if n == F]
    assert [stats["ahead"] for _, _, stats in dispatches] == [0, 0, 1, 1, 1]
    # step N's fetch is the N-th: the lane fetches in order of dispatch
    for n, (start, end, stats) in enumerate(dispatches):
        before = sum(f_start < start for f_start, _ in fetches)
        # a step behind another is dispatched with that one still unfetched
        assert before == (n - 1 if stats["ahead"] else n), (n, before)
        assert fetches[n][0] >= end
    # step 1 is fetched before step 2 is dispatched (the drain for the
    # second prompt's prefill); step 2 only after step 3's dispatch
    assert fetches[0][1] <= dispatches[1][0]
    assert dispatches[2][1] <= fetches[1][0]
    grown = {k: engine_run["stats"][k] - engine_run["stats_before"][k]
             for k in ("steps", "decode_ahead_steps", "stray_slot_steps")}
    assert grown == {"steps": 5, "decode_ahead_steps": 3,
                     "stray_slot_steps": 0}


def test_engine_regions_carry_their_attributes(engine_run):
    by_name = {}
    for name, start, end, stats in engine_run["regions"]:
        by_name.setdefault(name, []).append((start, end, stats))
    prefills = by_name["rt:engine.prefill"]
    assert sorted(s["prompt_len"] for _, _, s in prefills) == \
        sorted(len(p) for p in engine_trace.PROMPTS)
    for _, _, stats in prefills:
        assert stats["padded_len"] == rung_for(RUNGS, stats["prompt_len"])
        assert stats["waited_us"] >= stats["submit_us"] >= 0
    dispatches = by_name["rt:engine.decode.dispatch"]
    # the first sequence alone, then both until the shorter one has its
    # last token coming (a step is not dispatched for it), then the longer
    short, long = sorted(engine_trace.NEW_TOKENS)
    assert [s["active"] for _, _, s in dispatches] == \
        [1] + [2] * (short - 1) + [1] * (long - short - 1)
    assert all(s["submit_us"] >= 0 for _, _, s in dispatches)
    # the step's table is as wide as its longest sequence needs: the
    # longer prompt (5) grows to 10 positions, over the first page's 8
    assert [s["width_pages"] for _, _, s in dispatches] == [1, 1, 1, 2, 2]
    assert all(s["gathered_tokens"] == engine_trace.MAX_BATCH
               * s["width_pages"] * engine_trace.PAGE
               for _, _, s in dispatches)
    delivers = by_name["rt:engine.deliver"]
    assert sum(s["tokens"] for _, _, s in delivers) == \
        sum(engine_trace.NEW_TOKENS)
    assert all(s["resume_us"] >= 0 for _, _, s in delivers)
    schedules = [s for s in by_name["rt:engine.schedule"]
                 if s[2]["active"] or s[2]["waiting"]]
    assert (schedules[0][2]["active"], schedules[0][2]["waiting"]) == (0, 1)
    # the crossing into the exec thread starts where the ``schedule``
    # before it ends (attributes are whole microseconds).  A step on a
    # drained pipe is submitted after the step before it was delivered; a
    # step behind another before that one is even fetched.
    fetches = by_name["rt:engine.decode.fetch"]
    for i, (start, _, stats) in enumerate(dispatches):
        submitted = start - stats["submit_us"] * 1e3
        sched_end = max(end for _, end, _ in schedules if end <= start)
        assert sched_end - 2e3 <= submitted <= sched_end + SUBMIT_SLACK_NS
        if i and not stats["ahead"]:
            delivered = min(end for begun, end, _ in delivers
                            if begun > fetches[i - 1][0])
            assert delivered <= submitted
        elif i:
            assert submitted <= fetches[i - 1][0]
    # a drain's crossing rides on its fetch, the first region of its call
    assert ["submit_us" in s for _, _, s in fetches] == \
        [not following["ahead"] for _, _, following
         in dispatches[1:]] + [True]


def test_engine_counters_add_up(engine_run):
    stats, new = engine_run["stats"], engine_trace.NEW_TOKENS
    assert [len(t) for t in engine_run["tokens"]] == list(new)
    prompts = (engine_trace.WARM_PROMPT,) + engine_trace.PROMPTS
    generated = (engine_trace.WARM_NEW,) + new
    assert stats["admitted"] == len(prompts)
    assert stats["prefill_tokens"] == sum(len(p) for p in prompts)
    assert stats["prefill_padded_tokens"] == \
        sum(rung_for(RUNGS, len(p)) for p in prompts)
    assert stats["prefill_shapes"] == {
        rung: sum(rung_for(RUNGS, len(p)) == rung for p in prompts)
        for rung in RUNGS}
    # a sequence's first token comes from its prefill, the rest one a step
    assert stats["slot_steps"] == sum(n - 1 for n in generated)
    assert stats["steps"] == (engine_trace.WARM_NEW - 1) + max(new) - 1
    assert stats["slot_steps"] <= stats["steps"] * engine_trace.MAX_BATCH
    assert stats["retired"] == {"done": len(prompts), "cancelled": 0,
                                "expired": 0, "error": 0}
    assert stats["queue_wait_s"] > 0
    assert stats["active"] == 0 and stats["waiting"] == 0


# ------------------------ the step's phases on three clocks (ISSUE 36)

F, D, DELIVER = ("rt:engine.decode.fetch", "rt:engine.decode.dispatch",
                 "rt:engine.deliver")
# a thread's CPU clock and the wall clock are read one after the other
CLOCK_SLACK_US = 1000


def _stats_of(engine_run, name):
    return [stats for n, _, _, stats in engine_run["regions"] if n == name]


@pytest.mark.parametrize("name, phases", [
    (F, {"dispatch": ("us", "cpu_us", "loop_cpu_us")}),
    (DELIVER, {"fetch": ("loop_cpu_us",), "resume": ("us", "loop_cpu_us")}),
    (D, {"step": ("us", "loop_cpu_us"), "submit": ("us",)}),
])
def test_a_phase_rides_on_the_region_that_follows_it(engine_run, name,
                                                     phases):
    found = _stats_of(engine_run, name)
    assert found
    for stats in found:
        for phase, clocks in phases.items():
            for clock in clocks:
                value = stats[f"{phase}_{clock}"]
                assert isinstance(value, int) and value >= 0, stats
            # the exec thread cannot have run for longer than the phase took
            if "cpu_us" in clocks:
                assert stats[phase + "_cpu_us"] <= \
                    stats[phase + "_us"] + CLOCK_SLACK_US, stats
        # no phase has an exec-thread clock it was not on, and the fetch
        # phase's wall is the ``decode.fetch`` (or ``prefill``) region itself
        assert not {"resume_cpu_us", "step_cpu_us", "fetch_us",
                    "fetch_cpu_us"} & set(stats)


def test_step_us_tiles_the_wall_between_submissions(engine_run):
    dispatches = [(start, stats) for n, start, _, stats
                  in engine_run["regions"] if n == D]
    submitted = [start - stats["submit_us"] * 1e3
                 for start, stats in dispatches]
    between = sum(stats["step_us"] for _, stats in dispatches[1:])
    assert between * 1e3 == pytest.approx(submitted[-1] - submitted[0],
                                          abs=1e6)
    # the first step counts from the loop's waking: the first prefill and
    # its delivery lie before it
    first_prefill = min(start for n, start, _, _ in engine_run["regions"]
                        if n == "rt:engine.prefill")
    assert dispatches[0][1]["step_us"] * 1e3 >= submitted[0] - first_prefill


def test_host_sums_tile_no_more_than_the_engines_life(engine_run):
    stats = engine_run["stats"]
    assert set(stats["host_s"]) == {"schedule", "submit", "dispatch",
                                    "fetch", "resume", "deliver"}
    assert all(v > 0 for v in stats["host_s"].values()), stats["host_s"]
    assert sum(stats["host_s"].values()) <= engine_run["wall_s"]
    cpu = stats["host_cpu_s"]
    assert set(cpu) == {"exec_dispatch", "exec_fetch", "loop",
                        "loop_in_dispatch", "loop_in_fetch",
                        "loop_in_resume"}
    assert all(v >= 0 for v in cpu.values()), cpu
    assert cpu["exec_dispatch"] > 0 and cpu["loop"] > 0
    assert cpu["exec_fetch"] <= stats["host_s"]["fetch"] \
        + CLOCK_SLACK_US * 1e-6 * stats["host_cpu_calls"]
    # the loop's CPU over whole steps holds what it ran inside their phases
    # (but for each stretch's last step, which no later step counts)
    assert cpu["loop"] >= cpu["loop_in_dispatch"] + cpu["loop_in_fetch"] \
        + cpu["loop_in_resume"] - 2 * CLOCK_SLACK_US * 1e-6
    assert cpu["exec_dispatch"] <= stats["host_s"]["dispatch"] \
        + CLOCK_SLACK_US * 1e-6 * stats["steps"]


@pytest.mark.parametrize("table, key, attributes", [
    ("host_s", "submit", {D: "submit_us", "rt:engine.prefill": "submit_us",
                          F: "submit_us"}),
    ("host_s", "dispatch", {F: "dispatch_us"}),
    ("host_s", "resume", {DELIVER: "resume_us"}),
    ("host_cpu_s", "exec_dispatch", {F: "dispatch_cpu_us"}),
    ("host_cpu_s", "loop", {D: "step_loop_cpu_us"}),
    ("host_cpu_s", "loop_in_dispatch", {F: "dispatch_loop_cpu_us"}),
    ("host_cpu_s", "loop_in_fetch", {DELIVER: "fetch_loop_cpu_us"}),
    ("host_cpu_s", "loop_in_resume", {DELIVER: "resume_loop_cpu_us"}),
])
def test_the_regions_and_the_sums_come_from_one_set_of_reads(
        engine_run, table, key, attributes):
    """The traced stretch began and ended with the engine idle, so what its
    regions carry is what the always-on sum grew by: to the attributes'
    rounding down to whole microseconds."""
    grown = engine_run["stats"][table][key] \
        - engine_run["stats_before"][table][key]
    # (a crossing rides on the first region of its call: of the fetches,
    # on a drain's alone)
    carried = [stats[attr] for name, attr in attributes.items()
               for stats in _stats_of(engine_run, name)
               if name != F or attr in stats]
    assert 0 <= grown * 1e6 - sum(carried) <= len(carried) + 1e-3


def test_the_fetch_phase_wraps_its_region(engine_run):
    """``host_s["fetch"]`` is read around ``rt:engine.decode.fetch`` and
    around a whole ``rt:engine.prefill``, so the regions' own lengths are
    its wall (and ``rt:engine.deliver`` need not repeat them): it holds
    them, and nothing that its call does not.  A call's four phases
    (submit, dispatch, fetch, resume) are one chain of clock reads from
    its submission on the loop thread to ``_deliver``'s entry there, and
    that chain lies between the ``rt:engine.schedule`` before the call
    and the ``rt:engine.deliver`` that ends it: so the fetch phase is at
    most the calls' spans on the profiler's clock less the other three
    phases.  A thread that loses the processor between a clock read and
    a region's edge lengthens a phase AND its span: no such stall can
    break either side (a slack of 1 ms a region could, and did, under
    six loaded workers)."""
    def grown(phase):
        return engine_run["stats"]["host_s"][phase] \
            - engine_run["stats_before"]["host_s"][phase]
    regions = engine_run["regions"]
    inside = [end - start for name, start, end, _ in regions
              if name in (F, "rt:engine.prefill")]
    assert sum(inside) <= grown("fetch") * 1e9
    schedules = [end for name, _, end, _ in regions
                 if name == "rt:engine.schedule"]
    delivers = [start for name, start, _, _ in regions if name == DELIVER]
    # every call of the exec lane ends in a deliver: decode steps, drains
    # and prefills
    assert len(delivers) == len(inside) + sum(
        not stats["ahead"] for stats in _stats_of(engine_run, D))
    spans = sum(start - max(end for end in schedules if end <= start)
                for start in delivers)
    others = sum(grown(phase) for phase in ("submit", "dispatch", "resume"))
    assert grown("fetch") * 1e9 <= spans - others * 1e9


def test_the_cpu_clocks_are_read_for_a_sample_of_the_calls_untraced(
        engine_run):
    """With no session to carry them, one call in ``_CPU_EVERY`` reads the
    thread CPU clocks between its submission and its delivery; under a
    session every call does, and ``host_cpu_calls`` says how many did.  The
    lane's calls are the prefills, the decode steps and the drains: one for
    every step that was dispatched on a drained pipe."""
    from ray_tpu.serve.engine.engine import _CPU_EVERY
    before, after = engine_run["stats_before"], engine_run["stats"]

    def calls(stats):
        return stats["admitted"] + stats["steps"] \
            + stats["steps"] - stats["decode_ahead_steps"]
    assert calls(before) > 1
    assert before["host_cpu_calls"] == -(-calls(before) // _CPU_EVERY)
    assert after["host_cpu_calls"] - before["host_cpu_calls"] == \
        calls(after) - calls(before)
    # the wall is read at every call all the same
    assert all(v > 0 for v in before["host_s"].values()), before["host_s"]


def test_recording_follows_the_session(tmp_path):
    seen = []
    assert not tracing.recording()
    assert _trace(tmp_path, lambda: seen.append(tracing.recording())) == []
    assert seen == [True] and not tracing.recording()
    code = ("import sys\n"
            "from ray_tpu.util import tracing\n"
            "assert tracing.recording() is False\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_the_engines_stats_count_the_collector(engine_run):
    before, after = (engine_run[k]["gc"] for k in ("stats_before", "stats"))
    assert after["passes"][2] >= before["passes"][2] + 1
    assert after["pause_s"][2] > before["pause_s"][2]
    assert after["pause_max_s"] >= before["pause_max_s"] > 0
    full = [stats for name, _, _, stats in engine_run["regions"]
            if name == "rt:gc" and stats["generation"] == 2]
    assert len(full) == after["passes"][2] - before["passes"][2]


# ------------- what the loop does beside the engine (ISSUE 53): the sums

def test_a_step_carries_what_the_loops_sums_grew_by(engine_run):
    """Every ``rt:engine.decode.dispatch`` carries, as integers, what the
    streams' and the transport's always-on sums grew by since the
    submission before it: never more than their ``stats()`` twins grew by
    over the whole stretch (no cluster here: nothing streams, both 0)."""
    from ray_tpu.serve.engine.engine import _BESIDE
    found = _stats_of(engine_run, D)
    before, after = engine_run["stats_before"], engine_run["stats"]
    for attr, key in _BESIDE.items():
        table, name = key.split(".")
        carried = [stats[attr] for stats in found]
        assert all(isinstance(v, int) and v >= 0 for v in carried), attr
        twin = after[table].get(name, 0) - before[table].get(name, 0)
        assert sum(carried) <= twin * (1e6 if attr.endswith("_us") else 1)
    # the twins: the process's sums, seconds as floats and counts whole
    assert set(after["rpc"]) >= {"out", "in"}
    assert all(isinstance(v, float if k.endswith("_s") else int)
               for table in ("stream", "rpc")
               for k, v in after[table].items() if k not in ("out", "in"))


def test_beside_is_the_growth_of_the_sums_since_the_call_before():
    """``_beside()`` at a step's submission: each attribute the growth of
    its sum since the call before (or the loop's waking), whole
    microseconds and counts, so that over a stretch the regions carry the
    twins' growth to a microsecond a region."""
    from ray_tpu.serve.engine.engine import _BESIDE, InferenceEngine
    engine = InferenceEngine.__new__(InferenceEngine)
    sums = tracing.accumulator()
    sums["stream.store_s"] += 0.25                  # before the waking
    engine._sums_from = engine._beside_now()
    was = tracing.sums()
    steps = []
    for n in range(1, 4):
        sums["stream.yields"] += 2 * n
        sums["stream.store_s"] += n * 10.6e-6
        sums["stream.after_s"] += 31.2e-6
        sums["rpc.out_s"] += 5e-6
        sums["rpc.msgs_out"] += 6
        sums["rpc.frames_in"] += n
        sums["stream.wait_s"] += 1.0                # carried by no step
        steps.append(engine._beside())
    assert set(steps[0]) == set(_BESIDE)
    assert [s["yields"] for s in steps] == [2, 4, 6]
    assert [s["stream_store_us"] for s in steps] == [10, 21, 31]
    assert [s["frames_in"] for s in steps] == [1, 2, 3]
    assert all(s["msgs_out"] == 6 and s["rpc_in_us"] == 0 for s in steps)
    now = tracing.sums()
    for attr, key in _BESIDE.items():
        twin = (now.get(key, 0) - was.get(key, 0)) \
            * (1e6 if attr.endswith("_us") else 1)
        assert 0 <= twin - sum(s[attr] for s in steps) <= len(steps), attr
    assert engine._beside() == dict.fromkeys(_BESIDE, 0)


def test_the_sums_and_the_transport_count_where_jax_is_not_imported():
    """``tracing``'s sums and the connection's counts are always on, in a
    process without jax as well (the ingress, the raylet): one request
    over a local socket is a request and a hello out, a reply and a hello
    in, in whole frames, and the peer is known to be this host.  What
    kinds and types the messages are of is counted under a profiler
    session alone, and there is none without jax."""
    code = (
        "import asyncio, sys\n"
        "from ray_tpu.util import tracing\n"
        "from ray_tpu._private import protocol\n"
        "sums = tracing.accumulator()\n"
        "sums['stream.yields'] += 1\n"
        "sums['stream.store_s'] += 0.5\n"
        "sums['stream.store_s'] += 0.25\n"
        "assert tracing.sums('stream.') == {'yields': 1.0, 'store_s': 0.75}\n"
        "async def go():\n"
        "    async def served(msg):\n"
        "        return {'echo': msg['n']}\n"
        "    server = protocol.RpcServer(lambda conn: served)\n"
        "    await server.start(0)\n"
        "    conn = await protocol.connect(server.address, served)\n"
        "    assert conn.peer_is_local\n"
        "    assert await conn.request({'type': 'ask', 'n': 3}) == "
        "{'echo': 3}\n"
        "    await conn.close(); await server.close()\n"
        "asyncio.run(go())\n"
        "rpc = tracing.sums('rpc.')\n"
        "assert rpc['msgs_out'] == rpc['msgs_in'] == 4, rpc\n"
        "assert rpc['frames_out'] == rpc['frames_in'] >= 2, rpc\n"
        "assert rpc['bytes_out'] == rpc['bytes_in'] > 0, rpc\n"
        "assert rpc['out_s'] > 0 and rpc['in_s'] > 0, rpc\n"
        "assert not tracing.recording() and not tracing.sums('msgs.')\n"
        "assert 'jax' not in sys.modules, 'the sums imported jax'\n"
        # a frame packed while another thread is half way through
        # importing jax leaves all the same
        "import types\n"
        "sys.modules['jax'] = types.ModuleType('jax')\n"
        "assert tracing.recording() is False\n"
        "asyncio.run(go())\n"
        "assert tracing.sums('rpc.')['msgs_in'] == 8\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# ------------------------------------------------------------ the collector

def test_watch_gc_installs_one_callback_and_marks_a_full_pass(tmp_path):
    import gc
    tracing.watch_gc()
    tracing.watch_gc()
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = tracing.gc_stats()
    gc.disable()            # the one pass of the session is the forced one
    try:
        (name, start, end, stats), = _trace(tmp_path, gc.collect,
                                            keep_gc=True)
    finally:
        gc.enable()
    assert name == "rt:gc" and stats == {"generation": 2} and end > start
    after = tracing.gc_stats()
    assert after["passes"][2] == before["passes"][2] + 1
    assert after["pause_s"][2] > before["pause_s"][2]
    assert after["pause_max_s"] >= after["pause_s"][2] - before["pause_s"][2]
    assert after["pause_max_s"] >= before["pause_max_s"]


def test_watch_gc_only_counts_where_jax_is_not_imported():
    code = (
        "import gc, sys\n"
        "from ray_tpu.util import tracing\n"
        "tracing.watch_gc()\n"
        "gc.collect()\n"
        "gc.collect(0)\n"
        "stats = tracing.gc_stats()\n"
        "assert stats['passes'][2] == 1 and stats['passes'][0] >= 1, stats\n"
        "assert stats['pause_s'][2] > 0, stats\n"
        "assert stats['pause_max_s'] > 0, stats\n"
        "assert 'jax' not in sys.modules, 'watch_gc() imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
