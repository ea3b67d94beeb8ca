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


def _trace(tmp_path, body):
    import glob
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    return _host_regions(path)


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
    names = [name.removeprefix("rt:engine.")
             for name, _, _, stats in engine_run["regions"]
             if not (name == "rt:engine.schedule"
                     and stats["active"] == stats["waiting"] == 0)]
    steps = max(engine_trace.NEW_TOKENS) - 1
    # a prefill is followed by the delivery of its first token; what is
    # left is the decode steps
    rest, i = [], 0
    while i < len(names):
        if names[i] == "prefill":
            assert names[i + 1] == "deliver", names
            i += 2
        else:
            rest.append(names[i])
            i += 1
    assert names.count("prefill") == len(engine_trace.PROMPTS)
    step = ["schedule", "decode.dispatch", "decode.fetch", "deliver"]
    # the pass that admits and prefills builds the batch in a second
    # ``schedule``; every later step has exactly one
    assert rest == ["schedule"] + step * steps, names


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
    # both sequences decode until the shorter one is done
    short, long = sorted(engine_trace.NEW_TOKENS)
    assert [s["active"] for _, _, s in dispatches] == \
        [2] * (short - 1) + [1] * (long - short)
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
    assert (schedules[0][2]["active"], schedules[0][2]["waiting"]) == (0, 2)
    # the crossing into the exec thread starts where ``schedule`` ends
    # (attributes are whole microseconds)
    for (start, _, stats), (_, sched_end, _) in zip(dispatches,
                                                    schedules[1:]):
        assert sched_end - 2e3 <= start - stats["submit_us"] * 1e3 \
            <= sched_end + 100e3


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
