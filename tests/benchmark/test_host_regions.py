"""The readers of what the program says of itself in a profile: the split
of device-idle gaps by the host's regions, the clock check, and the
protobuf reader of the operations' ``op_name``."""

import os
import sys

import pytest

from benchmark import host_regions as hr
from benchmark import spec

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))     # tests/engine_trace.py

D, F = "rt:engine.decode.dispatch", "rt:engine.decode.fetch"
MS = 1e-3


def step(at, shift=0.0):
    """One decode step as the engine marks it, the device running
    ``jit__decode`` over [at + 1, at + 41] ms and ``jit__argmax`` right
    after: (regions, programs), times in seconds."""
    t = at * MS + shift
    regions = [
        ("rt:engine.deliver", t - 0.6 * MS, t - 0.5 * MS,
         {"tokens": 2, "resume_us": 300}),
        ("rt:engine.schedule", t - 0.5 * MS, t - 0.4 * MS,
         {"active": 2, "waiting": 0}),
        (D, t, t + 1.5 * MS, {"active": 2, "submit_us": 200}),
        (F, t + 1.5 * MS, t + 43 * MS, {}),
        ("rt:stream.yield", t + 2 * MS, t + 2 * MS,
         {"index": 1, "ack_us": 900}),
    ]
    programs = [((at + 1) * MS, (at + 41) * MS, hr.DECODE),
                ((at + 41) * MS, (at + 41.1) * MS, hr.ARGMAX)]
    return regions, programs


def steps(n, shift=0.0):
    regions, programs = [], []
    for i in range(n):
        r, p = step(44 * i, shift)
        regions += r
        programs += p
    return regions, programs


def test_gaps_are_split_by_kind_and_sum_to_the_gaps():
    regions, programs = steps(3)
    gaps = hr.program_gaps(programs)
    # after each argmax until the next decode: 44 - 41.1 + 1 ms
    assert gaps == pytest.approx(
        [(41.1 * MS, 45 * MS), (85.1 * MS, 89 * MS)])
    kinds = hr.split_gaps(gaps, hr.kind_intervals(regions))
    assert sum(kinds.values()) == pytest.approx(2 * 3.9 * MS)
    per_gap = {k: v / 2 / MS for k, v in kinds.items()}
    # fetch ends at 43, resume is the 0.3 before deliver at 43.4, deliver
    # and schedule 0.1 each, submit the 0.2 before dispatch at 44, and
    # dispatch until the program starts at 45; 43.0-43.1 and 43.6-43.8
    # are nobody's
    assert per_gap == pytest.approx({
        "fetch": 1.9, "resume": 0.3, "deliver": 0.1, "schedule": 0.1,
        "submit": 0.2, "dispatch": 1.0, "unnamed": 0.3})


def test_overlap_is_counted_once_and_the_innermost_wins():
    gaps = [(0.0, 10.0)]
    intervals = [(1.0, 9.0, "dispatch"), (2.0, 4.0, "fetch"),
                 (3.0, 6.0, "deliver")]
    kinds = hr.split_gaps(gaps, intervals)
    assert kinds["unnamed"] == 2.0
    assert kinds["fetch"] == 1.0        # 2-3; from 3 on deliver is inside
    assert kinds["deliver"] == 3.0
    assert kinds["dispatch"] == 4.0     # 1-2 and 6-9
    assert sum(kinds.values()) == 10.0


def test_a_prefill_counts_as_dispatch_and_its_crossing_as_submit():
    regions = [("rt:engine.prefill", 1.0, 3.0,
                {"prompt_len": 5, "padded_len": 16, "waited_us": 600_000,
                 "submit_us": 250_000})]
    kinds = hr.split_gaps([(0.0, 2.0)], hr.kind_intervals(regions))
    assert (kinds["submit"], kinds["dispatch"], kinds["unnamed"]) == \
        (0.25, 1.0, 0.75)


def test_clock_check_passes_on_one_clock_and_fails_when_shifted():
    regions, programs = steps(5)
    assert hr.clock_check(regions, programs) == (5, 5)
    # a device timeline a little off the host's is still one clock
    assert hr.clock_check(*steps(5, shift=1.2 * MS)) == (5, 5)
    for shift in (-8 * MS, 5 * MS, 20 * MS, 1.0):
        shifted, _ = steps(5, shift)
        checked, in_order = hr.clock_check(shifted, programs)
        assert in_order < max(checked, 1), shift
    # no decode step in the trace: nothing to check, nothing reported
    assert hr.clock_check([], programs) == (0, 0)


def test_device_lag_is_the_most_a_decode_precedes_its_enqueue():
    _, programs = steps(4)
    # the runtime enqueues each decode 0.2-0.3 ms before it starts, and
    # its argmax right after; then the device's timeline is read 1.5 ms
    # early
    enqueues = [p[0] - (0.2 + 0.05 * (i % 3)) * MS
                for i, p in enumerate(programs) if p[2] == hr.DECODE]
    enqueues += [e + 0.4 * MS for e in enqueues]
    assert hr.device_lag(enqueues, programs) == 0.0
    early = [(s - 1.5 * MS, e - 1.5 * MS, n) for s, e, n in programs]
    lag = hr.device_lag(enqueues, early)
    assert lag == pytest.approx(1.3 * MS)     # 1.5 less the least 0.2
    moved = [(s + lag, e + lag, n) for s, e, n in early]
    assert hr.device_lag(enqueues, moved) == pytest.approx(0.0, abs=1e-12)
    assert hr.program_gaps(moved) == pytest.approx(
        [(a + lag, b + lag) for a, b in hr.program_gaps(early)])
    # a runtime without the event: nothing to go by, nothing moved
    assert hr.device_lag([], early) == 0.0


def test_a_step_cut_by_the_trace_is_not_counted():
    regions, programs = steps(4)
    # the trace began after the first dispatch and ended inside the last
    # step: its decode ran, its argmax and fetch are missing
    regions = [r for r in regions[3:]
               if not (r[0] == F and r[1] > 3 * 44 * MS)]
    assert hr.clock_check(regions, programs[:-1]) == (2, 2)


# ------------------------------------------------------ the protobuf reader

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane(name, metadata, stat_names):
    """An XPlane with event metadata ``{id: (name, {stat id: value})}``: a
    str value is stored as one, an int as a reference to a stat's name."""
    out = field(2, name) + field(3, b"\x08\x01")       # a line, skipped
    for key, (text, stats) in metadata.items():
        meta = field(1, key) + field(2, text)
        for stat_id, value in stats.items():
            meta += field(5, field(1, stat_id) + (
                field(5, value) if isinstance(value, str)
                else field(7, value)))
        out += field(4, field(1, key) + field(2, meta))
    for key, text in stat_names.items():
        out += field(5, field(1, key) + field(2, field(1, key)
                                              + field(2, text)))
    return field(1, out)


def test_op_names_reads_tf_op_of_the_device_planes_only(tmp_path):
    names = {3: "tf_op", 4: "source", 9: "jit(f)/optimizer/mul:"}
    space = plane("/device:TPU:0", {
        1: ("%fusion.1 = f32[4] fusion()", {4: "gpt.py:1",
                                            3: "jit(f)/jvp(ce_head)/dot"}),
        2: ("%multiply.2 = f32[4] multiply()", {3: 9}),
        300: ("%copy.3 = f32[4] copy()", {4: "gpt.py:2"}),
    }, names) + plane("/host:CPU", {
        1: ("rt:engine.schedule", {3: "not an operation"})}, names)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert hr.op_names(str(path)) == {
        "%fusion.1 = f32[4] fusion()": "jit(f)/jvp(ce_head)/dot",
        "%multiply.2 = f32[4] multiply()": "jit(f)/optimizer/mul:"}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/transpose(jvp(ce_head))/while/body/dot_general", "ce_head"),
    ("jit(step)/jvp(ce_head)/while/body/squeeze", "ce_head"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(_decode)/while/body/paged_read/gather", "paged_read"),
    ("jit(_prefill)/while/body/paged_append/scatter", "paged_append"),
    ("jit(step)/my_optimizer_state/mul", None),
    ("jit(step)/jvp()/while/body/closed_call/flash_fwd/pallas_call", None),
    ("", None)])
def test_scope_of_an_op_name(op_name, scope):
    assert hr.scope_of(op_name) == scope


# ------------------------------------------------------ what metrics call

def run_of(cell, trace):
    bench = spec.load_benchmark()
    return {"cell": spec.load_cell(bench, cell), "trace": trace,
            "peaks": spec.peaks_for("TPU v5 lite")}


NEW_METRICS = [
    *(f"host_gap_{kind}_ms" for kind in hr.KINDS), "stream_yield_ack_ms",
    "prefill_useful_share.chat", "prefill_useful_share.batch",
    "engine_queue_wait_ms", "flash_fwd_roofline", "flash_dq_roofline",
    "flash_dkv_roofline", "ce_head_device_ms", "optimizer_device_ms",
    "paged_kv_device_ms"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_none_on_an_empty_trace(name):
    metric, = [m for m in spec.load_benchmark()["per_layer"]
               if m["name"] == name]
    for cell in metric["workloads"]:
        assert spec.metric_reader(name)(run_of(cell, {})) is None


def test_kernel_shares_read_the_named_kernels():
    steps_, layers = 5, 24
    trace = {"steps": steps_, "ops": {
        "flash_fwd.16 bf16[192,1024,64] tpu_custom_call":
            {"calls": steps_ * layers, "device_s": 0.087},
        "flash_fwd.17 bf16[192,1024,64] tpu_custom_call":
            {"calls": steps_ * layers, "device_s": 0.088},
        "flash_dq.9 bf16[192,1024,64] tpu_custom_call":
            {"calls": steps_ * layers, "device_s": 0.105},
        "fusion.1 bf16[2,3] fusion": {"calls": 7, "device_s": 1.0}}}
    run = run_of("train-gpt2-medium-1chip", trace)
    assert hr.kernel(run, "flash_fwd") == {
        "calls": 2 * steps_ * layers, "device_s": pytest.approx(0.175)}
    assert hr.kernel(run, "flash_dkv") is None
    fwd = spec.metric_reader("flash_fwd_roofline")(run)
    dq = spec.metric_reader("flash_dq_roofline")(run)
    assert 0 < fwd < dq < 100
    assert spec.metric_reader("flash_dkv_roofline")(run) is None
    # the program before the kernels had names: nothing to read
    old = {"ops": {"checkpoint.19 bf16[192,1024,64] tpu_custom_call":
                   {"calls": 120, "device_s": 0.1}}, "steps": 5}
    assert spec.metric_reader("flash_fwd_roofline")(
        run_of("train-gpt2-medium-1chip", old)) is None


# ------------------------- the host half, on a trace the engine itself wrote

@pytest.fixture(scope="module")
def engine_profile():
    import engine_trace
    run = engine_trace.run()
    return run, hr.read_profile(run["path"])


def test_the_engines_regions_are_read_with_their_attributes(engine_profile):
    import engine_trace
    _, profile = engine_profile
    names = [r[0] for r in profile["regions"]]
    assert names.count("rt:engine.prefill") == len(engine_trace.PROMPTS)
    assert names.count(D) == names.count(F) == \
        max(engine_trace.NEW_TOKENS) - 1
    prefills = [a for n, _, _, a in profile["regions"]
                if n == "rt:engine.prefill"]
    assert sum(p["prompt_len"] for p in prefills) == \
        sum(len(p) for p in engine_trace.PROMPTS)
    assert {p["padded_len"] for p in prefills} == \
        {engine_trace.MAX_PROMPT_LEN}
    # the CPU has no device plane: no programs, no scopes, and so no gaps
    assert profile["programs"] == [] and profile["kinds"] is None
    assert profile["lag_s"] == 0.0
    assert set(profile["scopes"].values()) == {0.0}


def test_the_engines_regions_split_a_gap_laid_over_them(engine_profile):
    _, profile = engine_profile
    regions = profile["regions"]
    dispatches = [r for r in regions if r[0] == D]
    fetches = [r for r in regions if r[0] == F]
    # the stretch of host work between two steps, as if the device had
    # finished when the first fetch began and started when the second
    # dispatch ended
    gap = (fetches[0][1], dispatches[1][2])
    kinds = hr.split_gaps([gap], hr.kind_intervals(regions))
    assert sum(kinds.values()) == pytest.approx(gap[1] - gap[0])
    for kind in ("fetch", "resume", "deliver", "schedule", "submit",
                 "dispatch"):
        assert kinds[kind] > 0, kinds
    # the regions and the two crossings cover the stretch
    assert kinds["unnamed"] < 0.2 * (gap[1] - gap[0]), kinds
