"""The readers of what the program says of itself in a profile: the host's
regions with their attributes, the protobuf reader of the operations'
``op_name``, the kernels by name, and that every per-layer reader of
BENCHMARK.json gives ``None`` where the trace is empty."""

import os
import sys

import pytest

from benchmark import host_regions as hr
from benchmark import spec

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))     # tests/engine_trace.py

D, F = "rt:engine.decode.dispatch", "rt:engine.decode.fetch"


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
    """A run of the cell as a generator hands it to the readers, with
    nothing in it but ``trace``: no request, no poll, no step."""
    cell = spec.load_cell(spec.load_benchmark(), cell)
    return {"cell": cell, "trace": trace,
            "peaks": spec.peaks_for("TPU v5 lite"), "requests": [],
            "loop_lag_ms": [], "window": (0.0, 1.0),
            "replica": {"replica_ttft_s": {}, "polls": [], "trace": trace,
                        "decode_steps": 0, "max_batch": cell["config"].get(
                            "engine", {}).get("max_batch")}}


# computed from the window's steps, which a run always has: no reader of
# the trace, and never None
NOT_OF_THE_TRACE = {"train_mfu"}
PER_LAYER = [(m["name"], cell) for m in spec.load_benchmark()["per_layer"]
             if m["name"] not in NOT_OF_THE_TRACE for cell in m["workloads"]]


@pytest.mark.parametrize("name, cell", PER_LAYER)
def test_reader_gives_none_on_an_empty_trace(name, cell):
    """Every per-layer metric, in every cell that lists it: a reader that
    finds nothing to read returns nothing (never 0, never an exception), so
    that one which would raise on the chip is found here."""
    assert spec.metric_reader(name)(run_of(cell, {})) is None


def test_every_per_layer_metric_names_its_cells():
    assert all(m.get("workloads")
               for m in spec.load_benchmark()["per_layer"])


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
    # the CPU has no device plane: no operations, so nothing under a scope
    assert set(profile) == {"regions", "scopes"}
    assert set(profile["scopes"].values()) == {0.0}
