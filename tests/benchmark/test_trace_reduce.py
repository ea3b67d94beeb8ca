"""The reduction from device events to what the per-layer metrics read."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

M, O = tr.MODULES, tr.OPS
HERE = os.path.dirname(os.path.abspath(__file__))


def test_short_op_keeps_instruction_result_and_kind():
    assert tr.short_op(
        "%fusion.159 = bf16[2,16,14336]{2,1,0:T(8,128)(2,1)} fusion("
        "bf16[2,16,4096]{2,1,0} %x), kind=kLoop") == \
        "fusion.159 bf16[2,16,14336] fusion"
    kernel = tr.short_op(
        "%closed_call.16 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, "
        "f32[192,1024,8]{2,1,0:T(8,128)}) custom-call(bf16[192,1024,64]"
        '{2,1,0} %bitcast.459), custom_call_target="tpu_custom_call"')
    assert kernel == "closed_call.16 bf16[192,1024,64] tpu_custom_call"
    assert tr.op_kind(kernel) == "tpu_custom_call"
    assert tr.op_kind(tr.short_op("%while.14")) == "op"
    assert tr.program_name("jit__decode(4017382939)") == "jit__decode"


def test_interval_arithmetic():
    cover = tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert cover == [[0, 2.5], [3, 4]]
    assert tr.length(cover) == 3.5
    assert tr.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == 6
    assert tr.subtract([[0, 1], [5, 6]], [[0.5, 5.5]]) == 1.0
    assert tr.subtract([[0, 1]], []) == 1


def test_an_operation_is_charged_its_own_time_only():
    ops = [(0, 10, "while"), (1, 3, "a"), (3, 5, "b"), (3.5, 4, "c"),
           (11, 12, "d")]
    assert tr.self_times(ops) == [(6, "while"), (2, "a"), (1.5, "b"),
                                  (0.5, "c"), (1, "d")]


def synthetic():
    return [
        # device 0: a decode step, a gap, an argmax; an all-reduce that a
        # fusion overlaps for 0.1 s of its 0.3 s
        (0, M, "jit__decode", 0.0, 1.0),
        (0, O, "fusion.1 bf16[2,3] fusion", 0.0, 0.4),
        (0, O, "all-reduce.2 f32[4] all-reduce", 0.5, 0.3),
        (0, O, "fusion.9 f32[4] fusion", 0.6, 0.1),
        (0, M, "jit__argmax", 1.5, 0.5),
        (0, O, "copy.3 s32[16] copy", 1.5, 0.5),
        # device 1: busy throughout
        (1, M, "jit__decode", 0.0, 2.0),
        (1, O, "fusion.1 bf16[2,3] fusion", 0.0, 2.0),
    ]


def test_busy_union_programs_ops_and_collectives():
    r = tr.reduce_events(synthetic())
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(2.0)
    # device 0 is busy 0.4 + 0.3 + 0.5 (the overlapped fusion adds nothing)
    assert r["busy_s"] == pytest.approx((1.2 + 2.0) / 2)
    assert r["collective_s"] == pytest.approx(0.3 / 2)
    assert r["collective_exposed_s"] == pytest.approx(0.2 / 2)
    assert r["programs"] == {
        "jit__decode": {"calls": 1, "device_s": pytest.approx(1.0)},
        "jit__argmax": {"calls": 1, "device_s": pytest.approx(0.5)}}
    assert r["ops"]["copy.3 s32[16] copy"] == {
        "calls": 1, "device_s": pytest.approx(0.5)}
    # the all-reduce is charged less what ran inside its span
    assert r["ops"]["all-reduce.2 f32[4] all-reduce"]["device_s"] == \
        pytest.approx(0.2)


def test_idle_gaps_are_attributed():
    r = tr.reduce_events(synthetic())
    assert r["gaps"] == {
        "inside_jit__decode": pytest.approx(0.3),
        "after_jit__decode_before_jit__argmax": pytest.approx(0.5)}
    assert r["program_gap_s"] == pytest.approx(0.5)
    b = tr.breakdown(r)
    assert b["idle_gaps"][0] == ["after_jit__decode_before_jit__argmax",
                                 pytest.approx(0.5)]
    assert b["device_ops"][0][0] == "copy.3_s32_16_copy"
    assert len(b["device_ops"]) <= 10


def test_nothing_to_read_gives_nothing():
    assert tr.reduce_events([]) == {}
    assert tr.breakdown({}) == {"device_ops": [], "idle_gaps": []}


@pytest.fixture(scope="module")
def recorded():
    """A prefill, its argmax chain and the decode step after it, of
    serve-chat-steady on a v5e chip, as ``read_xplane`` listed them
    (PR 23)."""
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return [tuple(e) for e in json.load(f)]


def test_recorded_trace(recorded):
    r = tr.reduce_events(recorded)
    assert r["devices"] == 1
    programs = r["programs"]
    assert programs["jit__prefill"]["calls"] == 1
    assert programs["jit__decode"]["calls"] == 1
    assert programs["jit__argmax"]["calls"] == 2
    # the prefill of 2048 padded positions takes ~0.11 s on the device and
    # a decode step ~0.04 s: the recording keeps the chip's proportions
    prefill = programs["jit__prefill"]["device_s"]
    decode = programs["jit__decode"]["device_s"] / \
        programs["jit__decode"]["calls"]
    assert 0.05 < prefill < 0.25 and 0.02 < decode < 0.08
    assert 0 < r["busy_s"] <= r["window_s"]
    # operations' own times add up to the busy time: nothing counted twice
    assert sum(v["device_s"] for v in r["ops"].values()) == \
        pytest.approx(r["busy_s"], rel=1e-3)
    # the host's round trip between a step's argmax and the next step
    assert any(k.startswith("after_jit__argmax_before_")
               for k in r["gaps"])
    kinds = {tr.op_kind(k) for k in r["ops"]}
    assert {"fusion", "convert", "while"} <= kinds
