"""``benchmark/costs_linear.py`` and the readers ISSUE 48 adds for a model
with linear-attention layers: what they count, that nothing read from a
step's own regions can pass 100%, and that each gives None where the program
(the parent's) says nothing."""

import pytest

from benchmark import (costs, costs_linear, decode_scopes, host_regions,
                       spec)

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HYBRID = spec.load_json("configs", "olmo-hybrid-7b-12l.json")
NEW_READERS = ["linear_state_device_ms", "linear_conv_device_ms",
               "linear_gate_norm_device_ms", "linear_state_roofline",
               "hybrid_step_hbm_roofline"]
STATE = 9 * 30 * 96 * 192 * 4              # a slot's states, float32
TRACED = {"programs": {"jit__decode": {"calls": 2, "device_s": 0.040},
                       "jit__prefill": {"calls": 1, "device_s": 0.030}}}


def run_of(trace=TRACED):
    return {"trace": trace, "cell": {"name": "x", "config": HYBRID},
            "peaks": PEAKS}


def test_the_family_counts_weights_pages_and_states():
    family = spec.load_part("families", "olmo_hybrid")
    assert family.layer_counts(HYBRID) == {"linear": 9, "full": 3}
    assert family.kv_bytes_per_token(HYBRID) == 3 * 2 * 3840 * 2 == 46080
    assert family.state_bytes_per_slot(HYBRID) == STATE == 19906560
    each = family.layer_params(HYBRID)
    assert round(each["linear"] / 1e6, 1) == 215.6
    assert round(each["full"] / 1e6, 1) == 185.8
    # the step reads every layer and the head, not the embedding
    assert family.decode_weight_params(HYBRID) == 9 * each["linear"] \
        + 3 * each["full"] + 3840 * 100352 + 3840
    assert family.linear_shape(HYBRID) == {
        "layers": 9, "heads": 30, "key_dim": 96, "value_dim": 192}


def test_a_state_step_reads_and_writes_every_live_state_once():
    cost = costs_linear.state_step(48, 9, 30, 96, 192)
    assert cost["bytes"] == 2 * 48 * STATE
    assert cost["flops"] == 7 * 48 * STATE / 4
    # memory bound: 1.91 GB at the memory's rate, 2.3 ms
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        2 * 48 * STATE / 819e9)
    assert costs_linear.state_step(24, 9, 30, 96, 192)["bytes"] == \
        cost["bytes"] / 2                  # parked slots are not counted


def test_the_scan_is_counted_in_whole_chunks_of_the_real_length():
    one = costs_linear.chunked_scan(64, 9, 30, 96, 192)
    assert costs_linear.chunked_scan(1, 9, 30, 96, 192)["flops"] == \
        one["flops"]
    assert costs_linear.chunked_scan(65, 9, 30, 96, 192)["flops"] == \
        2 * one["flops"]
    per_chunk = 2 * 64 * 64 * (3 * 96 + 2 * 192) + 64 ** 3 \
        + 6 * 64 * 96 * 192
    assert one["flops"] == 9 * 30 * per_chunk
    assert one["bytes"] == 9 * 4 * (64 * 30 * (2 * 96 + 2 * 192 + 2)
                                    + 30 * 96 * 192)
    # a prompt of 288: 0.18 GB of float32 rows outweigh its 26 GFLOP at
    # the peaks: a quarter of a millisecond
    cost = costs_linear.chunked_scan(288, 9, 30, 96, 192)
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        cost["bytes"] / 819e9)
    assert 2e-4 < cost["bytes"] / 819e9 < 3e-4


def test_a_hybrid_step_is_weights_pages_and_states():
    family = spec.load_part("families", "olmo_hybrid")
    weights = family.decode_weight_params(HYBRID)
    cost = costs_linear.hybrid_step(48, weights, 43200, 46080, STATE)
    assert cost["bytes"] == weights * 2 + 43200 * 46080 + 2 * 48 * STATE
    assert cost["flops"] == 2 * 48 * weights
    # the issue's count: 5.77 GB of weights, 1.99 of K/V at 900 live
    # positions a slot, 1.91 of states; memory bound
    assert 9.5e9 < cost["bytes"] < 9.8e9
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        cost["bytes"] / 819e9)
    assert 0.19 < 2 * 48 * STATE / cost["bytes"] < 0.21


def test_the_rooflines_read_the_regions_and_cannot_pass_100(monkeypatch):
    steps = [{"active": 48, "live_tokens": 43000},
             {"active": 46, "live_tokens": 41000}]
    monkeypatch.setattr(host_regions, "rows", lambda run, region: {
        "engine.decode.dispatch": steps}[region])
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: 4.0)
    run = run_of()
    state = spec.metric_reader("linear_state_roofline.hybrid")(run)
    want = 100 * costs.least_seconds(costs_linear.state_step(
        47, 9, 30, 96, 192), PEAKS) / 4e-3
    assert state == pytest.approx(want) and 50 < state < 60
    step = spec.metric_reader("hybrid_step_hbm_roofline.hybrid")(run)
    assert 50 < step < 65                  # 11.6 ms of bytes over 20 ms
    # the floors are the least the chip could take: at that time, 100%
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: 4.0 * state / 100)
    assert spec.metric_reader("linear_state_roofline.hybrid")(run) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_run_with_nothing_to_read_gives_none(name, monkeypatch):
    """No trace (a CPU rehearsal), and a traced parent whose program has no
    such scope and whose engine's regions are as before."""
    read = spec.metric_reader(name + ".hybrid")
    assert read(run_of({})) is None
    monkeypatch.setattr(host_regions, "rows", lambda run, region: None)
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: None)
    assert read(run_of()) is None
    assert read(run_of({"programs": {}})) is None
