"""``benchmark/costs_ssm.py``, the family's counts and the unlisted readers
ISSUE 61 adds for a model of Mamba-2 and grouped-query layers with experts in
every layer of which the program holds a share: what they count, that nothing
read from a step's own regions can pass 100%, and that each gives None where
the program (the parent's) says nothing."""

import pytest

from benchmark import (costs, costs_kda, costs_linear, costs_ssm,
                       decode_scopes, host_regions, moe_scopes,
                       prefill_scopes, spec)
from benchmark.tools import read_profile

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GRANITE = spec.load_json("configs", "granite-4.0-h-small-10l.json")
READERS = ["ssm_state_decode_ms", "ssm_conv_decode_ms",
           "ssm_gate_norm_decode_ms", "ssm_proj_decode_ms",
           "ssm_scan_prefill_ms", "ssm_state_roofline",
           "granite_step_hbm_roofline"]
STATE = 9 * 128 * 64 * 128 * 4             # a slot's states, float32
TRACED = {"programs": {"jit__decode": {"calls": 2, "device_s": 0.050},
                       "jit__prefill": {"calls": 1, "device_s": 0.030}}}


def run_of(trace=TRACED):
    return {"trace": trace, "cell": {"name": "x", "config": GRANITE},
            "peaks": PEAKS}


def test_the_family_counts_weights_pages_and_states():
    family = spec.load_part("families", "granite_hybrid")
    assert family.layer_counts(GRANITE) == {"ssm": 9, "full": 1}
    assert family.kv_bytes_per_token(GRANITE) == 2 * 8 * 128 * 2 == 4096
    assert family.state_bytes_per_slot(GRANITE) == STATE == 37748736
    assert family.tail_bytes_per_slot(GRANITE) == 9 * 3 * 8448 * 2
    each = family.layer_params(GRANITE)
    # the issue's arithmetic: 102.29M a Mamba mixer, 41.94M the attention
    # mixer, 9.437M an expert, 18.87M the shared one, 0.29M a router
    assert round(each["ssm"] / 1e6, 2) == 102.29
    assert round(each["full"] / 1e6, 2) == 41.94
    assert each["expert"] == 3 * 4096 * 768
    assert each["shared"] == 2 * each["expert"]
    assert round(each["router"] / 1e6, 2) == 0.29
    # a step reads the experts that were HIT
    assert family.decode_weight_params(GRANITE, 10 * 36) \
        - family.decode_weight_params(GRANITE, 10 * 30) \
        == 10 * 6 * each["expert"]
    # with every held expert hit it is the tree (the table once: tied)
    import jax
    model = family.program_config(GRANITE, 1536)
    stored = jax.eval_shape(lambda: family.init(jax.random.PRNGKey(0), model))
    assert family.weight_params(GRANITE) == sum(
        a.size for a in jax.tree.leaves(stored))
    assert family.linear_shape(GRANITE) == {
        "layers": 9, "heads": 128, "key_dim": 128, "value_dim": 64}
    assert family.moe_shape(GRANITE) == {
        "layers": 10, "experts": 36, "hidden": 4096, "width": 768}


def test_a_state_step_reads_and_writes_every_live_state_once():
    cost = costs_ssm.state_step(64, 9, 128, 128, 64)
    assert cost["bytes"] == 2 * 64 * STATE
    assert cost["bytes"] == costs_linear.state_step(64, 9, 128, 128,
                                                    64)["bytes"]
    assert cost["flops"] == 5 * 64 * STATE / 4
    # bound by the bytes, four times over
    assert cost["bytes"] / PEAKS["hbm_bytes_per_s"] \
        > 4 * cost["flops"] / PEAKS["bf16_flops_per_s"]


def test_the_chunked_scan_counts_real_length_in_whole_chunks():
    one = costs_ssm.chunked_scan(128, 9, 128, 128, 64)
    assert costs_ssm.chunked_scan(1, 9, 128, 128, 64)["flops"] == one["flops"]
    assert costs_ssm.chunked_scan(129, 9, 128, 128, 64)["flops"] \
        == 2 * one["flops"]
    # a chunk: the q . k matrix ONCE for all heads, then each head's decay,
    # values and two products with the state
    assert one["flops"] == 9 * (2 * 128 * 128 * 128 + 128 * (
        2 * 128 * 128 + 2 * 128 * 128 * 64 + 4 * 128 * 128 * 64 + 128 * 64))
    rows = 128 * (128 * (2 * 64 + 2) + 2 * 128)
    assert one["bytes"] == 9 * 4 * (rows + 128 * 128 * 64)


def test_the_whole_steps_bytes_are_the_issues():
    """64 live slots, every held expert hit, ~600 live positions a slot:
    weights 9.52 GB, states 4.83 GB, K/V 0.16 GB."""
    family = spec.load_part("families", "granite_hybrid")
    cost = costs_kda.step(
        64, family.decode_weight_params(GRANITE, 360), 64 * 600,
        family.kv_bytes_per_token(GRANITE),
        family.state_bytes_per_slot(GRANITE))
    assert round(cost["bytes"] / 1e9, 1) == 14.5
    assert round(2 * 64 * STATE / 1e9, 2) == 4.83
    assert 17 < 1e3 * costs.least_seconds(cost, PEAKS) < 18.5


def test_the_rooflines_read_what_the_regions_say(monkeypatch):
    steps = [{"active": 64, "live_tokens": 64 * 600},
             {"active": 32, "live_tokens": 32 * 600}]
    monkeypatch.setattr(host_regions, "rows", lambda run, name: steps
                        if name == "engine.decode.dispatch" else None)
    monkeypatch.setattr(moe_scopes, "decode_routing", lambda run: {
        "steps": 2, "experts_hit": 2 * 300, "assignments": 1})
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: 8.0 if scopes == (
                            "linear_state",) else None)
    run = run_of()
    share = read_profile.reader("ssm_state_roofline")(run)
    # 48 live slots on average: 48 x 2 x 37.75 MB at 819 GB/s over 8 ms
    assert share == pytest.approx(
        100 * (2 * 48 * STATE / 819e9) / 8e-3)
    assert 0 < share < 100
    whole = read_profile.reader("granite_step_hbm_roofline")(run)
    assert 0 < whole < 100
    # a step that took the least time reads 100, never more
    least = costs.least_seconds(costs_ssm.state_step(48, 9, 128, 128, 64),
                                PEAKS)
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: 1e3 * least)
    assert read_profile.reader("ssm_state_roofline")(run) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_on_a_program_that_says_nothing(
        name, monkeypatch):
    """The parent's traced run of an old cell, and a CPU rehearsal with no
    device plane: None, and no raise."""
    monkeypatch.setattr(host_regions, "rows", lambda run, name: None)
    monkeypatch.setattr(moe_scopes, "decode_routing", lambda run: None)
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: None)
    monkeypatch.setattr(prefill_scopes, "prefill_scope_ms",
                        lambda run, scopes: None)
    assert read_profile.reader(name)(run_of()) is None
    assert read_profile.reader(name + ".granite")(run_of({})) is None
