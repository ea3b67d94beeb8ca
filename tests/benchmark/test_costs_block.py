"""``benchmark/costs_block.py`` and the readers ISSUE 40 adds for a model
that generates by diffusion over blocks: what they count, that nothing read
from a step's own regions can pass 100%, and that each gives None where the
program (the parent's) says nothing."""

import pytest

from benchmark import (costs, costs_block, decode_scopes, host_regions,
                       moe_scopes, spec)

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SDAR = spec.load_json("configs", "sdar-30b-a3b-chat-6l.json")
NEW_READERS = ["block_tokens_per_slot_step", "block_dropped_share",
               "block_unmask_device_ms", "lm_head_device_ms",
               "block_read_roofline", "block_step_hbm_roofline"]
KV = 6 * 2 * 4 * 128 * 2                  # a cached position, all layers


TRACED = {"programs": {"jit__decode": {"calls": 2, "device_s": 0.030}}}


def run_of(trace=TRACED):
    return {"trace": trace, "cell": {"name": "x", "config": SDAR},
            "peaks": PEAKS}


def test_the_family_counts_a_position_and_a_steps_matrices():
    family = spec.load_part("families", "sdar")
    assert family.kv_bytes_per_token(SDAR) == KV == 12288
    attention = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
    assert family.step_weight_params(SDAR) == \
        6 * (attention + 2048 * 128) + 2048 * 151936
    assert family.moe_shape(SDAR) == {"layers": 6, "experts": 128,
                                      "hidden": 2048, "width": 768}


def test_block_read_counts_the_held_positions_bytes_alone():
    cost = costs_block.block_read(30000, KV)
    assert cost == {"flops": 0.0, "bytes": 30000.0 * 12288}
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        30000 * 12288 / 819e9)


def test_block_step_adds_matrices_touched_experts_and_the_cache():
    family = spec.load_part("families", "sdar")
    weights = family.step_weight_params(SDAR)
    cost = costs_block.block_step(128, weights, 6144, 700, 2048, 768, 2,
                                  30000, KV)
    assert cost["bytes"] == weights * 2 + 700 * 3 * 2048 * 768 * 2 \
        + 30000 * 12288
    assert cost["flops"] == 6144 * 6 * 2048 * 768 + 2 * 128 * weights
    # memory bound by a wide margin: the step's floor is its bytes
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        cost["bytes"] / 819e9)
    # every expert of every layer touched: the issue's 8.1 GB a step
    full = costs_block.block_step(128, weights, 6144, 768, 2048, 768, 2,
                                  0, KV)
    assert 8.0e9 < full["bytes"] < 8.3e9
    # an expert nobody chose is not counted; float32 storage doubles them
    none = costs_block.block_step(128, weights, 0, 0, 2048, 768, 2, 0, KV)
    assert none["bytes"] == weights * 2
    wide = costs_block.block_step(128, weights, 6144, 700, 2048, 768, 4,
                                  30000, KV)
    assert wide["bytes"] - cost["bytes"] == 700 * 3 * 2048 * 768 * 2


@pytest.mark.parametrize("name", NEW_READERS)
def test_no_trace_or_a_program_without_blocks_gives_none(monkeypatch, name):
    read = spec.metric_reader(name + ".sdar")
    for trace in ({}, None):
        assert read(run_of(trace)) is None
    # the parent's regions: no block attributes, no such scopes
    monkeypatch.setattr(host_regions, "profile", lambda run: {"regions": [
        ("rt:engine.decode.dispatch", 0.0, 0.001,
         {"active": 2, "live_tokens": 100, "gathered_tokens": 512}),
        ("rt:engine.deliver", 0.002, 0.003, {"tokens": 2})]})
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: (
        (1e-3, "jit(_decode)/while/body/closed_call/paged_read/gather"),))
    from benchmark import replica
    monkeypatch.setattr(replica, "find_xplane", lambda folder: "a.pb")
    assert read(run_of()) is None


def regions(steps, delivers):
    return {"regions": [("rt:engine.decode.dispatch", 0.0, 0.001, s)
                        for s in steps]
            + [("rt:engine.deliver", 0.0, 0.001, d) for d in delivers]}


def test_tokens_per_slot_step_and_the_dropped_share(monkeypatch):
    """Five steps of 32 slots at the static schedule: four denoise, one
    commit of 4 positions a slot; three tail tokens and one stray block of
    four reach nobody."""
    delivers = [{"tokens": 0, "dropped_tail": 0, "dropped_stray": 0,
                 "denoise_slots": 32, "commit_slots": 0}] * 4 + [
        {"tokens": 121, "dropped_tail": 3, "dropped_stray": 4,
         "denoise_slots": 0, "commit_slots": 31},
        {"tokens": 0}]                   # a dispatch that fetched nothing
    monkeypatch.setattr(host_regions, "profile",
                        lambda run: regions([], delivers))
    assert spec.metric_reader("block_tokens_per_slot_step.sdar")(
        run_of()) == pytest.approx(121 / (4 * 32 + 31))
    assert spec.metric_reader("block_dropped_share.sdar")(
        run_of()) == pytest.approx(100 * 7 / 128)
    # whole blocks and nothing dropped: B / (T + 1)
    whole = [{"tokens": 0, "dropped_tail": 0, "dropped_stray": 0,
              "denoise_slots": 32, "commit_slots": 0}] * 4 + [
        {"tokens": 128, "dropped_tail": 0, "dropped_stray": 0,
         "denoise_slots": 0, "commit_slots": 32}]
    monkeypatch.setattr(host_regions, "profile",
                        lambda run: regions([], whole))
    assert spec.metric_reader("block_tokens_per_slot_step.sdar")(
        run_of()) == pytest.approx(0.8)
    assert spec.metric_reader("block_dropped_share.sdar")(run_of()) == 0.0


def test_scope_readers_and_the_rooflines(monkeypatch):
    ops = ((4e-3, "jit(_decode)/while/body/closed_call/paged_read/gather"),
           (1e-3, "jit(_decode)/block_unmask/sort"),
           (2e-3, "jit(_decode)/block_unmask/reduce_sum"),
           (5e-3, "jit(_decode)/lm_head/dot_general"),
           (9e-3, "jit(_decode)/while/body/closed_call/moe_experts/x"))
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: ops)
    from benchmark import replica
    monkeypatch.setattr(replica, "find_xplane", lambda folder: "a.pb")
    steps = [{"active": 32, "block_len": 4, "live_tokens": 24000,
              "gathered_tokens": 49152},
             {"active": 30, "block_len": 4, "live_tokens": 26000,
              "gathered_tokens": 49152}]
    monkeypatch.setattr(host_regions, "profile",
                        lambda run: regions(steps, []))
    run = run_of()
    assert spec.metric_reader("block_unmask_device_ms.sdar")(run) == \
        pytest.approx(1.5)
    assert spec.metric_reader("lm_head_device_ms.sdar")(run) == \
        pytest.approx(2.5)
    least = 25000 * 12288 / 819e9                      # a step's read
    assert spec.metric_reader("block_read_roofline.sdar")(run) == \
        pytest.approx(100 * least / 2e-3, rel=1e-6)
    routed = [{"assignments": 6144, "experts_hit": 700, "load_max": 300,
               "weight_itemsize": 2},
              {"assignments": 5760, "experts_hit": 680, "load_max": 280,
               "weight_itemsize": 2}]
    monkeypatch.setattr(moe_scopes.host_regions, "rows",
                        lambda run, region: routed
                        if region == "engine.decode.moe" else steps
                        if region == "engine.decode.dispatch" else None)
    family = spec.load_part("families", "sdar")
    want = costs.least_seconds(costs_block.block_step(
        124, family.step_weight_params(SDAR), 5952, 690, 2048, 768, 2,
        25000, KV), PEAKS)
    got = spec.metric_reader("block_step_hbm_roofline.sdar")(run)
    assert got == pytest.approx(100 * want / 0.015, rel=1e-6)
    assert 0 < got < 100
