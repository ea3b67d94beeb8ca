"""``benchmark/costs_dsa.py`` and the unlisted readers of the sparse
attention's scopes, regions and rooflines, on what a profile would hold."""

import pytest

from benchmark import costs, costs_dsa, host_regions, spec
from benchmark.tools import read_profile

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = spec.load_json("configs", "deepseek-v3.2-exp-5l.json")
FAMILY = spec.load_part("families", CONFIG["family"])
SHAPE = FAMILY.dsa_shape(CONFIG)


def test_a_steps_selection_by_hand():
    """16 sequences of 10,000 positions that keep 2,048 each, five layers."""
    live, selected = 160000, 16 * 2048
    cost = costs_dsa.selection(live, selected, **SHAPE)
    assert cost["bytes"] == 5 * (live * 256 + selected * 1280)
    assert cost["flops"] == 5 * (live * 64 * 2 * 128
                                 + selected * 128 * 2 * (576 + 512))
    with_weights = costs_dsa.selection(
        live, selected, index_params=FAMILY.layer_params(CONFIG)["indexer"],
        **SHAPE)
    assert with_weights["bytes"] - cost["bytes"] == pytest.approx(
        5 * 13.96e6 * 2, rel=1e-3)
    # bound by the bytes at these sizes: 0.41 GB in 0.5 ms
    assert costs.least_seconds(cost, PEAKS) == cost["bytes"] / 819e9
    assert 4e-4 < costs.least_seconds(cost, PEAKS) < 6e-4


@pytest.mark.parametrize("live, selected", [(1, 1), (2048, 2048),
                                            (17408, 2048)])
def test_the_selection_scales_as_its_two_parts(live, selected):
    one = costs_dsa.selection(live, selected, **SHAPE)
    two = costs_dsa.selection(2 * live, selected, **SHAPE)
    assert two["bytes"] - one["bytes"] == 5 * live * 256
    assert two["flops"] - one["flops"] == 5 * live * 64 * 2 * 128
    more = costs_dsa.selection(live, 2 * selected, **SHAPE)
    assert more["bytes"] - one["bytes"] == 5 * selected * 1280


def test_the_whole_step_is_weights_then_the_selection():
    params = FAMILY.decode_weight_params(CONFIG, 4 * 8)
    step = costs_dsa.step(16, params, 160000, 16 * 2048, SHAPE)
    sparse = costs_dsa.selection(160000, 16 * 2048, **SHAPE)
    assert step["bytes"] == params * 2.0 + sparse["bytes"]
    assert step["flops"] == 2.0 * 16 * params + sparse["flops"]
    # all sixteen held experts of all four layers hit: every weight
    assert FAMILY.decode_weight_params(CONFIG, 64) + 7168 * 16160 \
        == FAMILY.weight_params(CONFIG)
    # the weights are most of what a step moves: 8 GB against 0.4
    assert 0.9 < params * 2.0 / step["bytes"] < 1.0


def run_of(monkeypatch, steps, scopes_ms=None, device_s=0.02, moe=None):
    """A run whose profile holds these decode dispatches; the scopes' time
    is set where a reader asks ``decode_scopes`` for it."""
    regions = {"engine.decode.dispatch": steps, "engine.decode.moe": moe}
    monkeypatch.setattr(host_regions, "rows",
                        lambda run, region: regions.get(region))
    if scopes_ms is not None:
        from benchmark import decode_scopes
        monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                            lambda run, scopes: sum(
                                scopes_ms[s] for s in scopes))
    return {"trace": {"programs": {"jit__decode": {
        "calls": len(steps or ()) or 1,
        "device_s": device_s * (len(steps or ()) or 1)}}},
        "peaks": PEAKS, "cell": {"name": "x", "config": CONFIG}}


STEPS = [{"active": 16, "live": 160000, "selected": 16 * 2048,
          "live_tokens": 160000},
         {"active": 15, "live": 150000, "selected": 15 * 2048,
          "live_tokens": 150000}]


def test_the_readers_on_a_profiles_regions(monkeypatch):
    scopes = {"dsa_index": 1.0, "dsa_select": 0.5, "dsa_read": 1.5}
    moe = [{"weight_itemsize": 2, "assignments": 64, "experts_hit": 30,
            "load_max": 8}] * 2
    run = run_of(monkeypatch, STEPS, scopes, moe=moe)
    read = {name: read_profile.reader(name)(run) for name in (
        "dsa_selected_share", "dsa_read_roofline",
        "dsv32_step_hbm_roofline")}
    assert read["dsa_selected_share"] == pytest.approx(
        100 * 31 * 2048 / 310000)
    least = costs.least_seconds(costs_dsa.selection(
        155000, 15.5 * 2048,
        index_params=FAMILY.layer_params(CONFIG)["indexer"], **SHAPE), PEAKS)
    assert read["dsa_read_roofline"] == pytest.approx(100 * least / 3e-3)
    assert 10 < read["dsa_read_roofline"] < 100
    # 6.4 GB of weights (30 of 64 held experts hit) in 20 ms
    assert 35 < read["dsv32_step_hbm_roofline"] < 45


def test_the_readers_give_none_where_there_is_nothing(monkeypatch):
    """A program without the regions' new attributes (the parent), a window
    without a step, no trace: None, never a raise."""
    old = [{"active": 16, "live_tokens": 160000}]
    for steps in (old, None):
        run = run_of(monkeypatch, steps, {"dsa_index": 0, "dsa_select": 0,
                                          "dsa_read": 0})
        for name in ("dsa_selected_share", "dsa_read_roofline",
                     "dsv32_step_hbm_roofline"):
            assert read_profile.reader(name)(run) is None
    empty = {"trace": {}, "peaks": PEAKS,
             "cell": {"name": "x", "config": CONFIG}}
    monkeypatch.undo()
    for name in ("dsa_index_decode_ms", "dsa_select_decode_ms",
                 "dsa_read_decode_ms", "dsa_index_prefill_ms",
                 "dsa_select_prefill_ms", "dsa_read_prefill_ms",
                 "dsa_selected_share", "dsa_read_roofline",
                 "dsv32_step_hbm_roofline"):
        assert read_profile.reader(name)(empty) is None, name
