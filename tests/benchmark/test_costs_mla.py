"""``costs_mla`` against hand-counted bytes and operations, the readers built
on it and on ``decode_scopes`` on made-up runs, and the three ``moe_routed_*``
readers against OLMoE's on a run in which both key sets describe one shape."""

import pytest

from benchmark import (costs, costs_mla, decode_scopes, host_regions,
                       moe_scopes, spec)

XING = spec.load_json("configs", "xing4.0-29b-a4b-6l.json")
OLMOE = spec.load_json("configs", "olmoe-1b-7b-0125-4l.json")
PEAKS = spec.peaks_for("TPU v5 lite")


def test_latent_read_at_the_published_size_by_hand():
    """32 sequences of 1,000 positions, 6 layers: a row is 512 + 64 bf16
    values, 1,152 B; a head's score is 576 multiply-adds and its weighing
    512 more."""
    cost = costs_mla.latent_read(32 * 1000, 6, 512, 64, 32)
    assert cost["bytes"] == 32000 * 6 * 1152 == 221184000
    assert cost["flops"] == 32000 * 6 * 32 * 2 * (576 + 512) == 13369344000
    # bytes bind: 0.27 ms against 0.068 ms of products
    assert costs.least_seconds(cost, PEAKS) == \
        cost["bytes"] / PEAKS["hbm_bytes_per_s"]


def test_latent_read_at_a_tiny_size_by_hand():
    cost = costs_mla.latent_read(10, 3, 32, 8, 4, itemsize=4)
    assert cost["bytes"] == 10 * 3 * 40 * 4
    assert cost["flops"] == 10 * 3 * 4 * 2 * (40 + 32)
    assert costs_mla.latent_read(0, 3, 32, 8, 4) == {"flops": 0.0,
                                                      "bytes": 0.0}


@pytest.mark.parametrize("op_name,scopes,found", [
    ("jit(_decode)/while/body/closed_call/latent_read/gather",
     ("latent_append", "latent_read"), True),
    ("jit(_decode)/latent_append/scatter", ("latent_append",), True),
    ("jit(_decode)/while/body/closed_call/hc_coeff/exp", ("hc_mix",), False),
    ("jit(_decode)/while/body/closed_call/hc_coeff/exp",
     ("hc_coeff", "hc_mix"), True),
    ("jit(_decode)/while/body/closed_call/moe_shared/dot_general",
     ("moe_shared",), True),
    ("jit(_decode)/while/body/closed_call/not_latent_read_at_all/x",
     ("latent_read",), False),
    ("", ("mla_absorb",), False),
])
def test_under(op_name, scopes, found):
    assert decode_scopes.under(op_name, scopes) is found


NEW_READERS = ("latent_kv_device_ms", "mla_absorb_device_ms", "hc_device_ms",
               "moe_shared_device_ms", "latent_read_roofline",
               "moe_routed_roofline", "moe_routed_hit_share",
               "moe_routed_load_max_over_mean")


def test_readers_find_nothing_in_a_run_without_a_trace():
    run = {"trace": {}, "cell": {"name": "x", "config": XING},
           "peaks": PEAKS}
    for name in NEW_READERS:
        assert spec.metric_reader(name + ".xing")(run) is None


def test_scope_readers_sum_their_scopes_per_decode_call(monkeypatch):
    ops = ((2e-3, "jit(_decode)/while/body/closed_call/latent_read/gather"),
           (1e-3, "jit(_decode)/latent_append/scatter"),
           (4e-3, "jit(_decode)/while/body/closed_call/hc_coeff/div"),
           (1e-3, "jit(_decode)/while/body/closed_call/hc_mix/reduce_sum"),
           (3e-3, "jit(_decode)/while/body/closed_call/mla_absorb/dot"),
           (5e-3, "jit(_decode)/while/body/closed_call/moe_shared/dot"),
           (9e-3, "jit(_decode)/while/body/closed_call/moe_experts/x"))
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: ops)
    from benchmark import replica
    monkeypatch.setattr(replica, "find_xplane", lambda folder: "a.pb")
    run = {"trace": {"programs": {"jit__decode": {"calls": 2,
                                                  "device_s": 1.0}}},
           "cell": {"name": "x", "config": XING}, "peaks": PEAKS}
    read = {name: spec.metric_reader(name + ".xing")(run)
            for name in NEW_READERS[:4]}
    assert read == pytest.approx({
        "latent_kv_device_ms": 1.5, "mla_absorb_device_ms": 1.5,
        "hc_device_ms": 2.5, "moe_shared_device_ms": 2.5})
    # the roofline: two steps that held 24,000 and 26,000 positions
    steps = [{"live_tokens": 24000, "gathered_tokens": 131072},
             {"live_tokens": 26000, "gathered_tokens": 131072}]
    monkeypatch.setattr(host_regions, "rows", lambda run, region: steps
                        if region == "engine.decode.dispatch" else None)
    least = 25000 * 6 * 1152 / PEAKS["hbm_bytes_per_s"]       # a step
    assert spec.metric_reader("latent_read_roofline.xing")(run) == \
        pytest.approx(100 * least / 1e-3, rel=1e-6)
    assert 0 < 100 * least / 1e-3 < 100


def test_moe_routed_readers_are_olmoes_where_the_keys_agree(monkeypatch):
    """A configuration with Xing's keys that describes OLMoE's expert
    layers (four layers that all route, 64 experts of 2048 x 1024): the
    three readers that ask the family read what OLMoE's three read."""
    steps = [{"assignments": 512, "experts_hit": 224, "load_max": 16,
              "weight_itemsize": 2},
             {"assignments": 512, "experts_hit": 216, "load_max": 20,
              "weight_itemsize": 2}]
    monkeypatch.setattr(moe_scopes.host_regions, "rows",
                        lambda run, region: steps
                        if region == "engine.decode.moe" else None)
    monkeypatch.setattr(moe_scopes, "decode_scope_ms",
                        lambda run, scopes: 4.0)
    alike = {**XING, "num_hidden_layers": 5, "first_k_dense_replace": 1,
             "n_routed_experts": 64, "hidden_size": 2048,
             "moe_intermediate_size": 1024}
    assert spec.load_part("families", "xing").moe_shape(alike) == {
        "layers": OLMOE["num_hidden_layers"],
        "experts": OLMOE["num_experts"], "hidden": OLMOE["hidden_size"],
        "width": OLMOE["intermediate_size"]}
    runs = [{"trace": {"programs": {}}, "peaks": PEAKS,
             "cell": {"name": "x", "config": config}}
            for config in (alike, OLMOE)]
    for mine, theirs in (("moe_routed_roofline", "moe_experts_roofline"),
                         ("moe_routed_hit_share", "moe_experts_hit_share"),
                         ("moe_routed_load_max_over_mean",
                          "moe_load_max_over_mean")):
        got = spec.metric_reader(mine + ".xing")(runs[0])
        assert got == pytest.approx(spec.metric_reader(theirs)(runs[1]))
        assert got > 0
    # and at the published shape: touched experts at 2 B, under 100%
    run = {"trace": {"programs": {}}, "peaks": PEAKS,
           "cell": {"name": "x", "config": XING}}
    least = 220 * 3 * 3584 * 1024 * 2 / PEAKS["hbm_bytes_per_s"]
    assert spec.metric_reader("moe_routed_roofline.xing")(run) == \
        pytest.approx(100 * least / 4e-3, rel=1e-6)
    assert spec.metric_reader("moe_routed_hit_share.xing")(run) == \
        pytest.approx(100 * 440 / (2 * 5 * 64))
