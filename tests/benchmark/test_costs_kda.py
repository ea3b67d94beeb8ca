"""``benchmark/costs_kda.py`` and the readers ISSUE 51 adds for a model of
KDA and latent layers with expert layers of which the program holds a share:
what they count, that nothing read from a step's own regions can pass 100%,
and that each gives None where the program (the parent's) says nothing."""

import pytest

from benchmark import (costs, costs_kda, costs_linear, decode_scopes,
                       host_regions, moe_scopes, spec)

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KIMI = spec.load_json("configs", "kimi-linear-48b-a3b-8l.json")
NEW_READERS = ["kda_gate_device_ms", "linear_state_roofline",
               "latent_read_roofline", "moe_kept_assignment_share",
               "kimi_step_hbm_roofline"]
STATE = 6 * 32 * 128 * 128 * 4             # a slot's states, float32
TRACED = {"programs": {"jit__decode": {"calls": 2, "device_s": 0.030},
                       "jit__prefill": {"calls": 1, "device_s": 0.030}}}


def run_of(trace=TRACED):
    return {"trace": trace, "cell": {"name": "x", "config": KIMI},
            "peaks": PEAKS}


def test_the_family_counts_weights_pages_and_states():
    family = spec.load_part("families", "kimi_linear")
    assert family.layer_counts(KIMI) == {"linear": 6, "full": 2}
    assert family.kv_bytes_per_token(KIMI) == 2 * 576 * 2 == 2304
    assert family.state_bytes_per_slot(KIMI) == STATE == 12582912
    each = family.layer_params(KIMI)
    # the issue's arithmetic: 39.51M a KDA mixer, 29.11M a latent one,
    # 63.70M the dense feed-forward, 7.078M an expert, 0.59M a router
    assert round(each["linear"] / 1e6, 2) == 39.51
    assert round(each["full"] / 1e6, 2) == 29.11
    assert round(each["dense"] / 1e6, 2) == 63.70
    assert each["expert"] == each["shared"] == 3 * 2304 * 1024
    assert round(each["router"] / 1e6, 2) == 0.59
    # a step reads the experts that were HIT: 56 a layer and all 64 differ
    # by 8 experts in each of the 7 layers
    assert family.decode_weight_params(KIMI, 7 * 64) \
        - family.decode_weight_params(KIMI, 7 * 56) == 7 * 8 * each["expert"]
    # with every held expert hit it is the tree less the embedding
    import jax
    model = family.program_config(KIMI, 4096)
    stored = jax.eval_shape(lambda: family.init(jax.random.PRNGKey(0), model))
    assert family.decode_weight_params(KIMI, 7 * 64) == sum(
        a.size for a in jax.tree.leaves(stored)) - 40960 * 2304
    assert family.linear_shape(KIMI) == {
        "layers": 6, "heads": 32, "key_dim": 128, "value_dim": 128}
    assert family.latent_shape(KIMI) == {
        "layers": 2, "rank": 512, "rope": 64, "heads": 32}
    assert family.moe_shape(KIMI) == {
        "layers": 7, "experts": 64, "hidden": 2304, "width": 1024}


def test_a_state_step_reads_and_writes_every_live_state_once():
    cost = costs_kda.state_step(64, 6, 32, 128, 128)
    assert cost["bytes"] == 2 * 64 * STATE
    assert cost["flops"] == 8 * 64 * STATE / 4
    # the decay a channel costs a multiply a value more than a decay a head
    assert cost["flops"] > costs_linear.state_step(64, 6, 32, 128,
                                                   128)["flops"]
    assert cost["bytes"] == costs_linear.state_step(64, 6, 32, 128,
                                                    128)["bytes"]
    # memory bound: 1.61 GB at the memory's rate, 2.0 ms
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        2 * 64 * STATE / 819e9)
    assert 1.9e-3 < costs.least_seconds(cost, PEAKS) < 2.0e-3
    assert costs_kda.state_step(32, 6, 32, 128, 128)["bytes"] == \
        cost["bytes"] / 2                  # parked slots are not counted


def test_the_scan_is_counted_in_whole_chunks_of_the_real_length():
    one = costs_kda.chunked_scan(64, 6, 32, 128, 128)
    assert costs_kda.chunked_scan(1, 6, 32, 128, 128)["flops"] == \
        one["flops"]
    assert costs_kda.chunked_scan(65, 6, 32, 128, 128)["flops"] == \
        2 * one["flops"]
    decayed = 2 * 64 * 64 * 128 * 3 / 8 + 3 * 64 * 16 * 128
    per_chunk = 2 * decayed + 2 * 64 * 64 * (128 + 2 * 128) + 64 ** 3 \
        + 6 * 64 * 128 * 128
    assert one["flops"] == 6 * 32 * per_chunk
    assert one["bytes"] == 6 * 4 * (64 * 32 * (3 * 128 + 2 * 128 + 1)
                                    + 32 * 128 * 128)
    # of a decayed matrix the blocks under the diagonal are matrix products
    # (3/8 of the square at four sub-chunks) and the diagonal's pair by pair
    assert decayed == 2 * 64 * 64 * 128 * 0.375 + 3 * 64 * 16 * 128
    # a prompt of 640: 0.32 GB of float32 rows outweigh its operations at
    # the peaks
    cost = costs_kda.chunked_scan(640, 6, 32, 128, 128)
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        cost["bytes"] / 819e9)


def test_a_step_is_weights_hit_pages_and_states():
    family = spec.load_part("families", "kimi_linear")
    weights = family.decode_weight_params(KIMI, 7 * 56)
    cost = costs_kda.step(64, weights, 96000, 2304, STATE)
    assert cost["bytes"] == weights * 2 + 96000 * 2304 + 2 * 64 * STATE
    assert cost["flops"] == 2 * 64 * weights
    # the issue's count: ~6.6 GB of weights at 56 of 64 experts hit, 0.22
    # of latent rows at 1,500 live positions a slot, 1.61 of states
    assert 8.2e9 < cost["bytes"] < 8.6e9
    assert costs.least_seconds(cost, PEAKS) == pytest.approx(
        cost["bytes"] / 819e9)
    assert 0.18 < 2 * 64 * STATE / cost["bytes"] < 0.20
    assert 96000 * 2304 / cost["bytes"] < 0.03       # the latent read: small


def test_the_rooflines_read_the_regions_and_cannot_pass_100(monkeypatch):
    steps = [{"active": 64, "live_tokens": 97000},
             {"active": 62, "live_tokens": 95000}]
    moe = [{"assignments": 900, "assignments_made": 3584, "experts_hit": 392,
            "load_max": 40, "weight_itemsize": 2},
           {"assignments": 868, "assignments_made": 3472, "experts_hit": 388,
            "load_max": 38, "weight_itemsize": 2}]
    monkeypatch.setattr(host_regions, "rows", lambda run, region: {
        "engine.decode.dispatch": steps, "engine.decode.moe": moe}[region])
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: 4.0)
    run = run_of()
    state = spec.metric_reader("linear_state_roofline.kimi")(run)
    want = 100 * costs.least_seconds(costs_kda.state_step(
        63, 6, 32, 128, 128), PEAKS) / 4e-3
    assert state == pytest.approx(want) and 45 < state < 52
    kept = spec.metric_reader("moe_kept_assignment_share.kimi")(run)
    assert kept == pytest.approx(100 * 1768 / 7056) and 24 < kept < 26
    step = spec.metric_reader("kimi_step_hbm_roofline.kimi")(run)
    assert 60 < step < 75                  # 10.3 ms of bytes over 15 ms
    # the latent read counts the TWO latent layers, not the eight
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: 0.5)
    latent = spec.metric_reader("latent_read_roofline.kimi")(run)
    assert latent == pytest.approx(
        100 * (96000 * 2 * 576 * 2 / 819e9) / 0.5e-3)
    # the floors are the least the chip could take: at that time, 100%
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: 4.0 * state / 100)
    assert spec.metric_reader("linear_state_roofline.kimi")(run) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_run_with_nothing_to_read_gives_none(name, monkeypatch):
    """No trace (a CPU rehearsal), and a traced parent whose program has no
    such scope and whose engine's regions are as before."""
    read = spec.metric_reader(name + ".kimi")
    assert read(run_of({})) is None
    monkeypatch.setattr(host_regions, "rows", lambda run, region: None)
    monkeypatch.setattr(decode_scopes, "decode_scope_ms",
                        lambda run, scopes: None)
    monkeypatch.setattr(moe_scopes, "decode_routing", lambda run: None)
    assert read(run_of()) is None
    assert read(run_of({"programs": {}})) is None


def test_regions_of_a_program_that_holds_every_expert_say_nothing_of_kept(
        monkeypatch):
    """The parent's ``rt:engine.decode.moe`` regions carry no
    ``assignments_made``: nothing to read, and no error."""
    monkeypatch.setattr(host_regions, "rows", lambda run, region: [
        {"assignments": 128, "experts_hit": 60, "load_max": 9,
         "weight_itemsize": 2}])
    assert spec.metric_reader("moe_kept_assignment_share.kimi")(
        run_of()) is None
