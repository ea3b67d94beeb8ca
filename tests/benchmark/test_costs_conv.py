"""``benchmark/costs_conv.py`` and ``benchmark/costs_prefill.py``: the gated
short convolution's operations and bytes a position and a step, and a
prefill's model operations, against sums written out for the published
widths and against the issue's reckoning."""

import pytest

from benchmark import costs, costs_conv, costs_prefill, spec

D, K = 2048, 3
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    return spec.load_json("configs", "lfm2-24b-a2b-9l.json")


def test_an_operator_is_its_three_leaves():
    assert costs_conv.operator_params(D, K) == \
        D * 3 * D + K * D + D * D == 16_783_360          # the issue's 16.78M
    # two operations a parameter of the two matrices, the taps' three
    # multiply-adds a channel, the two gates
    assert costs_conv.operator_position(D, K) == \
        2 * (3 * D * D + D * D) + 2 * K * D + 2 * D
    assert round(costs_conv.operator_position(D, K) / 1e6, 1) == 33.6


def test_a_prefill_counts_the_real_positions_and_the_operator_once():
    one = costs_conv.operator_prefill(1000, 7, D, K)
    assert one["flops"] == 1000 * 7 * costs_conv.operator_position(D, K)
    assert one["bytes"] == 7 * 2 * (costs_conv.operator_params(D, K)
                                    + 2 * 1000 * D)
    twice = costs_conv.operator_prefill(2000, 7, D, K)
    assert twice["flops"] == 2 * one["flops"]
    assert twice["bytes"] - one["bytes"] == 7 * 2 * 2 * 1000 * D
    # at a thousand positions the operations bind, not the bytes
    assert one["flops"] / PEAKS["bf16_flops_per_s"] > \
        one["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert costs.least_seconds(one, PEAKS) == \
        one["flops"] / PEAKS["bf16_flops_per_s"]


def test_a_step_reads_the_operators_and_moves_the_live_tails():
    step = costs_conv.operator_step(48, 7, D, K)
    assert step["flops"] == 48 * 7 * costs_conv.operator_position(D, K)
    tails = 48 * 7 * 2 * D * 2                   # two positions, bf16
    assert step["bytes"] == 7 * 2 * costs_conv.operator_params(D, K) \
        + 2 * tails
    # the seven operators are 0.23 GB of a step's 10.3 (the issue's 2%)
    assert round(7 * 2 * costs_conv.operator_params(D, K) / 1e9, 2) == 0.23
    # at 48 slots the bytes bind: the step is the weights'
    assert step["bytes"] / PEAKS["hbm_bytes_per_s"] > \
        step["flops"] / PEAKS["bf16_flops_per_s"]
    idle = costs_conv.operator_step(0, 7, D, K)
    assert idle["flops"] == 0 and idle["bytes"] == \
        7 * 2 * costs_conv.operator_params(D, K)


def test_a_prefills_operations_are_the_issues_gigaflop_a_position(config):
    family = spec.load_part("families", "lfm2_moe")
    shape = family.prefill_shape(config)
    assert shape["expert_params"] == 3 * D * 1536
    assert shape["head_params"] == D * 65536
    assert (shape["attention_layers"], shape["heads"], shape["head_dim"]) \
        == (2, 32, 64)
    # 7 conv operators' two matrices, 2 attention operators' four, the dense
    # SwiGLU, 8 routers
    assert shape["matrix_params"] == 7 * 4 * D * D \
        + 2 * (2 * D * 32 * 64 + 2 * D * 8 * 64) + 3 * D * 11776 \
        + 8 * D * 64
    n = 3000
    got = costs_prefill.model_operations(n, n * 4 * 8, **shape)
    by_hand = 2 * n * shape["matrix_params"] \
        + 2 * n * 4 * 8 * 3 * D * 1536 \
        + 2 * n * n * 2 * 32 * 64 + 2 * D * 65536
    assert got == by_hand
    # ~1.03 GFLOP a position before attention's triangle and the head
    per_position = (by_hand - 2 * n * n * 2 * 32 * 64 - 2 * D * 65536) / n
    assert abs(per_position / 1.03e9 - 1) < 0.01
    # a mean of prefills: the squares' mean, not the mean's square
    mean = costs_prefill.model_operations(
        2000, 2000 * 32, squared_positions=(1000 ** 2 + 3000 ** 2) / 2,
        **shape)
    both = (costs_prefill.model_operations(1000, 1000 * 32, **shape)
            + costs_prefill.model_operations(3000, 3000 * 32, **shape)) / 2
    assert mean == pytest.approx(both)
    # a dense model: no assignments, no expert term
    assert costs_prefill.model_operations(10, 0, 5.0, 7.0, 0, 1, 1, 0.0) \
        == 100.0
