"""The traffic generators offer every seed the same load."""

import itertools

import pytest

from benchmark import spec
from benchmark.generators import closed_loop_serve, open_loop_serve

SEEDS = [0, 1, 7, 12345, 2 ** 31 - 1, 2 ** 31, 2 ** 31 + 99, 424242, 31337,
         2 ** 31 + 2 ** 20]


def test_chat_steady_same_load_for_ten_seeds():
    traffic = spec.load_json("traffic", "chat-steady.json")
    plans = [open_loop_serve.plan(traffic, 51, seed) for seed in SEEDS]
    counts = {len(p) for p in plans}
    assert counts == {round(traffic["rate_per_s"] * 51)}
    assert len({sum(prompt for _, prompt, _ in p) for p in plans}) == 1
    assert len({sum(output for _, _, output in p) for p in plans}) == 1
    # the same sizes, at other times and in another order
    assert len({tuple(t for t, _, _ in p) for p in plans}) == len(SEEDS)
    assert len({tuple(n for _, n, _ in p) for p in plans}) == len(SEEDS)
    for p in plans:
        offsets = [t for t, _, _ in p]
        assert offsets == sorted(offsets) and 0 <= offsets[0] \
            and offsets[-1] < 51
        for _, prompt, output in p:
            assert 32 <= prompt <= 2048 and 16 <= output <= 512


def test_plan_is_the_seeds_alone():
    traffic = spec.load_json("traffic", "chat-steady.json")
    assert open_loop_serve.plan(traffic, 20, 5) == \
        open_loop_serve.plan(traffic, 20, 5)


@pytest.mark.parametrize("blocks", [1, 4, 40])
def test_closed_loop_blocks_are_the_same_work_for_every_seed(blocks):
    traffic = spec.load_json("traffic", "longprompt-batch.json")
    n = blocks * traffic["block"]
    taken = [list(itertools.islice(
        closed_loop_serve.plan(traffic, seed), n)) for seed in SEEDS]
    assert len({sum(p for p, _ in t) for t in taken}) == 1
    assert len({sum(o for _, o in t) for t in taken}) == 1
    assert len({tuple(t) for t in taken}) == len(SEEDS)
    for prompt, output in taken[0]:
        assert 1024 <= prompt <= 2048 and 16 <= output <= 64
