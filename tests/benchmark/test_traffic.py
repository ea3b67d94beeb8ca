"""The traffic generators offer every seed the same load."""

import itertools
import os

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


def open_loop_files():
    folder = os.path.join(spec.BENCH_DIR, "traffic")
    return sorted(name for name in os.listdir(folder)
                  if name.endswith(".json") and spec.load_json(
                      "traffic", name).get("generator") == "open_loop_serve")


@pytest.mark.parametrize("name", open_loop_files())
@pytest.mark.parametrize("seeds", [(0, 2 ** 31 + 99), (12345, 2 ** 31 - 1),
                                   (7, 2 ** 31 + 2 ** 20)])
def test_two_seeds_offer_the_same_count_and_lengths(name, seeds):
    traffic = spec.load_json("traffic", name)
    a, b = (open_loop_serve.plan(traffic, 51, seed) for seed in seeds)
    assert len(a) == len(b) == round(traffic["rate_per_s"] * 51)
    assert sorted(p for _, p, _ in a) == sorted(p for _, p, _ in b)
    assert sorted(o for _, _, o in a) == sorted(o for _, _, o in b)
    assert [p for _, p, _ in a] != [p for _, p, _ in b]


def test_chat_steady_lengths_are_the_distributions_it_names():
    """The rate is the knee's; the shapes are the traffic and stay."""
    traffic = spec.load_json("traffic", "chat-steady.json")
    assert traffic["prompt_tokens"] == {
        "distribution": "lognormal", "median": 256, "sigma": 0.9,
        "min": 32, "max": 2048}
    assert traffic["output_tokens"] == {
        "distribution": "lognormal", "median": 128, "sigma": 0.7,
        "min": 16, "max": 512}
    prompts = sorted(p for _, p, _ in open_loop_serve.plan(traffic, 51, 3))
    half = len(prompts) // 2
    assert prompts[half - 1] <= 256 <= prompts[half] or \
        prompts[half] == 256


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
