"""``benchmark/tools/numerics_deepseek_v32.py`` at a tiny size on the CPU, in
float32: the configuration as it is reads the reference's logits and keeps the
reference's positions, in the token steps and in the chunks; each planted
fault parts them, by the logits or by what was kept."""

import dataclasses

import pytest

import tiny_deepseek_v32 as tiny
from benchmark.replica import seeded_key
from benchmark.tools import numerics_deepseek_v32 as tool

# two layers and an engine of one prefill rung and three decode rungs: every
# mechanism at a third of the tracing
CONFIG = {**tiny.TINY_DSV32, "num_hidden_layers": 2,
          "engine": {"page_size": 8, "max_prompt_len": 32,
                     "max_new_tokens": 8, "max_batch": 2, "num_pages": 12,
                     "prefill_chunk": 16}}


@pytest.fixture(scope="module")
def program():
    return tiny.program(CONFIG)


@pytest.fixture(scope="module")
def honest(program):
    family, model, params = program
    return tool.compared_with(family, CONFIG, model, params, {},
                              seeded_key(5), chunks=True)


def test_as_configured_it_is_the_reference(honest):
    assert honest["prompt_lengths"] == [29, 7]
    assert max(honest["logits_rel_err"]) < 2e-5
    assert honest["steps_common_share"] == 1.0
    assert honest["chunks_common_share"] == 1.0
    assert honest["selected_positions"] == (8 * 2 * 8, 8 * 2 * 8)
    assert 0.2 < honest["selected_of_live"] < 0.3


# (one of the six that are functions: an engine a fault is eight seconds of
# tracing here, twice that beside five other workers; the others are planted
# the same way, ``test_every_fault_names_a_function_that_is_there`` holds
# their names, and the chip reads them all)
@pytest.mark.parametrize("what", ["the selection one short"])
def test_a_planted_fault_parts_them(program, honest, what):
    family, model, params = program
    found = tool.compared_with(family, CONFIG, model, params,
                               tool.FAULTS[what], seeded_key(5))
    worst = max(found["logits_rel_err"])
    if what == "the selection one short":
        # one position of eight is an eighth of a read here (a 2,048th at
        # the published size, which no logit shows): the count tells
        assert found["selected_positions"] == (7 * 2 * 8, 8 * 2 * 8)
    assert worst > 100 * max(honest["logits_rel_err"]), (what, found)


def test_every_fault_names_a_function_that_is_there():
    import importlib
    modules = {"llama": "ray_tpu.models.llama", "moe": "ray_tpu.ops.moe",
               "pa": "ray_tpu.ops.paged_attention"}
    for what, fault in tool.FAULTS.items():
        for where, fn in fault.get("patch", {}).items():
            module, name = where.split(".")
            assert callable(getattr(importlib.import_module(
                modules[module]), name)) and callable(fn), (what, where)
    assert sum(1 for f in tool.FAULTS.values() if f.get("weights")) == 1
    assert len(tool.FAULTS) == 7


def test_float8_rounds_the_matrices_and_nothing_else(program):
    _, _, params = program
    rounded = tool.to_float8(params)
    attn, was = rounded["layers"]["attn"], params["layers"]["attn"]
    assert (attn["wkv_b"] != was["wkv_b"]).any()
    assert (attn["index_k_bias"] == was["index_k_bias"]).all()
    assert (rounded["layers"]["mlp"]["router_bias"]
            == params["layers"]["mlp"]["router_bias"]).all()
    assert dataclasses.is_dataclass(program[1])
