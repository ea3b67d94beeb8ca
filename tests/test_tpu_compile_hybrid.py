"""The hybrid configuration's two programs at the published widths, compiled
for a described v5e from shapes alone (ISSUE 48), beside
``test_tpu_compile.py``: the decode step and the 512 rung fit the chip with
the cell's pools, every pool comes back in its argument's buffer, and no
program copies a stack of weights (a layer sliced at an index that is not
the scan's own cost a re-laid-out copy of the whole stack every call)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import _fits, no_persistent_cache, topo  # noqa: F401

GIB = 1024 ** 3


@pytest.fixture(scope="module")
def cell():
    from benchmark import spec
    config = spec.load_json("configs", "olmo-hybrid-7b-12l.json")
    family = spec.load_part("families", config["family"])
    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"])
    return family, model, engine


def compiled(topo, cell, program):
    from ray_tpu.models import llama
    family, model, engine = cell
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    params = on(jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), model)))
    pools = on(jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"])))
    maxp = (engine["max_prompt_len"] + engine["max_new_tokens"]) \
        // engine["page_size"]
    slots = engine["max_batch"]
    if program == "decode":
        lowered = jax.jit(
            lambda p, *a: llama.llama_decode_step(p, model, *a),
            donate_argnums=(3, 4)).lower(
                params, arg(slots), arg(slots), *pools, arg(slots, maxp))
    else:
        lowered = jax.jit(
            lambda p, *a: llama.llama_prefill(p, model, *a),
            donate_argnums=(3, 4)).lower(
                params, arg(1, program), arg(), *pools, arg(1, maxp), arg())
    return params, pools, lowered.compile()


@pytest.mark.parametrize("program", ["decode", 512])
def test_the_hybrid_program_fits_and_copies_no_stack(topo, cell, program):
    params, pools, exe = compiled(topo, cell, program)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    assert round(weights / 1e9, 2) == 6.54
    assert round(held / 1e9, 2) == 4.38       # pages 3.40, rows 0.99
    memory = exe.memory_analysis()
    # every pool is the argument's buffer: K, V, the states, the tails
    assert memory.alias_size_in_bytes >= held
    # 10.9 GB held; the decode step's gather of one full layer's pages
    # (48 x 96 pages) is its temporaries, the prefill has next to none
    assert _fits(exe) < 12.5 * GIB
    assert memory.temp_size_in_bytes < (1.6 if program == "decode"
                                        else 0.4) * GIB
    text = exe.as_text()
    # no instruction MAKES an array as large as a group's stack of
    # feed-forward weights [3 periods, 2, 3840, 11008]
    made = [line for line in text.splitlines() if re.search(
        r"= bf16\[3,(2,3840,11008|11008,3840|3840,11520)\]\S* "
        r"(?!parameter|get-tuple-element|bitcast)", line)]
    assert made == []
    # the states are float32 and folded: 45 panels of 128 lanes, 96 deep
    assert "f32[9,48,45,96,128]" in text
