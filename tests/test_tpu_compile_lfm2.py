"""The LFM2 configuration's programs at the published widths, compiled for a
described v5e from shapes alone (ISSUE 55), beside ``test_tpu_compile.py``:
the decode step and the 4,096 rung (the benchmark's first) fit the chip with
the cell's pools, every pool (K/V pages of the two attention layers, the
convolution tails) comes back in its argument's buffer, the decode step reads
the pages through the walker at heads of 64 (no gather), the prefill's causal
attention is the flash kernel with grouped K/V at a head of 64, and the
experts run as the grouped-matmul kernel with no copy of a layer's experts."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (_fits, _paged_read_kernels,  # noqa: F401
                              compiled_experts, compiled_kernels,
                              compiled_paged_read, made_of_shape,
                              no_persistent_cache, topo)

GIB = 1024 ** 3


@pytest.fixture(scope="module")
def cell():
    from benchmark import spec
    config = spec.load_json("configs", "lfm2-24b-a2b-9l.json")
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
        # on the chip ``auto`` gives a rung of 1,024 or more the flash
        # kernel; here the backend is the CPU, so it is pinned
        model = dataclasses.replace(model, attention="flash")
        assert llama.llama_prefill_attention(model, program) == "flash"
        lowered = jax.jit(
            lambda p, *a: llama.llama_prefill(p, model, *a),
            donate_argnums=(3, 4)).lower(
                params, arg(1, program), arg(), *pools, arg(1, maxp), arg())
    return params, pools, lowered.compile()


@pytest.mark.parametrize("program", ["decode", 4096])
def test_the_lfm2_program_fits_and_keeps_its_pools_in_place(
        topo, cell, compiled_paged_read, compiled_experts, compiled_kernels,
        program):
    params, pools, exe = compiled(topo, cell, program)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    assert round(weights / 1e9, 2) == 10.36
    # K/V pages 2 x 2 x 12,481 x 16 x 512 x 2 B = 0.818, tails 0.003
    assert round(held / 1e9, 3) == 0.821
    assert pools[1].state is None and pools[1].conv.shape == (7, 48, 4096)
    memory = exe.memory_analysis()
    assert memory.alias_size_in_bytes >= held      # pages and tails in place
    assert _fits(exe) < 14.0 * GIB
    text = exe.as_text()
    # no instruction MAKES an array as large as a layer's experts
    made = [line for line in text.splitlines() if re.search(
        r"= bf16\[(1,)?64,(2,2048,1536|1536,2048)\]\S* "
        r"(?!parameter|get-tuple-element|bitcast)", line)]
    assert made == []
    assert "grouped_matmul" in text
    pool = "bf16[2,12481,16,512]"
    if program == "decode":          # a kernel an attention layer, no gather
        assert len(_paged_read_kernels(text, pool)) == 2
        assert not re.search(r"bf16\[48,4160,", text)
    else:
        assert len(re.findall(r"flash_fwd[\w.]* = ", text)) >= 2
        assert made_of_shape(text, "f32[1,32,4096,4096]") == []
