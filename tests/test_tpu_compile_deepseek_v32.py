"""The DeepSeek-V3.2-Exp configuration's programs at the published widths,
compiled for a described v5e from shapes alone (ISSUE 57), beside
``test_tpu_compile.py``: the decode step over a 16k context and the
4,096-wide chunk of a prompt fit the chip with the cell's two pools (latent
pages and the indexer's keys), both pools come back in their arguments'
buffers, the chunk makes neither the attention's ``[heads, S, S]`` scores nor
the indexer's ``[heads, S, context]`` products whole, the experts run as
the grouped-matmul kernel, and the chunk's attention over the pages as the
kernel of ``ops/latent_prefill.py`` (ISSUE 58): no block of float32 scores
and no whole-chunk accumulator is made in device memory."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (_fits, compiled_experts,  # noqa: F401
                              compiled_kernels, made_of_shape,
                              no_persistent_cache, topo)

GIB = 1024 ** 3


@pytest.fixture
def compiled_chunk(monkeypatch):
    """``latent_chunk_attention`` asks the backend whether its kernel is
    compiled or interpreted; make it answer as on the chip."""
    from ray_tpu.ops import latent_prefill
    monkeypatch.setattr(latent_prefill, "_kernel_backend", lambda: True)


@pytest.fixture(scope="module")
def cell():
    from benchmark import spec
    config = spec.load_json("configs", "deepseek-v3.2-exp-5l.json")
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
    if program == "decode":          # the top rung: a 17,408-position table
        lowered = jax.jit(
            lambda p, *a: llama.llama_decode_step(p, model, *a),
            donate_argnums=(3, 4)).lower(
                params, arg(slots), arg(slots), *pools, arg(slots, maxp))
    else:                            # a chunk, its start an argument
        lowered = jax.jit(
            lambda p, *a: llama.llama_prefill(p, model, *a),
            donate_argnums=(3, 4)).lower(
                params, arg(1, program), arg(), *pools, arg(1, maxp), arg(),
                arg())
    return params, pools, lowered, lowered.compile()


@pytest.mark.parametrize("program", ["decode", 4096])
def test_the_program_fits_and_keeps_both_pools_in_place(
        topo, cell, compiled_experts, compiled_kernels, compiled_chunk,
        program):
    params, pools, lowered, exe = compiled(topo, cell, program)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    assert abs(weights / 9.27e9 - 1) < 0.01      # 9.286: the router is f32
    # 5 x 17,409 x 16 x (640 + 128) x 2 B
    assert pools[0].shape == (5, 17409, 16, 640)
    assert pools[1].shape == (5, 17409, 16, 128)
    assert round(held / 1e9, 2) == 2.14
    memory = exe.memory_analysis()
    assert memory.alias_size_in_bytes >= held      # both pools in place
    used = _fits(exe)
    print(f"{program}: {used / GIB:.2f} GiB, temporaries "
          f"{memory.temp_size_in_bytes / GIB:.2f} GiB")
    text = exe.as_text()
    assert "grouped_matmul" in text
    if program == "decode":
        # the selected rows alone are gathered from the latent pool
        assert made_of_shape(text, "bf16[16,17408,640]") == []
    else:
        assert made_of_shape(text, "f32[128,4096,4096]") == []
        # the chunk's attention is one kernel: its scores, probabilities and
        # accumulator stay in fast memory
        assert "latent_chunk" in text
        # and its module names no file, wherever the scalar arithmetic of
        # its index maps was first traced (ops/kernel_source.py)
        import base64
        import re
        modules = [base64.b64decode(body) for body in re.findall(
            r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered.as_text())]
        chunk = [m for m in modules if b"latent_chunk" in m]
        assert chunk and not any(b".py" in m for m in chunk)
        assert made_of_shape(text, "f32[128,512,1024]") == []
        assert made_of_shape(text, "bf16[128,512,1024]") == []
        assert made_of_shape(text, "f32[128,4096,128]") == []
        assert made_of_shape(text, "f32[4096,64,17408]") == []
        assert made_of_shape(text, "f32[64,4096,17408]") == []
