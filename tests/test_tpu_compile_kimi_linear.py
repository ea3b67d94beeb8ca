"""The Kimi Linear configuration's programs at the published widths, compiled
for a described v5e from shapes alone (ISSUE 51), beside
``test_tpu_compile.py`` and ``test_tpu_compile_hybrid.py``: the decode step
and the 1024 rung fit the chip with the cell's pools, every pool (latent
pages, states, convolution tails) comes back in its argument's buffer, the
latent read and the experts run as the kernels, and no program makes a copy
of a layer's experts."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (_fits, _linear_state_kernels,  # noqa: F401
                              _paged_read_kernels, compiled_experts,
                              compiled_linear_state, compiled_paged_read, la,
                              made_of_shape, no_persistent_cache, pa, topo)

GIB = 1024 ** 3


@pytest.fixture(scope="module")
def cell():
    from benchmark import spec
    config = spec.load_json("configs", "kimi-linear-48b-a3b-8l.json")
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


@pytest.mark.parametrize("program", ["decode", 1024])
def test_the_kimi_program_fits_and_keeps_its_pools_in_place(
        topo, cell, compiled_paged_read, compiled_experts, program):
    params, pools, exe = compiled(topo, cell, program)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    assert round(weights / 1e9, 2) == 7.55
    # latent pages 2 x 16,385 x 16 x 640 x 2 B = 0.67, states 0.81, tails 0.03
    assert round(held / 1e9, 2) == 1.5
    memory = exe.memory_analysis()
    # every pool is the argument's buffer: the pages, the states, the tails
    assert memory.alias_size_in_bytes >= held
    assert _fits(exe) < 12.0 * GIB
    text = exe.as_text()
    # no instruction MAKES an array as large as a layer's experts
    made = [line for line in text.splitlines() if re.search(
        r"= bf16\[(1,)?64,(2,2304,1024|1024,2304)\]\S* "
        r"(?!parameter|get-tuple-element|bitcast)", line)]
    assert made == []
    assert "grouped_matmul" in text
    if program == "decode":          # one a latent layer, the one pool
        assert len(_paged_read_kernels(
            text, "bf16[2,16385,16,640]", "latent_read", pools=1)) == 2


def test_the_kimi_decode_steps_the_states_through_the_kernel(
        topo, cell, compiled_paged_read, compiled_experts,
        compiled_linear_state, monkeypatch):
    """On the chip the six KDA layers step their states through
    ``ops/linear_state.py`` (ISSUE 52), the decay a key channel's: one
    kernel a linear layer (with experts from the second layer on every layer
    is a group of its own: six in the program's text), the whole pool its
    operand and its result in one buffer, nothing else of the pool's shape
    or of a layer's slab made, the temporaries the rule's and one layer's
    columns operand."""
    pool, slab = "f32[6,64,32,128,128]", "f32[64,32,128,128]"
    _, pools, exe = compiled(topo, cell, "decode")
    text = exe.as_text()
    assert len(_linear_state_kernels(text, pool)) == 6
    assert made_of_shape(text, pool, but="custom-call") == []
    assert made_of_shape(text, slab) == []
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    memory = exe.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    monkeypatch.setattr(la, "_kernel_backend", lambda: False)
    _, _, rule = compiled(topo, cell, "decode")
    assert _linear_state_kernels(rule.as_text(), pool) == []
    assert len(made_of_shape(rule.as_text(), pool)) >= 6
    # the one temporary the kernel adds: a layer's columns operand (every
    # head's decay, key and query a key channel a sublane: [64, 128, 128])
    assert memory.temp_size_in_bytes \
        <= rule.memory_analysis().temp_size_in_bytes + 64 * 128 * 128 * 4


def test_the_family_builds_the_published_program(cell):
    _, model, engine = cell
    assert (model.embed_dim, model.num_heads, model.num_layers) == \
        (2304, 32, 8)
    assert model.layer_pattern == ("linear", "linear", "linear", "full")
    assert (model.linear_heads, model.linear_key_dim, model.linear_value_dim,
            model.linear_conv, model.linear_gate_rank) == (32, 128, 128, 4,
                                                           128)
    assert (model.kv_lora_rank, model.q_lora_rank, model.qk_nope_dim,
            model.qk_rope_dim, model.v_head_dim) == (512, 0, 128, 64, 128)
    assert model.rope_theta == 0.0 and model.rope_yarn is None
    assert (model.num_experts, model.expert_share, model.experts_per_token,
            model.mlp_dim, model.dense_mlp_dim, model.shared_experts) == \
        (256, (0, 4), 8, 1024, 9216, 1)
    assert model.vocab_size == 40960
    assert model.max_seq_len == engine["max_prompt_len"] \
        + engine["max_new_tokens"] == 4096
