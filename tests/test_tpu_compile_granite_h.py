"""The Granite 4.0-H configuration's programs at the published widths for a
described v5e, from shapes alone (ISSUE 61), beside ``test_tpu_compile.py``
and ``test_tpu_compile_kimi_linear.py``: the ONE kernel of
``ops/linear_state.py`` in its shared kind (one key and one query a slot for
all heads) compiles at the cell's shape with the pool its result's buffer;
the decode step and two prefill rungs lower with every pool donated and the
mixer's scopes in their text.  Tier-1 has no room for the whole step's
compile (20 s; it ran while this PR was built: the numbers are in PERF.md
section 4, and the chip compiles all seven programs in every run)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (compiled_experts,  # noqa: F401
                              compiled_linear_state, compiled_paged_read,
                              no_persistent_cache, topo)


@pytest.fixture(scope="module")
def cell():
    from benchmark import spec
    config = spec.load_json("configs", "granite-4.0-h-small-10l.json")
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
    return params, pools, lowered


def test_the_state_kernels_shared_kind_compiles_at_the_published_shape(topo):
    """``ops/linear_state.py`` with ``shared=True`` at this model's shape (64
    panels of two heads side by side, no whole panel a head, one key and one
    query a slot): Mosaic takes it, and the pool donated is the result's
    buffer with no temporary.  (The whole decode step compiles for the
    described chip in ~20 s, 11.57 GiB with nine such kernels and nothing of
    the pool's or a slab's shape made anew: PERF.md section 4; the chip runs
    it in every run of the cell.)"""
    from test_tpu_compile import la, ls
    heads, dk, dv, layers, slots = 128, 128, 64, 9, 64
    one = SingleDeviceSharding(topo.devices[0])
    _, whole, side = la._panel_plan(heads, dv)
    panels = la.state_shape(heads, dk, dv)[0]
    assert (whole, side, panels) == (0, 2, 64)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    lowered = jax.jit(
        lambda *a: ls.state_step(*a, heads=heads, whole=whole, side=side,
                                 shared=True, interpret=False),
        donate_argnums=0).lower(
        shape((layers, slots, panels, dk, 128)), shape((), jnp.int32),
        shape((slots,), jnp.bool_), shape((slots, dk, 128)),
        shape((slots, 3, panels, 128)))
    memory = lowered.compile().memory_analysis()
    assert memory.alias_size_in_bytes == layers * slots * panels * dk * 512
    assert memory.temp_size_in_bytes < 2 ** 20


def test_the_configurations_bytes_are_the_files(cell):
    from ray_tpu.models import llama
    family, model, engine = cell
    params = jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), model))
    pools = jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"]))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    assert round(weights / 1e9, 2) == 9.52
    # states 9 x 64 x 4.19 MB = 2.42, tails 0.03, K and V pages 0.40
    assert round(held / 1e9, 2) == 2.85
    assert pools[1].state.shape == (9, 64, 64, 128, 128)


@pytest.mark.parametrize("program", ["decode", 128, 512])
def test_the_three_programs_lower_with_the_mixers_scopes_and_pools_donated(
        topo, cell, compiled_paged_read, compiled_experts,
        compiled_linear_state, program):
    """Lowered for the described chip, not compiled (the 512 rung compiles in
    ~25 s and fits in 11.76 GiB: PERF.md section 4): every pool is donated,
    and the scopes the readers find the mixer's device time by are in the
    program's text."""
    _, pools, lowered = compiled(topo, cell, program)
    text = lowered.as_text(debug_info=True)
    for scope in ("ssm_proj", "linear_conv", "linear_state",
                  "linear_gate_norm", "moe_experts"):
        assert f"/{scope}/" in text, scope
    donated = text.count("tf.aliasing_output") + text.count(
        "jax.buffer_donor")
    assert donated >= len(jax.tree.leaves(pools))


def test_the_family_builds_the_published_program(cell):
    _, model, engine = cell
    assert (model.embed_dim, model.num_heads, model.num_kv_heads,
            model.head_dim, model.num_layers) == (4096, 32, 8, 128, 10)
    assert model.layer_pattern == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert (model.linear_heads, model.linear_key_dim, model.linear_value_dim,
            model.linear_conv) == (128, 128, 64, 4)
    assert model.rope_theta == 0.0 and model.tie_embeddings
    assert (model.num_experts, model.expert_share, model.experts_per_token,
            model.mlp_dim, model.shared_experts, model.first_dense_layers) \
        == (72, (0, 2), 10, 768, 2, 0)
    assert (model.embedding_multiplier, model.residual_multiplier,
            model.attention_multiplier, model.logits_scaling) == \
        (12.0, 0.22, 0.0078125, 16.0)
    assert model.vocab_size == 50176
    assert model.max_seq_len == engine["max_prompt_len"] \
        + engine["max_new_tokens"] == 1536
