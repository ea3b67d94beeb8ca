"""The builder's tool ``numerics_granite_hybrid.py``: the faults it plants.
Beside ``test_tools.py``, which a PR that brings a configuration may not
edit."""

import importlib

from test_tools_xing import tool


def test_numerics_granite_hybrid_plants_every_fault_the_issue_lists():
    numerics = tool("numerics_granite_hybrid")
    assert list(numerics.FAULTS) == [
        "state in bfloat16", "delta without its softplus",
        "the gate after the norm", "the norm a head",
        "the padded tail updating the state", "D left out",
        "the convolution's bias left out", "the scale 128 ** -0.5",
        "embedding_multiplier 1", "residual_multiplier 1",
        "logits_scaling 1", "the experts of the other share",
        "float8 weights"]
    for fault in numerics.FAULTS.values():
        assert set(fault) <= {"config", "patch", "weights", "zero"} and fault
    llama = importlib.import_module("ray_tpu.models.llama")
    la = importlib.import_module("ray_tpu.ops.linear_attention")

    def real():
        return llama._ssm_mixer, la.ssm_step, la.ssm_chunked, la.fold_state
    before = real()
    with numerics.planted(numerics.FAULTS["state in bfloat16"]):
        assert la.ssm_step is not before[1]
        assert la.fold_state is not before[3]
        # a planted step wins over the kernel, on every backend
        assert la.state_step_kind(
            la.jnp.zeros((1, 1, 4, 16, 128)), 8, 64, True) == "rule"
    with numerics.planted(numerics.FAULTS["the gate after the norm"]):
        assert llama._ssm_mixer is not before[0]
    with numerics.planted(
            numerics.FAULTS["the padded tail updating the state"]):
        assert la.ssm_chunked is not before[2]
    assert real() == before


def test_the_planted_mixers_differ_from_the_programs_own_only_as_named():
    """At a tiny size, one mixer over one sequence: the honest stand-in (no
    switch set) is the program's mixer; each switch, and each zeroed leaf,
    moves its output."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    numerics = tool("numerics_granite_hybrid")
    llama = importlib.import_module("ray_tpu.models.llama")
    model = llama.LlamaConfig(
        embed_dim=64, num_layers=1, layer_pattern=("ssm",), linear_heads=8,
        linear_key_dim=16, linear_value_dim=16, dtype=jnp.float32)
    params = {"layers": (llama._init_group(
        jax.random.PRNGKey(3), model, 1, 0, 16, "ssm"),)}
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 64))
    state = llama._no_cache(model, None, None)

    def out(mixer, tree=params):
        p = jax.tree.map(lambda a: a[0], {"ssm": tree["layers"][0]["ssm"]})
        return jax.jit(lambda h: mixer(model, p, h, state, None, None)[0])(h)
    want = out(llama._ssm_mixer)
    np.testing.assert_allclose(out(numerics._mixer()), want, atol=1e-6)
    for switch in ("gate_after_norm", "norm_a_head", "raw_delta"):
        moved = out(numerics._mixer(**{switch: True}))
        assert float(jnp.abs(moved - want).max()) > 1e-4, switch
    for leaf in ("D", "conv_bias"):
        moved = out(llama._ssm_mixer, numerics.zeroed(params, leaf))
        assert float(jnp.abs(moved - want).max()) > 1e-4, leaf


def test_float8_rounds_the_matrices_and_nothing_else():
    import jax.numpy as jnp
    numerics = tool("numerics_granite_hybrid")
    tree = {"layers": ({"ssm": {
        "win": jnp.full((2, 2), 1.07), "wout": jnp.full((2, 2), 1.07),
        "A_log": jnp.full((2,), 1.07), "D": jnp.full((2,), 1.07),
        "conv": jnp.full((2, 2), 1.07), "norm": jnp.full((2,), 1.07)},
        "mlp": {"wgu": jnp.full((2, 2), 1.07),
                "router": jnp.full((2, 2), 1.07)}},),
        "wte": jnp.full((2, 2), 1.07)}
    out = numerics.to_float8(tree)
    layer = out["layers"][0]
    for rounded in (layer["ssm"]["win"], layer["ssm"]["wout"],
                    layer["mlp"]["wgu"]):
        assert float(rounded[0, 0]) == 1.125             # 3 bits of mantissa
    for kept in (layer["ssm"]["A_log"], layer["ssm"]["D"],
                 layer["ssm"]["conv"], layer["ssm"]["norm"],
                 layer["mlp"]["router"], out["wte"]):
        assert float(kept.reshape(-1)[0]) == float(jnp.float32(1.07))
