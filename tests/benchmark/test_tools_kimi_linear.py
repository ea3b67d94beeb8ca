"""The builder's tool ``numerics_kimi_linear.py``: the faults it plants.
Beside ``test_tools.py``, which a PR that brings a configuration may not
edit."""

import importlib

from test_tools_xing import tool


def test_numerics_kimi_linear_plants_every_fault_the_issue_lists():
    numerics = tool("numerics_kimi_linear")
    assert list(numerics.FAULTS) == [
        "a decay a head, not a channel", "beta doubled",
        "the latent layers' shared key rotated", "the output gate by silu",
        "the shared expert left out", "the bias in the gates",
        "the padded tail updating the state", "state in bfloat16",
        "float8 weights"]
    for fault in numerics.FAULTS.values():
        assert set(fault) <= {"config", "patch", "weights"} and fault
    llama = importlib.import_module("ray_tpu.models.llama")
    la = importlib.import_module("ray_tpu.ops.linear_attention")
    moe = importlib.import_module("ray_tpu.ops.moe")

    def real():
        return (llama._gated_norm, llama._rope_tables, la.kda_gate,
                la.kda_step, la.kda_chunked, la.fold_state, moe._route,
                moe.moe_dropless)
    before = real()
    with numerics.planted(numerics.FAULTS["state in bfloat16"]):
        assert la.kda_step is not before[3]
        assert la.fold_state is not before[5]
    with numerics.planted(numerics.FAULTS["a decay a head, not a channel"]):
        assert la.kda_gate is not before[2]
    with numerics.planted(numerics.FAULTS["the bias in the gates"]):
        assert moe._route is not before[6]
    with numerics.planted(numerics.FAULTS["the shared expert left out"]):
        assert moe.moe_dropless is not before[7]
    with numerics.planted(
            numerics.FAULTS["the latent layers' shared key rotated"]):
        assert llama._rope_tables is not before[1]
    assert real() == before


def test_a_decay_a_head_is_the_mean_of_its_channels():
    import jax
    import jax.numpy as jnp
    import numpy as np
    numerics = tool("numerics_kimi_linear")
    la = importlib.import_module("ray_tpu.ops.linear_attention")
    f = jax.random.normal(jax.random.PRNGKey(0), (5, 2 * 4))
    A_log, dt_bias = jnp.zeros((2,)), jnp.zeros((8,))
    with numerics.planted(numerics.FAULTS["a decay a head, not a channel"]):
        g = la.kda_gate(f, A_log, dt_bias)
    want = la.kda_gate(f, A_log, dt_bias).mean(-1, keepdims=True)
    np.testing.assert_allclose(g, jnp.broadcast_to(want, g.shape), rtol=1e-6)
    assert float(jnp.std(la.kda_gate(f, A_log, dt_bias), axis=-1).min()) > 0


def test_float8_rounds_the_matrices_and_nothing_else():
    import jax.numpy as jnp
    numerics = tool("numerics_kimi_linear")
    tree = {"layers": ({"linear": {
        "wqkv": jnp.full((2, 2), 1.07), "wf_b": jnp.full((2, 2), 1.07),
        "A_log": jnp.full((2,), 1.07), "dt_bias": jnp.full((2,), 1.07),
        "conv": jnp.full((2, 2), 1.07), "norm": jnp.full((2,), 1.07)},
        "mlp": {"wgu": jnp.full((2, 2), 1.07),
                "router": jnp.full((2, 2), 1.07),
                "router_bias": jnp.full((2,), 1.07)}},),
        "lm_head": jnp.full((2, 2), 1.07), "wte": jnp.full((2, 2), 1.07)}
    out = numerics.to_float8(tree)
    layer = out["layers"][0]
    for rounded in (layer["linear"]["wqkv"], layer["linear"]["wf_b"],
                    layer["mlp"]["wgu"], out["lm_head"]):
        assert float(rounded[0, 0]) == 1.125             # 3 bits of mantissa
    for kept in (layer["linear"]["A_log"], layer["linear"]["dt_bias"],
                 layer["linear"]["conv"], layer["linear"]["norm"],
                 layer["mlp"]["router"], layer["mlp"]["router_bias"],
                 out["wte"]):
        assert float(kept.reshape(-1)[0]) == float(jnp.float32(1.07))
