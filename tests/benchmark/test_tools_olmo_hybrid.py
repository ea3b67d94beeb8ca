"""The builder's tool ``numerics_olmo_hybrid.py``: the faults it plants.
Beside ``test_tools.py``, which a PR that brings a configuration may not
edit."""

from test_tools_xing import tool


def test_numerics_olmo_hybrid_plants_every_fault_the_issue_lists():
    numerics = tool("numerics_olmo_hybrid")
    assert list(numerics.FAULTS) == [
        "beta without its 2", "the decay left out", "q and k not normalised",
        "the convolution left out", "the gate silu(z) left out",
        "the padded tail updating the state", "rotation on the full layers",
        "state in bfloat16", "float8 weights"]
    for fault in numerics.FAULTS.values():
        assert set(fault) <= {"config", "patch", "weights"} and fault
    from ray_tpu.models import llama
    from ray_tpu.ops import linear_attention as la
    real = (llama._gated_norm, la.gated_delta_step, la.fold_state,
            la.causal_conv)
    with numerics.planted(numerics.FAULTS["state in bfloat16"]):
        assert la.gated_delta_step is not real[1]
        assert la.fold_state is not real[2]
    with numerics.planted(numerics.FAULTS["the gate silu(z) left out"]):
        assert llama._gated_norm is not real[0]
    assert (llama._gated_norm, la.gated_delta_step, la.fold_state,
            la.causal_conv) == real


def test_float8_rounds_the_matrices_and_nothing_else():
    import jax.numpy as jnp
    numerics = tool("numerics_olmo_hybrid")
    tree = {"layers": ({"linear": {
        "wqkv": jnp.full((2, 2), 1.07), "A_log": jnp.full((2,), 1.07),
        "conv": jnp.full((2, 2), 1.07), "norm": jnp.full((2,), 1.07)}},),
        "lm_head": jnp.full((2, 2), 1.07), "wte": jnp.full((2, 2), 1.07)}
    out = numerics.to_float8(tree)
    linear = out["layers"][0]["linear"]
    assert float(linear["wqkv"][0, 0]) == 1.125          # 3 bits of mantissa
    assert float(out["lm_head"][0, 0]) == 1.125
    for kept in (linear["A_log"], linear["conv"], linear["norm"],
                 out["wte"]):
        assert float(kept.reshape(-1)[0]) == float(jnp.float32(1.07))
