"""The builder's tool ``numerics_sdar.py``: the faults it plants. Beside
``test_tools.py``, which a PR that brings a configuration may not edit."""

import importlib
import importlib.util
import os

import jax.numpy as jnp
import numpy as np

from benchmark import spec


def tool(name):
    path = os.path.join(spec.BENCH_DIR, "tools", name + ".py")
    found = importlib.util.spec_from_file_location("bench_tool_" + name, path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


def test_numerics_sdar_plants_every_fault_the_issue_lists():
    """ISSUE 40, Tentpole 6: seven faults of the mathematics and the
    precision below, each planted by a change of configuration, of functions
    while the programs are traced, or of the program's weights."""
    numerics = tool("numerics_sdar")
    assert list(numerics.FAULTS) == [
        "float8 weights", "top-7", "no q/k norm",
        "q/k norm pooled over all heads", "causal mask inside the block",
        "last denoise pass's K/V kept at a commit", "logits shifted by one"]
    for fault in numerics.FAULTS.values():
        assert set(fault) <= {"config", "patch", "weights"} and fault
    from ray_tpu.models import llama
    paged = importlib.import_module("ray_tpu.ops.paged_attention")
    real = (llama._qk, llama.llama_block_step, paged.paged_block_attention)
    with numerics.planted(numerics.FAULTS["causal mask inside the block"]):
        assert paged.paged_block_attention is not real[2]
        assert llama._qk is real[0]
    with numerics.planted(numerics.FAULTS["logits shifted by one"]):
        assert llama.llama_block_step is not real[1]
    assert (llama._qk, llama.llama_block_step,
            paged.paged_block_attention) == real


def test_float8_rounds_the_matrices_and_leaves_norms_and_router():
    numerics = tool("numerics_sdar")
    x = jnp.asarray([1.0, 1.03, 1.09, -3.3, 0.0], jnp.bfloat16)
    got = np.asarray(numerics.round_to_float8(x), np.float32)
    np.testing.assert_array_equal(got, [1.0, 1.0, 1.125, -3.25, 0.0])
    tree = {"wte": x, "ln_f": {"scale": x.astype(jnp.float32)},
            "layers": {"mlp": {"router": x.astype(jnp.float32), "wd": x}}}
    out = numerics.to_float8(tree)
    assert out["wte"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["wte"], np.float32), got)
    np.testing.assert_array_equal(out["ln_f"]["scale"], tree["ln_f"]["scale"])
    np.testing.assert_array_equal(out["layers"]["mlp"]["router"],
                                  tree["layers"]["mlp"]["router"])
    np.testing.assert_array_equal(
        np.asarray(out["layers"]["mlp"]["wd"], np.float32), got)
