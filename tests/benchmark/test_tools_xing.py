"""The builder's tool ``numerics_xing.py``: the faults it plants. Beside
``test_tools.py``, which a PR that brings a configuration may not edit."""

import importlib.util
import os

from benchmark import spec


def tool(name):
    path = os.path.join(spec.BENCH_DIR, "tools", name + ".py")
    found = importlib.util.spec_from_file_location("bench_tool_" + name, path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


def test_numerics_xing_plants_every_fault_the_issue_lists():
    """ISSUE 34 (e): ten faults of the mathematics and the precision below,
    each planted by a change of configuration, of functions while the
    programs are traced, or of the program's weights."""
    numerics = tool("numerics_xing")
    assert list(numerics.FAULTS) == [
        "softmax scores for sigmoid", "bias left out of the selection",
        "bias left in the gates", "no routed_scaling_factor",
        "no shared expert", "rotation on the unrotated query values",
        "no m^2 in the softmax scale", "one Sinkhorn round",
        "H_post without its 2", "rows averaged at the end",
        "float8 latent path", "float8 latent path and cache",
        "float8 weights"]
    for fault in numerics.FAULTS.values():
        assert set(fault) <= {"config", "patch", "weights"} and fault
    from ray_tpu.models import llama
    from ray_tpu.ops import moe
    real = (llama._hc_coeff, llama._hc_reduce, moe._route)
    with numerics.planted(numerics.FAULTS["H_post without its 2"]):
        assert llama._hc_coeff is not real[0]
    with numerics.planted(numerics.FAULTS["bias left in the gates"]):
        assert moe._route is not real[2]
    assert (llama._hc_coeff, llama._hc_reduce, moe._route) == real
