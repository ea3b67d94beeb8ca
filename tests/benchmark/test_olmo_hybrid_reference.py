"""Olmo-Hybrid's plain reference (the recurrent form, a position at a time)
against ``ray_tpu/models/llama.py`` (the chunked scan and the one-step rule
on the folded state) at a tiny size: two periods of three linear layers and
one full layer.  The full forward, and prefill then decode through the pools
by the engine's own two programs, the way the replica checks it on the chip.
Two formulations of the rule, so agreement means something; and each fault
of ``benchmark/tools/numerics_olmo_hybrid.py`` has to part them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_olmo_hybrid
from benchmark import spec
from benchmark.reference import olmo_hybrid as reference
from benchmark.replica import seeded_key
from benchmark.tools import numerics_olmo_hybrid as tool
from benchmark.tools.numerics_ouro import errors, reference_logits

TINY = tiny_olmo_hybrid.TINY_HYBRID


@pytest.fixture(scope="module")
def program():
    """Seeded weights as the family makes them, with the norms' scales
    moved off one so that each of them matters."""
    family, model, params = tiny_olmo_hybrid.program()
    groups = []
    for at, group in enumerate(params["layers"]):
        group = dict(group)
        for n, name in enumerate(("ln1_post", "ln2_post")):
            group[name] = {"scale": 1 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 * at + n), group[name]["scale"].shape)}
        if "linear" in group:
            group["linear"] = {**group["linear"], "norm": 1 + 0.3 *
                               jax.random.normal(jax.random.PRNGKey(at + 50),
                                                 group["linear"]["norm"].shape)}
        groups.append(group)
    return family, model, {**params, "layers": tuple(groups)}


def test_the_full_forward_is_the_references(program):
    from ray_tpu.models import llama
    family, model, params = program
    tokens = np.random.default_rng(0).integers(0, 256, (2, 45))
    want = family.reference_forward(params, tokens, TINY)
    got = llama.llama_forward(params, tokens, model)
    assert want.dtype == jnp.float32 and want.shape == (2, 45, 256)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    assert float(jnp.abs(want).max()) > 0.1


def test_the_references_rule_is_the_written_recurrence():
    """Step 5 by hand for two positions of one head."""
    q = jnp.array([[[[1.0, 0.0]]], [[[0.0, 1.0]]]]).transpose(1, 0, 2, 3)
    k = q
    v = jnp.array([[[[2.0, 4.0, 6.0]]], [[[1.0, 1.0, 1.0]]]]).transpose(
        1, 0, 2, 3)
    alpha = jnp.array([[[0.5], [0.5]]])
    beta = jnp.array([[[2.0], [1.0]]])
    o = reference.delta_rule(q, k, v, alpha, beta)
    # t=0: S = 2 k0 v0^T; o0 = 2 v0.  t=1: S = 0.5 S; the write along k1
    # (orthogonal to k0) stores v1; o1 = v1
    np.testing.assert_allclose(o[0, 0, 0], [4.0, 8.0, 12.0])
    np.testing.assert_allclose(o[0, 1, 0], [1.0, 1.0, 1.0])


def test_prefill_then_decode_through_the_pools_is_the_reference(program):
    family, model, params = program
    seqs, served = tool.served_with(family, TINY, TINY["engine"], model,
                                    params, {}, seeded_key(5), 8)
    errs = errors(served, reference_logits(family, TINY, params, seqs))
    assert len(errs) == 2 and max(errs) < 1e-4, errs


@pytest.mark.parametrize("what", list(tool.FAULTS))
def test_every_planted_fault_parts_the_program_from_the_reference(
        program, what):
    """In float32 at the tiny size an honest program is 1e-5 from the
    reference; every fault of the mathematics reads over 0.3, the state kept
    in bfloat16 over 0.005."""
    family, model, params = program
    fault = tool.FAULTS[what]
    weights = tool.to_float8(params) if fault.get("weights") else params
    seqs, served = tool.served_with(
        family, TINY, TINY["engine"], model, weights,
        {} if fault.get("weights") else fault, seeded_key(5), 8)
    errs = errors(served, reference_logits(family, TINY, params, seqs))
    assert min(errs) > (0.005 if what == "state in bfloat16" else 0.3), errs
