"""``benchmark/reference/granite_hybrid.py`` against the program at a tiny
size in float32 (ISSUE 61): ``llama_forward``'s logits are the reference's;
prefill and eight token steps through the ENGINE's own programs and pools
give the reference's logits, and leave in the slot's rows the states and the
tail that the reference hands out after those positions; the two shares of a
layer, the shared expert counted once, add up to the UNCUT reference's layer;
the routing code keeps the code's experts with uneven gates; the reference is
written from the equations and shares no code with the program."""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_granite_hybrid
from tiny_granite_hybrid import TINY_GRANITE

STEPS = 8


@pytest.fixture(scope="module")
def program():
    return tiny_granite_hybrid.program()


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0,
                                         256), np.int32)


@pytest.fixture(scope="module")
def wanted(program, tokens):
    """The reference's logits and what it keeps after 16 and 24 positions:
    one traced program for every test of this file."""
    family, _, params = program
    return jax.jit(lambda p, t, after: family.reference_forward(
        p, t, TINY_GRANITE, states_after=after))(
            params, tokens, jnp.asarray([16, 16 + STEPS], jnp.int32))


def rel(got, want):
    return float(np.linalg.norm(np.asarray(got) - np.asarray(want))
                 / np.linalg.norm(np.asarray(want)))


def test_the_forward_is_the_references(program, tokens, wanted):
    from ray_tpu.models import llama
    family, model, params = program
    want = wanted[0]
    got = jax.jit(lambda p, t: llama.llama_forward(p, t, model))(params,
                                                                 tokens)
    assert rel(got, want) < 2e-5
    assert float(jnp.std(want)) > 1e-3            # and says something


def test_the_engines_programs_leave_the_references_states(program, tokens,
                                                          wanted):
    """Logits of the prefill and eight steps, and slot 0's state rows and
    tail after the prefill (of a rung that pads 16 to 32) and after the
    steps, by ``replica_states``' own helpers."""
    from benchmark.replica_states import drive, rel_errs
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    family, model, params = program
    n = 16
    want, kept = wanted
    assert kept["state"].shape == (2, 2, 1, 8, 16, 16)
    assert kept["tail"].shape == (2, 2, 1, 3, 8 * 16 + 2 * 16)
    engine = InferenceEngine(EngineConfig(
        model=family.ENGINE_MODEL, model_config=model,
        **TINY_GRANITE["engine"]), params=params)
    try:
        got, rows, dtype = drive(engine, tokens[0], n)
        assert str(dtype) == "float32"
    finally:
        engine.close()
    assert rel(got, want[0, n - 1:n + STEPS]) < 2e-5
    for moment, (states, tails) in enumerate(rows):
        worst, each = rel_errs(states, np.asarray(kept["state"])[:, moment, 0])
        assert worst < 2e-5 and len(each) == 2
        assert rel_errs(tails, np.asarray(kept["tail"])[:, moment, 0])[0] \
            < 2e-5
    # the two moments differ: the steps moved the rows
    assert rel(rows[1][0], rows[0][0]) > 1e-3


def test_the_shares_of_a_layer_add_up_to_the_uncut_references_layer(program):
    """The program at share 0 and at share 1 of two, each on its own half of
    an UNCUT layer's experts, the shared expert counted once, against the
    reference's layer with all 12 experts."""
    from benchmark.reference import granite_hybrid as reference
    from ray_tpu.models import llama
    family, model, _ = program
    uncut = {**TINY_GRANITE, "num_local_experts": 12, "expert_share": [0, 1]}
    whole = family.program_config(uncut, 48, dtype=jnp.float32,
                                  attention="dense")
    group = jax.jit(lambda key: family.init(key, whole)["layers"][0])(
        jax.random.PRNGKey(3))
    h = jax.random.normal(jax.random.PRNGKey(2), (7, 64))
    lp = jax.tree.map(lambda a: a[0], {"shared": group["shared"]})
    want = reference.feed_forward(
        h[None], lp, jax.tree.map(lambda a: a[0], group["mlp"]), uncut)[0]

    def part(share, mlp):
        cfg = dataclasses.replace(model, expert_share=(share, 2))
        return jax.jit(lambda mlp: llama._ffn(
            cfg, {**lp, "mlp": 0}, h, experts=mlp)[0])(mlp)
    halves = [{name: leaf[:, 6 * share:6 * share + 6]
               if name in ("wgu", "wd") else leaf
               for name, leaf in group["mlp"].items()} for share in (0, 1)]
    shared = part(0, {**halves[0], "wd": jnp.zeros_like(halves[0]["wd"])})
    parts = [part(share, halves[share]) - shared for share in (0, 1)]
    assert rel(parts[0] + parts[1] + shared, want) < 2e-5
    assert min(float(jnp.abs(p).max()) for p in parts) > 0


def test_the_routing_code_keeps_its_experts_with_uneven_gates(program):
    from benchmark.families import granite_hybrid as family
    from benchmark.reference import granite_hybrid as reference
    _, model, params = program
    R, hot = 12, 4
    code = np.asarray(params["wte"][:, :R])
    assert ((code > 0).sum(axis=1) == hot).all()
    assert np.allclose(code.max(axis=1) / np.where(code > 0, code, np.inf)
                       .min(axis=1), family.CODE_SPAN, rtol=1e-5)
    for group in params["layers"]:
        way_out = group["ssm"]["wout"] if "ssm" in group else \
            group["attn"]["wo"]
        assert not np.asarray(way_out)[..., :R].any()
        assert not np.asarray(group["mlp"]["wd"])[..., :R].any()
        assert not np.asarray(group["shared"]["wd"])[..., :R].any()
        router = np.asarray(group["mlp"]["router"][0])
        assert (router[:R].sum(axis=0) == family.CODE_WEIGHT).all()
        assert not router[R:].any()
    h = reference._rms_norm(12.0 * params["wte"][:32].astype(jnp.float32),
                            1.0, 1e-5)
    gates = np.asarray(reference.gate_matrix(
        h, params["layers"][0]["mlp"]["router"][0], TINY_GRANITE))
    assert ((gates > 0).sum(axis=1) == hot).all()
    np.testing.assert_allclose(gates.sum(axis=1), 1.0, rtol=1e-5)
    # far from even and far from one-hot
    assert 1.5 < (gates.max(axis=1) / np.where(gates > 0, gates, np.inf)
                  .min(axis=1)).mean() < 20
    # about half the assignments fall on a half's share
    assert 0.3 < (gates[:, 6:] > 0).mean() * 6 / hot * 2 < 1.7


def test_the_reference_shares_no_code_with_the_program():
    from benchmark.reference import granite_hybrid as reference
    source = inspect.getsource(reference)
    assert "import ray_tpu" not in source and "from ray_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "lax.scan" in source and "pallas" not in source
