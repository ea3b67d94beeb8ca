"""Kimi Linear's plain reference (the KDA rule in its recurrent form, a
position at a time; latent attention expanded a head; the router over all its
experts and the experts over the share it is given) against
``ray_tpu/models/llama.py`` (the chunked scan with sub-chunks and the
one-step rule, the latent prefill and the absorbed read, the dropless path
told its share) at a tiny size.  The full forward, and prefill then decode
through the pools by the engine's own two programs, the way the replica checks
it on the chip.  Two formulations, so agreement means something; each fault of
``benchmark/tools/numerics_kimi_linear.py`` has to part them; and the four
shares' references add up to the uncut model's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_kimi_linear
from benchmark.reference import kimi_linear as reference
from benchmark.replica import seeded_key
from benchmark.tools import numerics_kimi_linear as tool

WHOLE = tiny_kimi_linear.TINY_KIMI
# One period (the dense KDA layer, two KDA layers with experts, the latent
# layer): every mechanism at half the tracing of the eleven engines below;
# two periods run through the full forward here, and through the pools in
# tests/test_llama_kimi_linear.py and the rehearsal.
TINY = {**WHOLE, "num_hidden_layers": 4, "linear_attn_config": {
    **WHOLE["linear_attn_config"], "kda_layers": [1, 2, 3],
    "full_attn_layers": [4]}}


def moved_off_one(config):
    """Seeded weights as the family makes them, with the norms' scales
    moved off one so that each of them matters."""
    family, model, params = tiny_kimi_linear.program(config)
    groups = []
    for at, group in enumerate(params["layers"]):
        group = dict(group)
        for n, name in enumerate(("ln1", "ln2")):
            group[name] = {"scale": 1 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 * at + n), group[name]["scale"].shape)}
        mixer = "linear" if "linear" in group else "attn"
        norm = "norm" if mixer == "linear" else "kv_a_norm"
        group[mixer] = {**group[mixer], norm: 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(at + 50), group[mixer][norm].shape)}
        # at the initialisation's scale a 64-wide model's scores are flat
        # and its sublayers whisper: a rotated key or a missing expert
        # would change nothing
        for name in ("wq", "wkv_a", "wkv_b") if mixer == "attn" else ():
            group[mixer][name] = 8.0 * group[mixer][name]
        group[mixer]["wo"] = 8.0 * group[mixer]["wo"]
        for part in ("mlp", "shared") if "shared" in group else ("mlp",):
            group[part] = {**group[part], "wd": 8.0 * group[part]["wd"]}
        groups.append(group)
    return family, model, {**params, "layers": tuple(groups)}


@pytest.fixture(scope="module")
def program():
    return moved_off_one(TINY)


def test_the_full_forward_is_the_references():
    from ray_tpu.models import llama
    family, model, params = moved_off_one(WHOLE)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 45))
    want, states = family.reference_forward(params, tokens, WHOLE, True)
    got = llama.llama_forward(params, tokens, model)
    assert want.dtype == jnp.float32 and want.shape == (2, 45, 256)
    assert states.shape == (6, 2, 4, 8, 8)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    assert float(jnp.abs(want).max()) > 0.1


def test_the_references_rule_is_the_written_recurrence():
    """Step 4 by hand for two positions of one head with two key channels:
    the second position's decay halves row 0 of the state and keeps row 1."""
    q = jnp.array([[1.0, 0.0], [1.0, 1.0]]).reshape(1, 2, 1, 2)
    k = jnp.array([[1.0, 0.0], [0.0, 1.0]]).reshape(1, 2, 1, 2)
    v = jnp.array([[2.0, 4.0, 6.0], [1.0, 1.0, 1.0]]).reshape(1, 2, 1, 3)
    alpha = jnp.array([[1.0, 1.0], [0.5, 1.0]]).reshape(1, 2, 1, 2)
    beta = jnp.array([1.0, 1.0]).reshape(1, 2, 1)
    o, state = reference.delta_rule(q, k, v, alpha, beta)
    # t=0: S = k0 v0^T (row 0 = v0); o0 = v0.  t=1: row 0 halved; the write
    # along k1 stores v1 in row 1; o1 = row 0 + row 1 = v0 / 2 + v1
    np.testing.assert_allclose(o[0, 0, 0], [2.0, 4.0, 6.0])
    np.testing.assert_allclose(o[0, 1, 0], [2.0, 3.0, 4.0])
    np.testing.assert_allclose(state[0, 0], [[1.0, 2.0, 3.0],
                                             [1.0, 1.0, 1.0]])


def test_the_routing_code_is_what_the_family_says(program):
    """Every token's gates are equal at ``num_experts_per_token`` of the
    router's 32 experts (a share's sum is 2.446 / 4 a kept assignment), the
    same in every precision, and about a quarter fall on the share held."""
    family, model, params = program
    tokens = np.random.default_rng(1).integers(0, 256, (1, 64))
    x = params["wte"][tokens[0]].astype(jnp.float32)
    assert int((x[:, :32] > 0).sum(-1).min()) == 5      # the code's places
    mlp = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    kept = []
    for dtype in (jnp.float32, jnp.bfloat16):
        h = (x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)).astype(
            dtype).astype(jnp.float32)
        gates = reference.gate_matrix(h, mlp["router"], mlp["router_bias"],
                                      TINY)
        assert int((gates > 0).sum(-1).min()) == 4 == \
            int((gates > 0).sum(-1).max())
        np.testing.assert_allclose(gates[gates > 0], 2.446 / 4, rtol=1e-5)
        kept.append(np.asarray(gates[:, 8:16] > 0))
    np.testing.assert_array_equal(*kept)
    assert 0.1 < kept[0].sum() / (64 * 4) < 0.45
    # no sublayer writes the code's places
    for group in params["layers"]:
        mixer = group["linear" if "linear" in group else "attn"]
        assert not np.asarray(mixer["wo"][..., :32]).any()
        assert not np.asarray(group["mlp"]["wd"][..., :32]).any()


def test_the_four_shares_references_add_up_to_the_uncut_layer(program):
    """The share tied to the model, on the reference's side: the routed
    parts that the four shares of the first expert layer give, and the
    shared expert once, are what one tree holding all 32 experts gives."""
    family, _, params = program
    _, whole_model, whole = tiny_kimi_linear.program(
        {**TINY, "num_experts": 32, "expert_share": [0, 1]})
    layer = jax.tree.map(lambda a: a[0], whole["layers"][1])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 64))
    h = h.at[..., :32].set(params["wte"][:64, :32][None].astype(jnp.float32))
    lp = {"shared": jax.tree.map(lambda a: a.astype(jnp.float32),
                                 layer["shared"])}
    with jax.default_matmul_precision("highest"):
        uncut = reference.feed_forward(
            h, lp, layer["mlp"], {**TINY, "expert_share": [0, 1]})
        shared = reference.swiglu(h, lp["shared"]["wgu"], lp["shared"]["wd"])
        parts = []
        for share in range(4):
            mlp = {**layer["mlp"],
                   "wgu": layer["mlp"]["wgu"][8 * share:8 * share + 8],
                   "wd": layer["mlp"]["wd"][8 * share:8 * share + 8]}
            parts.append(reference.feed_forward(
                h, lp, mlp, {**TINY, "expert_share": [share, 4]}) - shared)
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-5)
    assert all(float(jnp.abs(part).max()) > 1e-4 for part in parts)


def test_prefill_then_decode_through_the_pools_is_the_reference(program):
    family, model, params = program
    seqs, got = tool.served_with(family, TINY, TINY["engine"], model,
                                 params, {}, seeded_key(5), 8)
    errs = tool.errors(got, tool.reference(family, TINY, params, seqs))
    assert len(errs["logits_rel_err"]) == 2
    assert max(errs["logits_rel_err"]) < 1e-4, errs
    assert max(errs["state_rel_err"]) < 1e-4, errs


@pytest.mark.parametrize("what", list(tool.FAULTS))
def test_every_planted_fault_parts_the_program_from_the_reference(
        program, what):
    """In float32 at the tiny size an honest program is 1e-6 from the
    reference; every fault of the mathematics reads over 0.03 in the logits
    (the bias in the gates, +-0.2 beside scores of one, over 0.001: three
    expert layers are a small part of a 64-wide stream), the state kept in
    bfloat16 over 0.001 in the logits and in the state."""
    family, model, params = program
    fault = tool.FAULTS[what]
    weights = tool.to_float8(params) if fault.get("weights") else params
    seqs, got = tool.served_with(
        family, TINY, TINY["engine"], model, weights,
        {} if fault.get("weights") else fault, seeded_key(5), 8)
    errs = tool.errors(got, tool.reference(family, TINY, params, seqs))
    floor = {"state in bfloat16": 0.001,
             "the bias in the gates": 0.001}.get(what, 0.03)
    assert min(errs["logits_rel_err"]) > floor, errs
    if what == "state in bfloat16":
        assert min(errs["state_rel_err"]) > 0.001, errs
