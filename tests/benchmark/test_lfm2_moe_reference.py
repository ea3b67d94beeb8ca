"""LFM2's plain reference (the convolution an explicit sum over three shifted
copies, attention a head at a time, every expert on every token) against
``ray_tpu/models/llama.py`` (``_conv_operator`` with the tail handed to a
decode slot and stepped there, K/V pages, the dropless path) at a tiny size:
the full forward, and prefill then decode through the pools by the engine's
own two programs, the way the replica checks it on the chip.  Two
formulations, so agreement means something; and each fault of
``benchmark/tools/numerics_lfm2_moe.py`` has to part them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_lfm2_moe
from benchmark.reference import lfm2_moe as reference
from benchmark.replica import seeded_key
from benchmark.tools import numerics_lfm2_moe as tool

WHOLE = tiny_lfm2_moe.TINY_LFM2
# One dense conv layer, an attention layer and a conv layer with experts:
# every mechanism at half the tracing of the thirteen engines below
# (the six layers run through the full forward here, and through the pools in
# tests/test_llama_lfm2.py and the rehearsal); and an engine of one prefill
# rung and two decode rungs, whose prompts of 7 and 4 do not fill the rung.
TINY = {**WHOLE, "num_hidden_layers": 3,
        "layer_types": WHOLE["layer_types"][:3]}
ENGINE = {"page_size": 16, "max_prompt_len": 16, "max_new_tokens": 16,
          "max_batch": 2, "num_pages": 5}


def moved_off_one(config):
    """Seeded weights as the family makes them, with the norms' scales moved
    off one so that each of them matters, and the operators' and the
    feed-forwards' ways out eight times louder: at the initialisation's scale
    a 64-wide model's sublayers whisper and its scores are flat, and a
    missing gate or rotation would change nothing."""
    family, model, params = tiny_lfm2_moe.program(config)
    groups = []
    for at, group in enumerate(params["layers"]):
        group = dict(group)
        for n, name in enumerate(("ln1", "ln2")):
            group[name] = {"scale": 1 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 * at + n), group[name]["scale"].shape)}
        if "conv" in group:
            # B, C and z of order one, so that each of the gates matters
            group["conv"] = {**group["conv"],
                             "win": 12.0 * group["conv"]["win"],
                             "wout": 8.0 * group["conv"]["wout"]}
        else:
            attn = dict(group["attn"])
            for n, name in enumerate(("q_norm", "k_norm")):
                attn[name] = 1 + 0.3 * jax.random.normal(
                    jax.random.PRNGKey(at + 50 + n), attn[name].shape)
            attn.update(wq=8.0 * attn["wq"], wkv=8.0 * attn["wkv"],
                        wo=8.0 * attn["wo"])
            group["attn"] = attn
        group["mlp"] = {**group["mlp"], "wgu": 8.0 * group["mlp"]["wgu"],
                        "wd": 8.0 * group["mlp"]["wd"]}
        groups.append(group)
    return family, model, {**params, "layers": tuple(groups)}


@pytest.fixture(scope="module")
def program():
    return moved_off_one(TINY)


def test_the_full_forward_is_the_references():
    from ray_tpu.models import llama
    family, model, params = moved_off_one(WHOLE)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 21))
    want = jax.jit(lambda p, t: family.reference_forward(p, t, WHOLE))(
        params, tokens)
    got = jax.jit(lambda p, t: llama.llama_forward(p, t, model))(
        params, tokens)
    assert want.dtype == jnp.float32 and want.shape == (2, 21, 256)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    assert float(jnp.abs(want).max()) > 0.1
    assert "lm_head" not in params            # the head is the table


def test_the_references_convolution_is_the_written_sum():
    """Three taps by hand: position t is w0 u[t-2] + w1 u[t-1] + w2 u[t],
    zeros before the sequence, nothing after the sum."""
    D = 2
    h = jnp.arange(8.0).reshape(1, 4, D) - 3.0
    eye = jnp.eye(D)
    p = {"win": jnp.concatenate([eye, 2 * eye, 3 * eye], axis=1),
         "taps": jnp.array([[1.0, 1.0], [10.0, 10.0], [100.0, -100.0]]),
         "wout": eye}
    u = h * 3 * h                                       # B * z
    want = np.zeros((4, D))
    for t in range(4):
        for j in range(3):
            if t - 2 + j >= 0:
                want[t] += np.asarray(p["taps"][j] * u[0, t - 2 + j])
    want = np.asarray(2 * h[0]) * want                  # C *
    np.testing.assert_allclose(reference.short_conv(h, p)[0], want,
                               rtol=1e-6)
    assert (want < 0).any()                             # no SiLU clipped it


def test_the_routing_code_is_what_the_family_says(program):
    """Every token's gates are equal at ``num_experts_per_tok`` of the 8
    experts and sum to ``routed_scaling_factor`` (over the sum + 1e-6), the
    same experts in bfloat16 as in float32; no sublayer writes the code's
    places."""
    family, model, params = program
    tokens = np.random.default_rng(1).integers(0, 256, (1, 64))
    x = params["wte"][tokens[0]].astype(jnp.float32)
    assert int((x[:, :8] > 0).sum(-1).min()) == 5       # the code's places
    mlp = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    chosen = []
    for dtype in (jnp.float32, jnp.bfloat16):
        h = (x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)).astype(
            dtype).astype(jnp.float32)
        gates = reference.gate_matrix(h, mlp["router"], mlp["router_bias"],
                                      TINY)
        assert int((gates > 0).sum(-1).min()) == 4 == \
            int((gates > 0).sum(-1).max())
        np.testing.assert_allclose(gates[gates > 0], 0.25, rtol=1e-5)
        chosen.append(np.asarray(gates > 0))
    np.testing.assert_array_equal(*chosen)
    for group in params["layers"]:
        out = group["conv"]["wout"] if "conv" in group else \
            group["attn"]["wo"]
        assert not np.asarray(out[..., :8]).any()
        assert not np.asarray(group["mlp"]["wd"][..., :8]).any()


CASES = ["as configured", *tool.FAULTS]


@pytest.mark.parametrize("what", CASES)
def test_prefill_then_decode_against_the_reference_and_every_fault(
        program, what):
    """In float32 at the tiny size the honest program, prefill and then 8
    decode positions through the pools, is 1e-4 from the reference; every
    planted fault reads over 0.01 on both sequences, but two that only move
    the routed sum of two 16-wide expert layers in a 64-wide stream: the
    bias in the gates (+-0.2 beside scores of one) over 0.003, and softmax
    scoring (the code's five experts then tie, the four LOWEST run and not
    the four the bias names, with the same gates: one expert in some tokens)
    over 0.0005, five hundred times the honest reading."""
    family, model, params = program
    fault = tool.FAULTS.get(what, {})
    weights = tool.to_float8(params) if fault.get("weights") else params
    seqs, got = tool.served_with(
        family, TINY, ENGINE, model, weights,
        {} if fault.get("weights") else fault, seeded_key(5), 8)
    errs = tool.errors(got, tool.reference(family, TINY, params, seqs))
    assert len(errs["logits_rel_err"]) == 2
    if what == "as configured":
        assert max(errs["logits_rel_err"]) < 1e-4, errs
    else:
        floor = {"the bias in the gates": 0.003,
                 "softmax scoring": 0.0005}.get(what, 0.01)
        assert min(errs["logits_rel_err"]) > floor, errs
