"""OLMoE's plain reference against ``ray_tpu/models/llama.py`` at a tiny
size: the full forward, and prefill then decode through the paged cache by
the engine's own two programs, the way the replica checks it on the chip.

Routing is discrete, so the comparison has two parts.  In float32 the two
must agree to rounding AND choose the same experts.  In the configuration's
bfloat16 a token whose last chosen and first unchosen expert are close may
choose differently, so there the logits are held to the configuration's
``numerics.logits_rtol`` (relative Frobenius error) with the share of equal
expert sets beside it; three planted faults and a precision below bfloat16
have to fall outside it.  The tolerance is a property of the widths: this
file holds the tiny configuration to the tiny configuration's (bfloat16
rounds a 64-wide block to 0.02), and the published widths' own readings,
taken on the chip by ``benchmark/tools/numerics_olmoe.py``, stand in
``benchmark/configs/olmoe-1b-7b-0125-4l.json``.
"""

import jax
import jax.numpy as jnp
import pytest

import tiny_olmoe
from benchmark import spec
from benchmark.tools import numerics_olmoe

TINY = tiny_olmoe.TINY_OLMOE
RTOL = TINY["numerics"]["logits_rtol"]
SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
FAULTS = {"gates renormalised": {"norm_topk_prob": True},
          "no q/k norm": {"qk_norm": False},
          "one expert fewer": {"experts_per_token":
                               TINY["num_experts_per_tok"] - 1}}


@pytest.fixture(scope="module")
def family():
    return spec.load_part("families", "olmoe")


@pytest.fixture(scope="module")
def params(family):
    """Seeded weights, with the layers made to matter at this width: the
    init's 0.02 leaves a 64-wide block a rounding error of the embedding
    and its norms the identity.  The experts' output is kept comparable to
    the residual it joins: where it dominates, the last RMSNorm hides a
    renormalised gate."""
    cfg = family.program_config(TINY, 48)
    p = family.init(jax.random.PRNGKey(1), cfg)
    layers = p["layers"]
    layers["mlp"] = {"router": layers["mlp"]["router"] * 5,
                     "wgu": layers["mlp"]["wgu"] * 10,
                     "wd": layers["mlp"]["wd"] * 10}
    layers["attn"]["wo"] = layers["attn"]["wo"] * 5
    for name, seed in (("q_norm", 5), ("k_norm", 6)):
        layers["attn"][name] = 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(seed), layers["attn"][name].shape)
    return p


def rel_err(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def served(family, params, reference=None, **overrides):
    """(largest logits error of the two sequences, equal expert sets, of)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    engine = InferenceEngine(EngineConfig(
        model=family.ENGINE_MODEL,
        model_config=family.program_config(TINY, 48, **overrides),
        **TINY["engine"]), params=params)
    errs, same, pairs = numerics_olmoe.served_and_reference(
        engine, family, TINY, params if reference is None else reference,
        jax.random.PRNGKey(7), steps=8)
    engine.close()
    return max(errs), same, pairs


@pytest.mark.parametrize("renormalised", [False, True])
def test_forward_agrees_with_the_reference_in_float32(family, params,
                                                      renormalised):
    from ray_tpu.models.llama import llama_forward
    config = {**TINY, "norm_topk_prob": renormalised}
    cfg = family.program_config(config, 48, dtype=jnp.float32,
                                attention="dense")
    assert (cfg.num_experts, cfg.experts_per_token, cfg.mlp_dim,
            cfg.qk_norm, cfg.norm_topk_prob) == (8, 3, 32, True,
                                                 renormalised)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 97)
    want, gates = family.reference_forward(params, tokens, config,
                                           with_gates=True)
    assert rel_err(llama_forward(params, tokens, cfg), want) < 1e-5
    assert gates.shape == (2, 2, 33, 8)
    assert ((gates > 0).sum(-1) == 3).all()
    total = gates.sum(-1)
    assert (jnp.abs(total - 1) < 1e-5).all() if renormalised else \
        (total < 0.999).all()


def test_served_float32_agrees_and_chooses_the_same_experts(family, params):
    err, same, pairs = served(family, params, dtype=jnp.float32)
    assert err < 1e-4
    assert same == pairs == 2 * 8 * TINY["num_hidden_layers"]


def test_served_bfloat16_is_inside_the_configurations_tolerance(family,
                                                                params):
    err, same, pairs = served(family, params)
    assert 1e-4 < err < RTOL
    assert same >= 0.75 * pairs


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 1e-4),
                                             (jnp.bfloat16, RTOL)])
def test_a_planted_fault_is_outside_the_tolerance(family, params, fault,
                                                  dtype, tolerance):
    err, _, _ = served(family, params, dtype=dtype, **FAULTS[fault])
    assert err > 2 * tolerance


def test_a_precision_below_bfloat16_is_outside_the_tolerance(family, params):
    """The program's weights rounded to float8 before its bfloat16
    products, against the reference on the weights as they are."""
    err, _, _ = served(family, numerics_olmoe.to_float8(params),
                       reference=params)
    assert err > RTOL


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("clip_qkv", 8.0), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("tie_word_embeddings", True), ("num_experts_per_tok", 9),
    ("num_attention_heads", 5)])
def test_family_refuses_what_the_program_cannot_run(family, key, value):
    with pytest.raises(ValueError):
        family.program_config({**TINY, key: value}, 48)


def lowered(cfg):
    from ray_tpu.models.llama import (llama_decode_step, llama_init,
                                      llama_init_paged_cache)
    params = jax.eval_shape(lambda: llama_init(jax.random.PRNGKey(0), cfg))
    kp, vp = jax.eval_shape(lambda: llama_init_paged_cache(cfg, 13, 8))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    step = jax.jit(lambda p, *a: llama_decode_step(p, cfg, *a)).lower(
        params, ints(4), ints(4), kp, vp, ints(4, 6))
    return step.as_text(debug_info=True), step.out_info


def test_only_an_expert_model_has_the_scopes_and_the_load(family):
    from ray_tpu.models.llama import LlamaConfig
    text, out = lowered(family.program_config(TINY, 48))
    assert all(scope in text for scope in SCOPES)
    assert len(out) == 4 and out[3].shape == (2, 8)
    text, out = lowered(LlamaConfig.tiny(seq=48))
    assert not any(scope in text for scope in SCOPES)
    assert len(out) == 3
