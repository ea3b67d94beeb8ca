"""Ouro's plain reference against ``ray_tpu/models/llama.py`` at a tiny size
(64 wide, 4 heads of 16, 3 layers run 3 times): the full forward, and
prefill then decode through the paged cache by the engine's own two
programs, the way the replica checks it on the chip.

In float32 the two agree to rounding.  In the configuration's bfloat16 the
logits are held to the configuration's ``numerics.logits_rtol`` (relative
Frobenius error), and each planted fault of
``benchmark/tools/numerics_ouro.py`` has to fall outside it.  The tolerance
is a property of the widths: this file holds the tiny configuration to the
tiny configuration's, and the published size's own readings, taken on the
chip by that tool, stand in ``benchmark/configs/ouro-2.6b.json``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_ouro
from benchmark import spec
from benchmark.tools import numerics_ouro

TINY = tiny_ouro.TINY_OURO
RTOL = TINY["numerics"]["logits_rtol"]
SEQ = 48


@pytest.fixture(scope="module")
def family():
    return spec.load_part("families", "ouro")


@pytest.fixture(scope="module")
def params(family):
    """Seeded weights as the family makes them (the stored tree), with the
    norm scales moved off one so that each of the five kinds matters."""
    p = family.init(jax.random.PRNGKey(1), family.program_config(TINY, SEQ))
    layers = p["layers"]
    for n, name in enumerate(("ln1", "ln2", "ln1_post", "ln2_post")):
        layers[name] = {"scale": 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(10 + n), layers[name]["scale"].shape)}
    p["ln_f"] = {"scale": 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(20), p["ln_f"]["scale"].shape)}
    return p


def rel_err(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def served(family, params, what="as configured", reference=None,
           **overrides):
    """Largest logits error of the two sequences, prefill then eight decode
    positions, with the fault ``what`` planted in the program."""
    model = family.program_config(TINY, SEQ, **overrides)
    seqs, got = numerics_ouro.served_with(
        family, TINY, TINY["engine"], model, params,
        numerics_ouro.FAULTS.get(what, {}), jax.random.PRNGKey(7), 8)
    return max(numerics_ouro.errors(got, numerics_ouro.reference_logits(
        family, TINY, params if reference is None else reference, seqs)))


def test_the_family_makes_the_stored_tree_and_the_looped_program(family,
                                                                 params):
    cfg = family.program_config(TINY, SEQ)
    assert (cfg.ut_steps, cfg.post_norm, cfg.num_layers, cfg.head_dim) == \
        (3, True, 3, 16)
    assert params["layers"]["mlp"]["wgu"].dtype == jnp.bfloat16
    assert params["lm_head"].dtype == jnp.bfloat16
    assert params["layers"]["ln1_post"]["scale"].dtype == jnp.float32
    assert params["layers"]["ln2_post"]["scale"].shape == (3, 64)
    layer = 4 * 64 * 64 + 3 * 64 * 96
    assert family.decode_weight_params(TINY) == 3 * 3 * layer + 64 * 256
    assert family.kv_bytes_per_token(TINY) == 3 * 3 * 2 * 4 * 16 * 2
    published = spec.load_json("configs", "ouro-2.6b.json")
    assert family.kv_bytes_per_token(published) == 3 * 2 ** 19   # 1.5 MiB
    assert family.decode_weight_params(published) == \
        4 * 48 * 51380224 + 2048 * 49152


def test_forward_agrees_with_the_reference_in_float32(family, params):
    from ray_tpu.models.llama import llama_forward
    cfg = family.program_config(TINY, SEQ, dtype=jnp.float32,
                                attention="dense")
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 256)
    want = family.reference_forward(params, tokens, TINY)
    assert want.dtype == jnp.float32 and want.shape == (2, 33, 256)
    assert rel_err(llama_forward(params, tokens, cfg), want) < 1e-5


def test_served_float32_agrees_with_the_references_full_forward(family,
                                                                params):
    assert served(family, params, dtype=jnp.float32) < 1e-4


def test_served_bfloat16_is_inside_the_configurations_tolerance(family,
                                                                params):
    assert 1e-4 < served(family, params) < RTOL


@pytest.mark.parametrize("fault", [f for f, v in
                                   numerics_ouro.FAULTS.items()
                                   if not v.get("weights")])
@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 1e-4),
                                             (jnp.bfloat16, RTOL)])
def test_a_planted_fault_is_outside_the_tolerance(family, params, fault,
                                                  dtype, tolerance):
    assert served(family, params, fault, dtype=dtype) > 2 * tolerance


def test_a_precision_below_bfloat16_is_outside_the_tolerance(family, params):
    """The program's matrices rounded to float8 before its bfloat16
    products, against the reference on the weights as they are."""
    rounded = numerics_ouro.to_float8(params)
    wq, was = (t["layers"]["attn"]["wq"] for t in (rounded, params))
    assert wq.dtype == was.dtype and not np.array_equal(wq, was)
    assert served(family, rounded, "float8 weights",
                  reference=params) > 2 * RTOL


def test_the_gate_is_idle_at_the_published_threshold_and_not_below(
        family, params):
    """At threshold 1 every position leaves at the last pass, whatever the
    gate says short of saturation, and the logits are the gateless ones; at
    0.5 a gate that is sure after the first pass lets every position leave
    there, with that pass's hidden state under the head."""
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 17), 0, 256)
    plain = family.reference_forward(params, tokens, TINY)
    for bias in (-3.0, 0.0, 3.0):
        gate = (0.02 * jax.random.normal(jax.random.PRNGKey(4), (64,)),
                jnp.float32(bias))
        logits, at = family.reference_forward(params, tokens, TINY, gate)
        assert (at == 2).all()
        np.testing.assert_array_equal(logits, plain)
    sure = (jnp.zeros((64,)), jnp.float32(3.0))       # lam = 0.95 each pass
    logits, at = family.reference_forward(params, tokens, TINY, sure,
                                          threshold=0.5)
    assert (at == 0).all()
    assert rel_err(logits, plain) > 0.1
    one_pass = family.reference_forward(
        params, tokens, {**TINY, "total_ut_steps": 1})
    np.testing.assert_allclose(logits, one_pass, rtol=1e-5, atol=1e-5)
    unsure = (jnp.zeros((64,)), jnp.float32(-1.0))    # 0.27, 0.47, 0.61
    _, at = family.reference_forward(params, tokens, TINY, unsure,
                                     threshold=0.5)
    assert (at == 2).all()
    unsure = (jnp.zeros((64,)), jnp.float32(-0.5))    # 0.38, 0.61
    _, at = family.reference_forward(params, tokens, TINY, unsure,
                                     threshold=0.5)
    assert (at == 1).all()


@pytest.mark.parametrize("key,value", [
    ("early_exit_threshold", 0.5), ("sliding_window", 4096),
    ("use_sliding_window", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("tie_word_embeddings", True), ("head_dim", 32),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"]),
    ("total_ut_steps", 0)])
def test_family_refuses_what_the_program_cannot_run(family, key, value):
    with pytest.raises(ValueError):
        family.program_config({**TINY, key: value}, SEQ)


def test_the_published_configuration_is_the_catalogs(family):
    """Every width as published, nothing reduced, the engine the issue
    sized, and the program it makes."""
    config = spec.load_json("configs", "ouro-2.6b.json")
    assert {k: config[k] for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "total_ut_steps", "early_exit_threshold",
        "rope_theta", "rms_norm_eps", "max_position_embeddings")} == {
        "hidden_size": 2048, "intermediate_size": 5632,
        "num_hidden_layers": 48, "num_attention_heads": 16,
        "num_key_value_heads": 16, "head_dim": 128, "vocab_size": 49152,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "rope_theta": 1000000, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 65536}
    assert config["reduced"] == [] and len(config["layer_types"]) == 48
    assert {"layer_norms", "final_norm_every_pass", "cache_per_pass",
            "early_exit_gate", "biases", "weights", "positions",
            "last_step_cache_reuse"} <= set(config["assumed"])
    assert config["engine"] == {"page_size": 16, "max_prompt_len": 128,
                                "max_new_tokens": 192, "max_batch": 12,
                                "num_pages": 241}
    cfg = family.program_config(config, 320)
    assert (cfg.ut_steps, cfg.post_norm, cfg.num_layers, cfg.embed_dim,
            cfg.mlp_dim, cfg.head_dim, cfg.num_kv_heads, cfg.rms_eps) == \
        (4, True, 48, 2048, 5632, 128, 16, 1e-6)
