"""Each plain reference against ``ray_tpu/models`` at a tiny size, in
float32, where the two must agree to rounding."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark import spec

TINY_GPT2 = {"family": "gpt2", "vocab_size": 97, "n_embd": 32, "n_layer": 2,
             "n_head": 2, "n_inner": None}
TINY_MISTRAL = {"family": "mistral", "vocab_size": 97, "hidden_size": 64,
                "intermediate_size": 96, "num_hidden_layers": 2,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
                "sliding_window": None}


def rel_err(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 97)


def test_gpt2_reference_agrees_with_the_program(tokens):
    from ray_tpu.models.gpt import gpt_forward, gpt_loss
    family = spec.load_part("families", "gpt2")
    cfg = family.program_config(TINY_GPT2, 32, dtype=jnp.float32,
                                attention="dense")
    params = family.init(jax.random.PRNGKey(1), cfg)
    assert rel_err(gpt_forward(params, tokens[:, :-1], cfg),
                   family.reference_forward(params, tokens[:, :-1],
                                            TINY_GPT2)) < 1e-5
    assert float(family.reference_loss(params, tokens, TINY_GPT2)) == \
        pytest.approx(float(gpt_loss(params, {"tokens": tokens}, cfg)),
                      rel=1e-5)


def test_mistral_reference_agrees_with_the_program(tokens):
    from ray_tpu.models.llama import llama_forward
    family = spec.load_part("families", "mistral")
    cfg = family.program_config(TINY_MISTRAL, 32, dtype=jnp.float32,
                                attention="dense")
    assert (cfg.num_kv_heads, cfg.rope_theta, cfg.mlp_dim) == (2, 1e6, 96)
    params = family.init(jax.random.PRNGKey(1), cfg)
    assert rel_err(llama_forward(params, tokens[:, :-1], cfg),
                   family.reference_forward(params, tokens[:, :-1],
                                            TINY_MISTRAL)) < 1e-5


def test_a_wrong_rope_base_is_outside_the_tolerance(tokens):
    """The comparison is tight enough to see a changed constant."""
    from ray_tpu.models.llama import llama_forward
    family = spec.load_part("families", "mistral")
    cfg = family.program_config(TINY_MISTRAL, 32, dtype=jnp.float32,
                                attention="dense")
    params = family.init(jax.random.PRNGKey(1), cfg)
    wrong = dataclasses.replace(cfg, rope_theta=1e4)
    assert rel_err(llama_forward(params, tokens[:, :-1], wrong),
                   family.reference_forward(params, tokens[:, :-1],
                                            TINY_MISTRAL)) > 1e-3


def test_families_refuse_what_the_program_cannot_run():
    with pytest.raises(ValueError):
        spec.load_part("families", "mistral").program_config(
            {**TINY_MISTRAL, "sliding_window": 4096}, 32)
    with pytest.raises(ValueError):
        spec.load_part("families", "gpt2").program_config(
            {**TINY_GPT2, "n_inner": 100}, 32)
