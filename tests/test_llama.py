"""LLaMA model family tests: RoPE/GQA correctness, causality, and
training parity under real shardings on the virtual 8-device mesh
(same contract as tests/test_models.py for GPT)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.llama import (LlamaConfig, apply_rope, llama_forward,
                                  llama_init, llama_loss, llama_param_axes,
                                  make_train_step, rope_tables)
from ray_tpu.parallel import LogicalAxisRules, MeshSpec
from ray_tpu.parallel.sharding import shard_params

TINY = LlamaConfig(vocab_size=128, max_seq_len=32, num_layers=2,
                   num_heads=4, num_kv_heads=2, embed_dim=16, mlp_dim=48,
                   dtype=jnp.float32)


def _batch(B=4, S=33, vocab=128, key=0):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(key), (B, S), 0, vocab, jnp.int32)}


def test_llama_forward_shape_and_param_axes():
    params = llama_init(jax.random.PRNGKey(0), TINY)
    logits = llama_forward(params, _batch()["tokens"][:, :-1], TINY)
    assert logits.shape == (4, 32, 128)
    axes = llama_param_axes(TINY)
    pl = jax.tree_util.tree_structure(
        params, is_leaf=lambda x: not isinstance(x, dict))
    al = jax.tree_util.tree_structure(
        axes, is_leaf=lambda x: not isinstance(x, dict))
    assert pl == al


def test_llama_causality():
    params = llama_init(jax.random.PRNGKey(0), TINY)
    toks = _batch()["tokens"][:, :-1]
    logits1 = llama_forward(params, toks, TINY)
    logits2 = llama_forward(params, toks.at[:, 20:].set(0), TINY)
    np.testing.assert_allclose(logits1[:, :20], logits2[:, :20], atol=1e-5)


def test_rope_preserves_norm_and_relative_phase():
    cos, sin = rope_tables(8, 4, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 8, 4))
    y = apply_rope(x, cos, sin)
    # Rotation preserves per-pair norms.
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)
    # Position 0 is the identity rotation.
    np.testing.assert_allclose(np.asarray(y[..., 0, :]),
                               np.asarray(x[..., 0, :]), rtol=1e-5)
    # q.k after RoPE depends only on relative distance: the SAME q/k
    # content at positions (3,1) and (4,2) must produce equal scores.
    qv = jax.random.normal(jax.random.PRNGKey(1), (4,))
    kv = jax.random.normal(jax.random.PRNGKey(2), (4,))
    q = jnp.broadcast_to(qv, (1, 1, 8, 4))
    k = jnp.broadcast_to(kv, (1, 1, 8, 4))
    qr, kr = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    d1 = float(jnp.sum(qr[..., 3, :] * kr[..., 1, :]))
    d2 = float(jnp.sum(qr[..., 4, :] * kr[..., 2, :]))
    np.testing.assert_allclose(d1, d2, rtol=1e-4)


def test_gqa_equals_mha_when_kv_heads_match():
    """With num_kv_heads == num_heads and shared kv weights, GQA reduces
    exactly to standard attention — checked by collapsing a 2-kv-head
    config into a 4-kv-head one with duplicated kv projections."""
    cfg_gqa = TINY
    cfg_mha = LlamaConfig(**{**TINY.__dict__, "num_kv_heads": 4})
    params = llama_init(jax.random.PRNGKey(0), cfg_gqa)
    toks = _batch()["tokens"][:, :-1]
    out_gqa = llama_forward(params, toks, cfg_gqa)
    # Duplicate each kv head to build the equivalent MHA weights.
    p2 = jax.tree.map(lambda x: x, params)
    p2["layers"] = dict(p2["layers"])
    attn = dict(p2["layers"]["attn"])
    attn["wkv"] = jnp.repeat(params["layers"]["attn"]["wkv"], 2, axis=3)
    p2["layers"]["attn"] = attn
    out_mha = llama_forward(p2, toks, cfg_mha)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha),
                               atol=2e-5)


@pytest.mark.parametrize("spec", [
    MeshSpec(dp=8),
    MeshSpec(dp=2, fsdp=2, tp=2),
])
def test_llama_train_step_loss_decreases(spec):
    mesh = spec.build()
    rules = LogicalAxisRules.for_transformer(spec)
    with jax.sharding.set_mesh(mesh):
        params = llama_init(jax.random.PRNGKey(0), TINY)
        params = shard_params(params, mesh, rules, llama_param_axes(TINY))
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        step = make_train_step(TINY, tx, rules)
        batch = _batch(B=8)
        losses = []
        for _ in range(5):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_llama_sharded_matches_single_device():
    batch = _batch(B=8, key=7)
    tx = optax.sgd(1e-2)

    def run(spec):
        if spec is None:
            params = llama_init(jax.random.PRNGKey(0), TINY)
            opt_state = tx.init(params)
            step = make_train_step(TINY, tx, None, donate=False)
            for _ in range(2):
                params, opt_state, m = step(params, opt_state, batch)
            return float(m["loss"])
        mesh = spec.build()
        rules = LogicalAxisRules.for_transformer(spec)
        with jax.sharding.set_mesh(mesh):
            params = llama_init(jax.random.PRNGKey(0), TINY)
            params = shard_params(params, mesh, rules,
                                  llama_param_axes(TINY))
            opt_state = tx.init(params)
            step = make_train_step(TINY, tx, rules, donate=False)
            for _ in range(2):
                params, opt_state, m = step(params, opt_state, batch)
            return float(m["loss"])

    l_single = run(None)
    assert abs(l_single - run(MeshSpec(dp=8))) < 1e-4
    assert abs(l_single - run(MeshSpec(tp=2, fsdp=4))) < 1e-4


def test_gqa_grouped_matches_repeat_path():
    """The repeat-free grouped dense attention must equal the
    materialized-repeat formulation exactly."""
    from ray_tpu.ops.attention import _dense_causal_attention_bnsh
    from ray_tpu.models.llama import _dense_causal_attention_gqa
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    B, G, rep, S, H = 2, 2, 3, 16, 8
    q = jax.random.normal(kq, (B, G * rep, S, H))
    k = jax.random.normal(kk, (B, G, S, H))
    v = jax.random.normal(kv, (B, G, S, H))
    grouped = _dense_causal_attention_gqa(q, k, v, rep)
    repeated = _dense_causal_attention_bnsh(
        q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1))
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(repeated),
                               atol=1e-5)
