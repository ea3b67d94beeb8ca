"""Tests for ray_tpu.models: GPT forward/train-step under real shardings.

Reference analogue: the torch model tests under `python/ray/train/tests/`;
here the interesting property is that one model definition trains correctly
under any MeshSpec on the virtual 8-device mesh.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from dataclasses import replace as dataclasses_replace

from ray_tpu.models.gpt import (GPTConfig, gpt_forward, gpt_init,
                                gpt_loss, gpt_param_axes, make_train_step)
from ray_tpu.models.mlp import mlp_forward, mlp_init, mlp_loss
from ray_tpu.parallel import LogicalAxisRules, MeshSpec
from ray_tpu.parallel.sharding import shard_params

TINY = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=2, num_heads=2,
                 embed_dim=16, dtype=jnp.float32)


def _batch(B=4, S=33, vocab=128, key=0):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(key), (B, S), 0, vocab, jnp.int32)}


def test_gpt_forward_shape():
    params = gpt_init(jax.random.PRNGKey(0), TINY)
    logits = gpt_forward(params, _batch()["tokens"][:, :-1], TINY)
    assert logits.shape == (4, 32, 128)
    assert logits.dtype == jnp.float32


def test_gpt_param_axes_tree_matches():
    params = gpt_init(jax.random.PRNGKey(0), TINY)
    axes = gpt_param_axes(TINY)
    pl = jax.tree_util.tree_structure(
        params, is_leaf=lambda x: not isinstance(x, dict))
    al = jax.tree_util.tree_structure(
        axes, is_leaf=lambda x: not isinstance(x, dict))
    assert pl == al


def test_gpt_causality():
    """Changing future tokens must not change past logits."""
    params = gpt_init(jax.random.PRNGKey(0), TINY)
    toks = _batch()["tokens"][:, :-1]
    logits1 = gpt_forward(params, toks, TINY)
    toks2 = toks.at[:, 20:].set(0)
    logits2 = gpt_forward(params, toks2, TINY)
    np.testing.assert_allclose(logits1[:, :20], logits2[:, :20], atol=1e-5)


@pytest.mark.parametrize("spec", [
    MeshSpec(dp=8),
    MeshSpec(fsdp=8),
    MeshSpec(dp=2, fsdp=2, tp=2),
    MeshSpec(fsdp=2, sp=2, tp=2),
])
def test_gpt_train_step_loss_decreases(spec):
    mesh = spec.build()
    rules = LogicalAxisRules.for_transformer(spec)
    with jax.sharding.set_mesh(mesh):
        params = gpt_init(jax.random.PRNGKey(0), TINY)
        params = shard_params(params, mesh, rules, gpt_param_axes(TINY))
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        step = make_train_step(TINY, tx, rules)
        batch = _batch(B=8)
        losses = []
        for _ in range(5):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("attention", ["auto", "flash"])
def test_gpt_sharded_matches_single_device(attention):
    """Same seed, same batch: dp=8 sharded step == single-device step.
    With "flash" the kernel runs per shard under shard_map (the compiler
    cannot partition it), which must not change the loss either."""
    cfg = dataclasses_replace(TINY, attention=attention)
    batch = _batch(B=8, key=7)
    tx = optax.sgd(1e-2)

    def run(spec_build):
        if spec_build is None:
            params = gpt_init(jax.random.PRNGKey(0), cfg)
            opt_state = tx.init(params)
            step = make_train_step(cfg, tx, None, donate=False)
            for _ in range(2):
                params, opt_state, m = step(params, opt_state, batch)
            return float(m["loss"])
        spec = spec_build
        mesh = spec.build()
        rules = LogicalAxisRules.for_transformer(spec)
        with jax.sharding.set_mesh(mesh):
            params = gpt_init(jax.random.PRNGKey(0), cfg)
            params = shard_params(params, mesh, rules, gpt_param_axes(cfg))
            opt_state = tx.init(params)
            step = make_train_step(cfg, tx, rules, donate=False)
            for _ in range(2):
                params, opt_state, m = step(params, opt_state, batch)
            return float(m["loss"])

    l_single = run(None)
    l_dp = run(MeshSpec(dp=8))
    l_tp = run(MeshSpec(tp=2, fsdp=4))
    assert abs(l_single - l_dp) < 1e-4
    assert abs(l_single - l_tp) < 1e-4


TP_RING_SPECS = {"fsdp2xtp2": MeshSpec(fsdp=2, tp=2),
                 "tp4xfsdp2": MeshSpec(tp=4, fsdp=2),
                 "fsdp2xsp2xtp2": MeshSpec(fsdp=2, sp=2, tp=2)}


@functools.lru_cache(maxsize=None)
def _single_device_loss_and_grads(cfg):
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(B=8, key=7)
    return params, batch, jax.jit(jax.value_and_grad(
        lambda p: gpt_loss(p, batch, cfg)))(params)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("layout", TP_RING_SPECS)
def test_gpt_tp_ring_matches_single_device(layout, remat):
    """Under tp > 1 the block moves its activation sums as chunks around
    the tp ring (parallel/collectives.py), the residual stream sharded
    along the sequence: loss AND gradients are the single device's, at
    tp = 2, around a ring of four, and with sp beside tp."""
    cfg = dataclasses_replace(TINY, num_heads=4, remat=remat,
                              remat_policy="dots")
    params, batch, (want, want_grads) = _single_device_loss_and_grads(cfg)
    spec = TP_RING_SPECS[layout]
    mesh = spec.build()
    rules = LogicalAxisRules.for_transformer(spec)
    with jax.sharding.set_mesh(mesh):
        placed = shard_params(params, mesh, rules, gpt_param_axes(cfg))
        lowered = jax.jit(jax.value_and_grad(
            lambda p: gpt_loss(p, batch, cfg, rules))).lower(placed)
        got, got_grads = lowered.compile()(placed)
    # through the ring, not around it
    assert "collective_permute" in lowered.as_text()
    np.testing.assert_allclose(float(got), float(want), atol=1e-5)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_gpt_ring_attention_mode_trains():
    spec = MeshSpec(fsdp=2, sp=2, tp=2)
    mesh = spec.build()
    rules = LogicalAxisRules.for_transformer(spec)
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=2,
                    num_heads=2, embed_dim=16, dtype=jnp.float32,
                    attention="ring")
    with jax.sharding.set_mesh(mesh):
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        params = shard_params(params, mesh, rules, gpt_param_axes(cfg))
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        step = make_train_step(cfg, tx, rules, mesh=mesh)
        batch = _batch(B=4)
        losses = []
        for _ in range(4):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_mlp_trains():
    params = mlp_init(jax.random.PRNGKey(0), [4, 16, 3])
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 4))
    y = (x.sum(axis=1) > 0).astype(jnp.int32)
    batch = {"x": x, "y": y}
    grad_fn = jax.jit(jax.value_and_grad(mlp_loss))
    loss0, _ = grad_fn(params, batch)
    for _ in range(50):
        loss, g = grad_fn(params, batch)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg, params, g)
    assert loss < loss0


@pytest.mark.parametrize("attention, S, backend, want", [
    ("auto", 512, "tpu", "dense"),      # too short to amortize the grid
    ("auto", 1024, "tpu", "flash"),     # both training cells' length
    ("auto", 1100, "tpu", "dense"),     # not whole 128-lane tiles
    ("auto", 4096, "tpu", "flash"),
    ("auto", 2048, "cpu", "dense"),     # the interpreter never wins
    ("dense", 4096, "tpu", "dense"),    # pinned values come back as given
    ("flash", 512, "cpu", "flash"),
    ("ring", 1024, "tpu", "ring"),
])
def test_attention_auto_dispatch(attention, S, backend, want, monkeypatch):
    """The one function that picks the attention variant: "auto" by S and
    the backend, a pinned value as it is."""
    from ray_tpu.ops.attention import resolve_attention
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_attention(attention, S) == want


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_attention_auto_forward_runs(family):
    """attention="auto" resolves to dense on the CPU and the forward runs."""
    tokens = _batch()["tokens"][:, :-1]
    if family == "gpt":
        cfg = dataclasses_replace(TINY, num_layers=1, attention="auto")
        logits = gpt_forward(gpt_init(jax.random.PRNGKey(0), cfg), tokens,
                             cfg)
    else:
        from ray_tpu.models.llama import (LlamaConfig, llama_forward,
                                          llama_init)
        cfg = LlamaConfig(vocab_size=128, max_seq_len=32, num_layers=1,
                          num_heads=2, num_kv_heads=1, embed_dim=16,
                          mlp_dim=32, dtype=jnp.float32, attention="auto")
        logits = llama_forward(llama_init(jax.random.PRNGKey(0), cfg),
                               tokens, cfg)
    assert logits.shape == (4, 32, 128)


def test_blocked_ce_matches_unblocked():
    """ce_block loss + grads match the full-logits path bit-for-bit-ish
    (f32 tiny config; blocked head must be a pure memory optimization)."""
    params = gpt_init(jax.random.PRNGKey(0), TINY)
    batch = _batch()
    blocked = dataclasses_replace(TINY, ce_block=8)
    l0, g0 = jax.value_and_grad(lambda p: gpt_loss(p, batch, TINY))(params)
    l1, g1 = jax.value_and_grad(lambda p: gpt_loss(p, batch, blocked))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_blocked_ce_llama_and_ragged_block():
    """LlamaConfig.ce_block ("dv" head layout) parity; a block that does
    not divide S falls back to one chunk instead of padding."""
    from ray_tpu.models.llama import (LlamaConfig, llama_init, llama_loss)
    cfg = LlamaConfig.tiny(vocab=64, seq=32)
    cfg = dataclasses_replace(cfg, dtype=jnp.float32)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(B=2, S=33, vocab=64)
    l0 = llama_loss(params, batch, cfg)
    for blk in (8, 7):  # 7 does not divide 32 -> single-chunk fallback
        l1 = llama_loss(params, batch, dataclasses_replace(cfg, ce_block=blk))
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
