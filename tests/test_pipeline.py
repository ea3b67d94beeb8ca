"""Pipeline parallelism: GPipe over pp axis matches non-pipelined numerics.

Reference has no PP (SURVEY §2.4) — these tests validate the new capability:
forward parity, gradient parity (the autodiff-derived backward schedule),
and loss decrease over steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.models.gpt_pipeline import (gpt_loss_pipelined,
                                        make_pipeline_train_step)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _setup(pp=2, dp=4):
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = MeshSpec(dp=dp, pp=pp).build()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=4,
                    num_heads=2, embed_dim=32, dtype=jnp.float32)
    params = gpt_init(jax.random.PRNGKey(1), cfg)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    # batch must give microbatches divisible by dp: 16 / M=4 -> mb=4 over dp=4
    tokens = np.random.RandomState(0).randint(0, 128, (16, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    return mesh, cfg, params, batch


def test_forward_parity_pp2():
    mesh, cfg, params, batch = _setup()
    ref = float(gpt_loss(params, batch, cfg))
    got = float(gpt_loss_pipelined(params, batch, cfg, mesh,
                                   num_microbatches=4))
    assert abs(got - ref) < 1e-5


def test_grad_parity_pp2():
    mesh, cfg, params, batch = _setup()
    g_ref = jax.grad(gpt_loss)(params, batch, cfg)
    g_pp = jax.grad(gpt_loss_pipelined)(params, batch, cfg, mesh,
                                        num_microbatches=4)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    flat_pp = jax.tree_util.tree_leaves(g_pp)
    for a, b in zip(flat_ref, flat_pp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_pipeline_training_learns():
    import optax
    mesh, cfg, params, batch = _setup()
    tx = optax.adamw(1e-2)
    step = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4,
                                    donate=False)
    opt_state = tx.init(params)
    losses = []
    for _ in range(5):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_odd_microbatch_count():
    """M=3 against pp=2: fill/drain phases are asymmetric (T = M+pp-1 = 4)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = MeshSpec(dp=2, pp=2).build()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=4,
                    num_heads=2, embed_dim=32, dtype=jnp.float32)
    params = gpt_init(jax.random.PRNGKey(1), cfg)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    tokens = np.random.RandomState(0).randint(0, 128, (12, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    ref = float(gpt_loss(params, batch, cfg))
    got = float(gpt_loss_pipelined(params, batch, cfg, mesh,
                                   num_microbatches=3))
    assert abs(got - ref) < 1e-5


def test_pipeline_with_flash_attention():
    """Flash attention (Pallas interpret on CPU) inside pipeline stages
    must match the non-pipelined dense loss (VERDICT r2 #10)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = MeshSpec(dp=2, pp=2).build()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=4,
                    num_heads=2, embed_dim=32, dtype=jnp.float32,
                    attention="flash")
    params = gpt_init(jax.random.PRNGKey(2), cfg)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    tokens = np.random.RandomState(1).randint(0, 128, (8, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    ref = float(gpt_loss(params, batch, cfg))
    got = float(gpt_loss_pipelined(params, batch, cfg, mesh,
                                   num_microbatches=4))
    assert abs(got - ref) < 1e-4


def test_pipeline_moe_ep_aux_preserved():
    """pp x ep: expert weights shard over ep inside the stages and the
    load-balance aux loss survives the schedule — the pipelined loss
    (which includes moe_aux_coef * aux) matches the GSPMD reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = MeshSpec(dp=2, pp=2, ep=2).build()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=4,
                    num_heads=2, embed_dim=32, dtype=jnp.float32,
                    num_experts=4, expert_top_k=2)
    params = gpt_init(jax.random.PRNGKey(3), cfg)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    tokens = np.random.RandomState(2).randint(0, 128, (8, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    ref = float(gpt_loss(params, batch, cfg))          # includes aux term
    got = float(gpt_loss_pipelined(params, batch, cfg, mesh,
                                   num_microbatches=4))
    assert abs(got - ref) < 1e-4
    # and the aux is genuinely nonzero (the term isn't vacuously matched)
    from ray_tpu.models.gpt import gpt_forward_with_aux
    _, aux = gpt_forward_with_aux(params, batch["tokens"][:, :-1], cfg)
    assert float(aux) > 0.0


def test_pipeline_moe_ep_trains():
    """One pp x ep training step runs end to end and the loss is finite."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = MeshSpec(dp=2, pp=2, ep=2).build()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=4,
                    num_heads=2, embed_dim=32, dtype=jnp.float32,
                    num_experts=4, expert_top_k=2)
    params = gpt_init(jax.random.PRNGKey(4), cfg)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    tokens = np.random.RandomState(3).randint(0, 128, (8, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    tx = optax.adamw(1e-3)
    step = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4,
                                    donate=False)
    params2, _, m = step(params, tx.init(params), batch)
    assert np.isfinite(float(m["loss"]))
    # expert weights actually moved
    d = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()),
        params["layers"]["mlp"], params2["layers"]["mlp"]))
    assert max(d) > 0.0


# ------------------------------------------------------------ 1F1B + sp

def test_1f1b_loss_and_grad_parity():
    """The hand-scheduled 1F1B backward must match autodiff numerics
    (VERDICT r3 #6): loss vs gpt_loss and grads vs jax.grad, in f32."""
    from ray_tpu.models.gpt_pipeline import gpt_loss_1f1b
    mesh, cfg, params, batch = _setup()
    M = 4   # microbatch size 16/M must stay divisible by dp=4
    ref = float(gpt_loss(params, batch, cfg))
    got = float(jax.jit(lambda p, b: gpt_loss_1f1b(
        p, b, cfg, mesh, num_microbatches=M))(params, batch))
    assert abs(got - ref) < 1e-5, (got, ref)

    g_ref = jax.grad(lambda p: gpt_loss(p, batch, cfg))(params)
    g_f1 = jax.jit(jax.grad(lambda p: gpt_loss_1f1b(
        p, batch, cfg, mesh, num_microbatches=M)))(params)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(a)) + 1e-8)), g_ref, g_f1)
    worst = max(jax.tree.leaves(errs))
    assert worst < 1e-4, errs


@pytest.mark.slow
def test_1f1b_trains_and_memory_win():
    """1F1B's activation footprint is O(pp), not O(M): with M=32 the
    compiled temp allocation must be well under GPipe's, and the step
    must still reduce the loss."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.models.gpt_pipeline import (make_1f1b_train_step,
                                            make_pipeline_train_step)
    mesh = MeshSpec(dp=2, pp=2).build()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=4,
                    num_heads=2, embed_dim=32, dtype=jnp.float32)
    params = gpt_init(jax.random.PRNGKey(1), cfg)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    M = 32
    tokens = np.random.RandomState(0).randint(0, 128, (M * 2, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    tx = optax.adamw(3e-3)
    opt = tx.init(params)

    mems = {}
    for name, mk in (("gpipe", make_pipeline_train_step),
                     ("1f1b", make_1f1b_train_step)):
        step = mk(cfg, tx, mesh, num_microbatches=M, donate=False)
        mems[name] = jax.jit(step).lower(
            params, opt, batch).compile().memory_analysis() \
            .temp_size_in_bytes
    # Measured: ~22.4MB vs ~4.3MB on this shape; assert a conservative 2x.
    assert mems["1f1b"] * 2 < mems["gpipe"], mems

    step = make_1f1b_train_step(cfg, tx, mesh, num_microbatches=M)
    p, o = params, opt
    losses = []
    for _ in range(12):
        p, o, m = step(p, o, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.15, losses


def test_ring_attention_through_pipeline_stages():
    """sp threads through stage bodies (VERDICT r3 #6): ring attention
    inside a pp x sp x dp pipeline matches the dense non-pipelined loss,
    and gradients are finite."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = MeshSpec(dp=2, pp=2, sp=2).build()
    cfg_d = GPTConfig(vocab_size=128, max_seq_len=64, num_layers=4,
                      num_heads=2, embed_dim=32, dtype=jnp.float32)
    cfg_r = GPTConfig(vocab_size=128, max_seq_len=64, num_layers=4,
                      num_heads=2, embed_dim=32, dtype=jnp.float32,
                      attention="ring")
    params = gpt_init(jax.random.PRNGKey(1), cfg_d)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    tokens = np.random.RandomState(0).randint(0, 128, (8, 65))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    ref = float(gpt_loss(params, batch, cfg_d))
    got = float(jax.jit(lambda p, b: gpt_loss_pipelined(
        p, b, cfg_r, mesh, num_microbatches=4))(params, batch))
    assert abs(got - ref) < 1e-4, (got, ref)
    g = jax.jit(jax.grad(lambda p: gpt_loss_pipelined(
        p, batch, cfg_r, mesh, num_microbatches=4)))(params)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


def test_1f1b_bf16_default_dtype_grads():
    """The default GPTConfig uses bf16 activations: the custom_vjp must
    hand back a bf16 x_mbs cotangent or jax rejects the rule (regression
    for an f32-only bug — every other pipeline test pins f32)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.models.gpt_pipeline import gpt_loss_1f1b
    mesh = MeshSpec(dp=2, pp=2).build()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, num_layers=4,
                    num_heads=2, embed_dim=32)   # default dtype = bf16
    assert cfg.dtype == jnp.bfloat16
    params = gpt_init(jax.random.PRNGKey(1), cfg)
    params["layers"] = jax.device_put(
        params["layers"], NamedSharding(mesh, P("pp")))
    tokens = np.random.RandomState(0).randint(0, 128, (8, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    loss, g = jax.jit(jax.value_and_grad(lambda p: gpt_loss_1f1b(
        p, batch, cfg, mesh, num_microbatches=4)))(params)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
               for x in jax.tree.leaves(g))


class _Picked(Exception):
    """Raised by a spy in place of building the attention body."""


@pytest.mark.parametrize("S", [1024, 1100])
def test_pipeline_auto_attention_is_gpt_hiddens(S, monkeypatch):
    """Under "auto" the pipelined stages and the 1F1B loss take the variant
    gpt_hidden takes at the batch's own length: one rule, asked with S (not
    max_seq_len: flash at 2048 does not make 1100 divisible)."""
    import ray_tpu.models.gpt as gpt
    import ray_tpu.models.gpt_pipeline as pipeline
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPTConfig(vocab_size=128, max_seq_len=2048, num_layers=2,
                    num_heads=2, embed_dim=32, attention="auto")
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))

    def variant(attn_fn):
        return ("dense" if attn_fn is gpt._dense_causal_attention_bnsh
                else "flash")

    def picked(fn, *args):
        try:
            jax.eval_shape(fn, *args)
        except _Picked as e:
            return str(e)

    def spy_flash(rules, mesh=None):
        raise _Picked("flash")

    with monkeypatch.context() as m:
        m.setattr(gpt, "_flash_attention_bnsh", spy_flash)
        hidden = picked(lambda p, t: gpt.gpt_hidden(p, t, cfg), params,
                        jax.ShapeDtypeStruct((2, S), jnp.int32)) or "dense"
    assert hidden == ("flash" if S == 1024 else "dense")
    assert variant(pipeline._attn_fn_for(cfg, S)) == hidden

    real = pipeline._attn_fn_for

    def spy_stage(cfg, S, mesh=None):
        raise _Picked(variant(real(cfg, S, mesh)))

    monkeypatch.setattr(pipeline, "_attn_fn_for", spy_stage)
    mesh = MeshSpec(dp=4, pp=2).build()
    batch = {"tokens": jax.ShapeDtypeStruct((8, S + 1), jnp.int32)}
    assert picked(lambda p, b: pipeline.gpt_loss_1f1b(
        p, b, cfg, mesh, num_microbatches=2), params, batch) == hidden
