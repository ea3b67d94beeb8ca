"""The GPT's pipeline stages: ``models/gpt.py``'s block run as the stages of
the model-free schedules of ``parallel/pipeline.py`` (GPipe with the loss
fused into the drain, and a hand-scheduled 1F1B), one SPMD program over the
``pp`` mesh axis.

Stages compose with the rest of the model zoo (round-3, VERDICT r2 #10):

  * any local attention body runs inside a stage — dense, the Pallas
    flash kernels, or RING attention with the sp axis threaded through
    the schedule (activations seq-sharded inside the pipeline shard_map,
    the ring collective riding the same mesh);
  * MoE blocks run with their load-balance aux loss CARRIED through the
    schedule (gated so fill/drain garbage ticks contribute zero), and
    expert weights shard over a ``pp x ep`` mesh via moe_mlp's shard_map
    mode (experts local to each ep member, all_gather reassembly);
  * training uses a FUSED loss epilogue: the last stage computes the
    cross-entropy of each microbatch as it drains, so the collective at
    the end of the program is a scalar psum — not the old full
    [M, mb, S, D] output-buffer psum around the pp ring.

No chip has compiled these stages yet (ROADMAP Reach 4); the tests and
``__graft_entry__.py`` run them on a virtual CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models.gpt import (GPTConfig, _block, _layer_norm, gpt_init,
                                gpt_loss, gpt_param_axes, make_train_step,
                                token_loglikes)
from ray_tpu.ops.attention import (_dense_causal_attention_bnsh,
                                   _flash_attention_bnsh, resolve_attention)
from ray_tpu.parallel.pipeline import (gpipe_fused_loss_spmd,
                                       one_f_one_b_spmd)


def _pipeline_head(params):
    """The params the fused drain epilogue needs (shared by both
    pipeline loss paths — keep their numerics in ONE place)."""
    return {"wte": params["wte"], "ln_f": params["ln_f"]}


def _make_loss_mb(cfg):
    """Per-microbatch fused epilogue: final LN + LM head + summed target
    log-likelihoods for one drained microbatch."""
    dt = cfg.dtype

    def loss_mb(head, y, tgt):
        y = _layer_norm(y, head["ln_f"]["scale"], head["ln_f"]["bias"])
        logits = jnp.einsum("bsd,vd->bsv", y, head["wte"].astype(dt))
        return jnp.sum(token_loglikes(logits, tgt))

    return loss_mb


def _attn_fn_for(cfg, S, mesh=None):
    """Same head-major (bnsh) selections the non-pipelined block uses at
    sequence length S — pipelined stages must not silently keep the
    relayout-paying path.
    ``ring`` threads the sp axis through the stage body: stages see
    [mb, S/sp, ...] activation shards and the ring collective runs inside
    the same shard_map as the pipeline (VERDICT r3 #6)."""
    attention = resolve_attention(cfg.attention, S)
    assert attention in ("dense", "flash", "ring"), (
        f"pipelined stages support dense/flash/ring attention, got "
        f"{attention!r}")
    if attention == "ring":
        assert mesh is not None and mesh.shape.get("sp", 1) > 1, (
            "ring attention in a pipeline needs an sp mesh axis > 1")
        from ray_tpu.ops.ring_attention import ring_attention_sharded

        def attn_fn(q, k, v):
            return ring_attention_sharded(q, k, v, axis_name="sp")
        return attn_fn
    if attention == "flash":
        # No rules: the stage body already runs inside the schedule's
        # shard_map, so the kernel is called on the shard as it is.
        return _flash_attention_bnsh(None, mesh)
    return _dense_causal_attention_bnsh


def _layer_in_specs(cfg, mesh) -> Any:
    """PartitionSpec pytree for the stacked layer params: the [L] dim maps
    to pp, and (when the mesh has a real ep axis) expert dims map to ep —
    translated straight from the model's logical annotations."""
    use_ep = cfg.num_experts and mesh.shape.get("ep", 1) > 1

    def to_spec(ann):
        axes = []
        for a in ann:
            if a == "layers":
                axes.append("pp")
            elif a == "expert" and use_ep:
                axes.append("ep")
            else:
                axes.append(None)
        return P(*axes)

    return jax.tree_util.tree_map(
        to_spec, gpt_param_axes(cfg)["layers"],
        is_leaf=lambda x: isinstance(x, tuple))


def _check_pipeline_shapes(cfg, mesh, B, M):
    pp = mesh.shape.get("pp", 1)
    assert cfg.num_layers % pp == 0, (
        f"num_layers {cfg.num_layers} not divisible by pp={pp}")
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
    dsize = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    assert (B // M) % dsize == 0, (
        f"microbatch size {B // M} not divisible by data-axis size {dsize}")
    if cfg.num_experts and mesh.shape.get("ep", 1) > 1:
        assert cfg.num_experts % mesh.shape["ep"] == 0, (
            f"num_experts {cfg.num_experts} not divisible by "
            f"ep={mesh.shape['ep']}")
    return dsize


def gpt_loss_pipelined(params, batch, cfg, mesh, *, num_microbatches: int):
    """Pipelined next-token cross-entropy with the fused drain epilogue.

    Numerically matches ``gpt_loss`` on the same params/batch: per-token
    mean CE plus ``moe_aux_coef`` times the per-(layer, full-batch) aux
    mean (microbatch routing is per-row, so splitting the batch doesn't
    change dispatch decisions).
    """
    toks = batch["tokens"]
    tokens, targets = toks[:, :-1], toks[:, 1:]
    B, S = tokens.shape
    M = num_microbatches
    dsize = _check_pipeline_shapes(cfg, mesh, B, M)
    dt = cfg.dtype

    x = params["wte"].astype(dt)[tokens] + params["wpe"].astype(dt)[:S][None]
    x_mbs = x.reshape(M, B // M, S, -1)
    tgt_mbs = targets.reshape(M, B // M, S)

    use_ep = cfg.num_experts and mesh.shape.get("ep", 1) > 1
    block = functools.partial(_block, cfg, None, _attn_fn_for(cfg, S, mesh),
                              moe_ep_axis="ep" if use_ep else None)

    loss_mb = _make_loss_mb(cfg)

    data = tuple(a for a in ("dp", "fsdp") if a in mesh.shape)
    # Ring stages thread sp through the schedule: activations/targets are
    # seq-sharded inside the pipeline shard_map, each sp member computes
    # its chunk's partial ll, and the all-axes psum totals them — sp
    # stops being a replication axis (VERDICT r3 #6).
    use_sp = cfg.attention == "ring" and mesh.shape.get("sp", 1) > 1
    seq = "sp" if use_sp else None
    spsize = mesh.shape.get("sp", 1) if use_sp else 1
    mb_spec = P(None, data, seq, None)
    repl = mesh.size // (mesh.shape.get("pp", 1) * dsize * spsize)
    head = _pipeline_head(params)
    piped = jax.shard_map(
        functools.partial(gpipe_fused_loss_spmd, block, loss_mb,
                          all_axes=tuple(mesh.axis_names),
                          repl_factor=float(repl), remat=cfg.remat),
        mesh=mesh,
        in_specs=(_layer_in_specs(cfg, mesh), P(), mb_spec,
                  P(None, data, seq)),
        out_specs=(P(), P()), check_vma=False)
    ll_sum, aux_sum = piped(params["layers"], head, x_mbs, tgt_mbs)

    ce = -ll_sum / (B * S)
    # aux_sum totals per-(stage-layer, microbatch, data-shard, seq-shard)
    # means; the full-batch equivalent is their mean over those.
    aux = aux_sum / (M * dsize * spsize)
    return ce + cfg.moe_aux_coef * aux


def gpt_loss_1f1b(params, batch, cfg, mesh, *, num_microbatches: int):
    """Pipelined loss on the 1F1B schedule (activation memory O(pp)).

    Numerically matches ``gpt_loss`` / ``gpt_loss_pipelined``; gradients
    come from the hand-scheduled backward inside ``one_f_one_b_spmd``,
    surfaced to autodiff through a custom_vjp whose residuals ARE the
    gradients.  v1 scope: dense/flash stages, dp/fsdp data sharding (use
    the GPipe path for pp x ep MoE or sp ring stages).
    """
    toks = batch["tokens"]
    tokens, targets = toks[:, :-1], toks[:, 1:]
    B, S = tokens.shape
    M = num_microbatches
    dsize = _check_pipeline_shapes(cfg, mesh, B, M)
    assert not (cfg.num_experts and mesh.shape.get("ep", 1) > 1), (
        "1F1B v1 does not compose with ep; use the GPipe path")
    assert resolve_attention(cfg.attention, S) in ("dense", "flash"), (
        "1F1B v1 supports dense/flash stages; ring/sp uses the GPipe path")
    dt = cfg.dtype

    block = functools.partial(_block, cfg, None, _attn_fn_for(cfg, S),
                              moe_ep_axis=None)
    loss_mb = _make_loss_mb(cfg)

    data = tuple(a for a in ("dp", "fsdp") if a in mesh.shape)
    mb_spec = P(None, data, None, None)
    all_axes = tuple(mesh.axis_names)
    non_pp = tuple(a for a in all_axes if a != "pp")
    non_mb = tuple(a for a in all_axes if a not in data)
    layer_spec = _layer_in_specs(cfg, mesh)
    repl = float(mesh.size // (mesh.shape.get("pp", 1) * dsize))
    # Cotangents of the FINAL loss wrt each microbatch's ll / stage aux:
    # loss = -ll_total/(B*S) + coef * aux_total/(M*dsize).
    ll_cot = -1.0 / (B * S)
    aux_cot = cfg.moe_aux_coef / (M * dsize)

    def spmd(layers, head, x_mbs, tgt_mbs):
        ll, aux, gl, gh, gx = one_f_one_b_spmd(
            block, loss_mb, layers, head, x_mbs, tgt_mbs,
            ll_cot=ll_cot, aux_cot=aux_cot, remat=cfg.remat)
        def red(v, axes):
            return jax.lax.psum(v / repl, axes) if axes else v / repl
        ll = red(ll, all_axes)
        aux = red(aux, all_axes)
        gl = jax.tree.map(lambda g: red(g, non_pp), gl)
        gh = jax.tree.map(lambda g: red(g, all_axes), gh)
        # Accumulated in f32 for accuracy; the custom_vjp bwd must hand
        # back a cotangent with the PRIMAL's dtype (bf16 activations by
        # default) or jax rejects the rule.
        gx = red(gx, non_mb).astype(x_mbs.dtype)
        return ll, aux, gl, gh, gx

    core_spmd = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(layer_spec, P(), mb_spec, P(None, data, None)),
        out_specs=(P(), P(), layer_spec, P(), mb_spec), check_vma=False)

    def _loss_of(ll, aux):
        return -ll / (B * S) + cfg.moe_aux_coef * aux / (M * dsize)

    @jax.custom_vjp
    def core(layers, head, x_mbs, tgt_mbs):
        ll, aux, _, _, _ = core_spmd(layers, head, x_mbs, tgt_mbs)
        return _loss_of(ll, aux)

    def core_fwd(layers, head, x_mbs, tgt_mbs):
        ll, aux, gl, gh, gx = core_spmd(layers, head, x_mbs, tgt_mbs)
        return _loss_of(ll, aux), (gl, gh, gx, tgt_mbs.shape)

    def core_bwd(res, g):
        import numpy as np
        gl, gh, gx, tgt_shape = res
        scale = lambda t: jax.tree.map(lambda a: g * a, t)  # noqa: E731
        return (scale(gl), scale(gh), scale(gx),
                np.zeros(tgt_shape, jax.dtypes.float0))

    core.defvjp(core_fwd, core_bwd)

    x = params["wte"].astype(dt)[tokens] + params["wpe"].astype(dt)[:S][None]
    x_mbs = x.reshape(M, B // M, S, -1)
    tgt_mbs = targets.reshape(M, B // M, S)
    return core(params["layers"], _pipeline_head(params), x_mbs, tgt_mbs)


def make_1f1b_train_step(cfg, tx, mesh, *, num_microbatches: int,
                         donate: bool = True):
    """Jittable 1F1B train step — drop-in for make_pipeline_train_step
    with O(pp) activation memory (the dryrun reports both schedules'
    compiled temp sizes)."""
    def loss_fn(params, batch):
        return gpt_loss_1f1b(params, batch, cfg, mesh,
                             num_microbatches=num_microbatches)

    return make_train_step(cfg, tx, donate=donate, loss_fn=loss_fn)


def make_pipeline_train_step(cfg, tx, mesh, *, num_microbatches: int,
                             donate: bool = True):
    """Jittable GPipe train step: (params, opt_state, batch) -> same + metrics.

    The reference's closest analog is torch DDP's per-bucket allreduce hook
    (`train/torch/train_loop_utils.py:70`) — here the entire fill/drain
    schedule, the fused per-microbatch loss, and gradient reduction are
    compiled into one XLA program.
    """
    def loss_fn(params, batch):
        return gpt_loss_pipelined(params, batch, cfg, mesh,
                                  num_microbatches=num_microbatches)

    return make_train_step(cfg, tx, donate=donate, loss_fn=loss_fn)


def dryrun_pipeline(n_devices: int) -> None:
    """Driver check: three pipeline configs train a step on a virtual mesh.

    1. pp x dp dense — fused-epilogue loss matches the non-pipelined step;
    2. pp x dp FLASH attention inside the stages (Pallas interpret mode);
    3. pp x ep MoE — expert weights sharded over ep within each stage,
       aux loss preserved (vs. the GSPMD reference loss).
    """
    import numpy as np
    import optax

    from ray_tpu.parallel.mesh import MeshSpec

    if n_devices % 2:
        print(f"pipeline dryrun SKIPPED (n={n_devices} odd; pp needs an "
              f"even split)")
        return

    def one(cfg, spec, tag, mbs=4):
        mesh = spec.build()
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        params["layers"] = jax.device_put(
            params["layers"], jax.sharding.NamedSharding(mesh, P("pp")))
        dsize = spec.dp * spec.fsdp
        batch = {"tokens": jnp.asarray(
            np.random.RandomState(0).randint(
                0, cfg.vocab_size, (mbs * max(dsize, 1), 65)), jnp.int32)}
        ref = float(gpt_loss(params, batch, cfg))
        tx = optax.adamw(1e-3)
        step = make_pipeline_train_step(cfg, tx, mesh,
                                        num_microbatches=mbs)
        _, _, metrics = step(params, tx.init(params), batch)
        got = float(metrics["loss"])
        assert abs(got - ref) < 1e-3, (tag, got, ref)
        print(f"pipeline dryrun[{tag}]: mesh={spec.axis_sizes} M={mbs} "
              f"loss={got:.4f} (matches reference {ref:.4f})")

    dense = GPTConfig(vocab_size=256, max_seq_len=64, num_layers=4,
                      num_heads=4, embed_dim=64, dtype=jnp.float32)
    one(dense, MeshSpec(dp=n_devices // 2, pp=2), "dense pp x dp")

    flash = GPTConfig(vocab_size=256, max_seq_len=64, num_layers=4,
                      num_heads=4, embed_dim=64, dtype=jnp.float32,
                      attention="flash")
    one(flash, MeshSpec(dp=n_devices // 2, pp=2), "flash pp x dp")

    if n_devices % 4 == 0:
        moe = GPTConfig(vocab_size=256, max_seq_len=64, num_layers=4,
                        num_heads=4, embed_dim=64, dtype=jnp.float32,
                        num_experts=4, expert_top_k=2)
        one(moe, MeshSpec(dp=n_devices // 4, pp=2, ep=2), "moe pp x ep")
    else:
        print("pipeline dryrun[moe pp x ep] SKIPPED (needs n % 4 == 0)")

    # 1F1B: same numerics as GPipe, O(pp) activation memory -- report the
    # measured compiled temp sizes at a microbatch count where it matters.
    spec = MeshSpec(dp=n_devices // 2, pp=2)
    mesh = spec.build()
    params = gpt_init(jax.random.PRNGKey(0), dense)
    params["layers"] = jax.device_put(
        params["layers"], jax.sharding.NamedSharding(mesh, P("pp")))
    M = 16
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(
            0, dense.vocab_size, (M * max(spec.dp, 1), 65)), jnp.int32)}
    ref = float(gpt_loss(params, batch, dense))
    tx = optax.adamw(1e-3)
    step_1f1b = make_1f1b_train_step(dense, tx, mesh, num_microbatches=M,
                                     donate=False)
    opt = tx.init(params)
    _, _, metrics = jax.jit(step_1f1b)(params, opt, batch)
    got = float(metrics["loss"])
    assert abs(got - ref) < 1e-3, ("1f1b", got, ref)
    try:
        mem_1f1b = jax.jit(step_1f1b).lower(params, opt, batch) \
            .compile().memory_analysis().temp_size_in_bytes
        step_gp = make_pipeline_train_step(dense, tx, mesh,
                                           num_microbatches=M, donate=False)
        mem_gp = jax.jit(step_gp).lower(params, opt, batch) \
            .compile().memory_analysis().temp_size_in_bytes
        print(f"pipeline dryrun[1f1b pp x dp]: M={M} loss={got:.4f} "
              f"(matches reference {ref:.4f}); activation temp "
              f"{mem_1f1b / 1e6:.1f}MB vs gpipe {mem_gp / 1e6:.1f}MB "
              f"({mem_gp / max(mem_1f1b, 1):.1f}x less)")
    except Exception:   # memory_analysis availability is backend-dependent
        print(f"pipeline dryrun[1f1b pp x dp]: M={M} loss={got:.4f} "
              f"(matches reference {ref:.4f})")
