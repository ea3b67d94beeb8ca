"""Real pipeline parallelism: the model-free schedules over the ``pp`` axis
(microbatched GPipe with the loss fused into the drain, and 1F1B).

The reference has no pipeline parallelism at all (SURVEY §2.4 — its scaling
story is DDP/FSDP only); this is new capability, built the TPU way rather
than as host-level stage actors: the whole pipeline is ONE SPMD program.
``shard_map`` places one stage per device along the ``pp`` mesh axis, layer
weights are sharded on their stacked ``[L]`` dim, and microbatch activations
flow stage-to-stage with ``lax.ppermute`` over ICI.  The schedule is a
``lax.scan`` over ``num_microbatches + pp - 1`` ticks, which keeps it
reverse-mode differentiable — autodiff through the scan + ppermute yields the
backward pipeline (activations replay in reverse, gradient traffic rides the
inverse permutation), so one forward definition gives the full GPipe
fill/steady/drain schedule for training with no hand-written backward pass.

The schedules know no model: a caller hands them a block function and a
per-microbatch loss (``models/gpt_pipeline.py`` is the GPT's).  A block may
carry an auxiliary loss through the schedule (gated so fill/drain garbage
ticks contribute zero), and the loss is FUSED into the drain: the last stage
computes each microbatch's as it leaves, so the collective at the end of the
program is a scalar psum, not an [M, mb, S, D] output buffer around the ring.

Bubble fraction is the usual (pp-1)/(M+pp-1); raise ``num_microbatches`` to
amortize.  Weight grads for each stage stay device-local (the transpose of a
sharded-in param is a sharded-out grad), so the only cross-stage traffic is
the [mb, S, D] activation/grad hop per tick — exactly the wire pattern of a
1F1B/GPipe implementation, but emitted by XLA.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def _stage_machinery(axis_name: str):
    pp = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    shift = [(i, (i + 1) % pp) for i in range(pp)]
    return pp, idx, shift


def gpipe_fused_loss_spmd(block_fn: Callable, loss_mb_fn: Callable,
                          local_params, head_params, x_mbs, tgt_mbs, *,
                          axis_name: str = "pp", all_axes, repl_factor: float,
                          remat: bool = True):
    """GPipe schedule with the loss fused into the drain.

    As each real microbatch leaves the last stage, ``loss_mb_fn(
    head_params, y, tgt) -> ll_sum`` computes its log-likelihood sum right
    there — so no [M, mb, S, D] output buffer is ever materialized or
    psummed around the ring; the program's epilogue collectives are two
    SCALAR psums (ll and aux) over the mesh.

    ``repl_factor`` is the number of mesh devices holding a redundant copy
    of this computation (product of axis sizes not carrying pp or data):
    locals are pre-divided by it so the all-axis psum both totals the
    distinct contributions and keeps the transpose (gradient) math
    consistent for replicated inputs.
    Returns (ll_sum, aux_sum) as replicated scalars.
    """
    pp, idx, shift = _stage_machinery(axis_name)
    M = x_mbs.shape[0]
    T = M + pp - 1
    body = jax.checkpoint(block_fn) if remat else block_fn

    def apply_stage(x):
        y, auxs = jax.lax.scan(lambda c, lp: body(c, lp), x, local_params)
        return y, jnp.sum(auxs)

    def tick(carry, t):
        state, ll_acc, aux_acc = carry
        inp = jax.lax.dynamic_index_in_dim(
            x_mbs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        state = jnp.where(idx == 0, inp, state)
        y, aux = apply_stage(state)
        m_here = t - idx
        aux_acc = aux_acc + jnp.where(
            (m_here >= 0) & (m_here < M), aux, 0.0)
        m = t - (pp - 1)
        tgt = jax.lax.dynamic_index_in_dim(
            tgt_mbs, jnp.clip(m, 0, M - 1), 0, keepdims=False)
        # Gate the head (LM-head matmul + CE, the priciest op here at real
        # vocab sizes) so only the last stage pays it: under shard_map the
        # predicate is a per-device scalar, so lax.cond lowers to a real
        # branch and non-final stages skip the FLOPs instead of computing
        # and discarding through a where-mask.
        ll = jax.lax.cond(
            (idx == pp - 1) & (m >= 0),
            lambda: loss_mb_fn(head_params, y, tgt).astype(jnp.float32),
            lambda: jnp.zeros((), jnp.float32))
        ll_acc = ll_acc + ll
        state = jax.lax.ppermute(y, axis_name, shift)
        return (state, ll_acc, aux_acc), None

    zero = jnp.zeros((), jnp.float32)
    (_, ll_acc, aux_acc), _ = jax.lax.scan(
        tick, (jnp.zeros_like(x_mbs[0]), zero, zero), jnp.arange(T))
    ll = jax.lax.psum(ll_acc / repl_factor, all_axes)
    aux = jax.lax.psum(aux_acc / repl_factor, all_axes)
    return ll, aux


# ---------------------------------------------------------- 1F1B schedule

def one_f_one_b_spmd(block_fn: Callable, loss_mb_fn: Callable,
                     local_params, head_params, x_mbs, tgt_mbs, *,
                     axis_name: str = "pp", ll_cot: float, aux_cot: float,
                     remat: bool = True):
    """1F1B pipeline schedule with the backward pass written OUT, not
    autodiffed: activation memory O(pp), not O(M).

    GPipe-via-autodiff (``gpipe_fused_loss_spmd``) must keep every tick's
    carry alive for the reverse sweep — O(M + pp) stage inputs per device.  Here each
    tick runs one forward AND one backward block application per stage
    (masked during fill/drain), with microbatch m's backward at stage i
    scheduled ``2(pp-1-i)`` ticks after its forward — so at most
    ``2(pp-1)`` stage inputs are ever stashed, in a fixed ring buffer.
    Weight gradients accumulate in-place; the input cotangent rides the
    inverse ppermute.  (New capability — the reference has no pipeline
    parallelism; schedule follows the PipeDream-flush/Megatron 1F1B
    pattern, re-derived for a single SPMD ``lax.scan`` program.)

    ``ll_cot``/``aux_cot`` are d(final_loss)/d(per-microbatch ll / aux) —
    the caller folds its normalization in, so this function returns
    gradients OF THE FINAL SCALAR LOSS.

    Returns (ll_sum, aux_sum, g_layers, g_head, g_x_mbs) — ll/aux/grads
    are per-device partials; the caller psums (g_layers stays
    pp-sharded).
    """
    pp, idx, shift = _stage_machinery(axis_name)
    rshift = [(i, (i - 1) % pp) for i in range(pp)]
    M = x_mbs.shape[0]
    T = M + 2 * pp - 2
    R = 2 * pp                     # ring slots >= max in-flight (2pp-2) + 1
    body = jax.checkpoint(block_fn) if remat else block_fn

    def stage_fn(params, x):
        y, auxs = jax.lax.scan(lambda c, lp: body(c, lp), x, params)
        return y, jnp.sum(auxs)

    f32 = jnp.float32

    def tick(carry, t):
        (fwd_msg, bwd_msg, stash, ll_acc, aux_acc,
         g_layers, g_head, g_x) = carry

        # ---- forward: stage idx runs microbatch mf = t - idx
        mf = t - idx
        f_valid = (mf >= 0) & (mf < M)
        inp = jax.lax.dynamic_index_in_dim(
            x_mbs, jnp.clip(mf, 0, M - 1), 0, keepdims=False)
        x_in = jnp.where(idx == 0, inp, fwd_msg)
        y, aux = stage_fn(local_params, x_in)
        aux_acc = aux_acc + jnp.where(f_valid, aux.astype(f32), 0.0)
        # Stash the stage INPUT (remat: backward recomputes the body).
        # Write-protect with where: an invalid tick must not clobber a
        # live slot.
        slot = jnp.where(f_valid, mf % R, 0)
        stash = jnp.where(
            f_valid,
            jax.lax.dynamic_update_index_in_dim(stash, x_in, slot, 0),
            stash)

        # ---- last stage: loss of THIS microbatch + its cotangent (1F1B:
        # the last stage's backward immediately follows its forward).
        # lax.cond, not a where-mask: the head matmul + its VJP is the
        # priciest op in the tick at real vocab sizes, and the predicate
        # is a per-device scalar under shard_map, so non-final stages and
        # fill/drain ticks genuinely skip the FLOPs.
        tgt = jax.lax.dynamic_index_in_dim(
            tgt_mbs, jnp.clip(mf, 0, M - 1), 0, keepdims=False)
        is_last = idx == pp - 1

        def head_branch():
            ll, loss_vjp = jax.vjp(
                lambda yy, hh: loss_mb_fn(hh, yy, tgt), y, head_params)
            dy, dh = loss_vjp(jnp.asarray(ll_cot, ll.dtype))
            return ll.astype(f32), dy, dh

        def skip_branch():
            return (jnp.zeros((), f32), jnp.zeros_like(y),
                    jax.tree.map(jnp.zeros_like, head_params))

        ll, dy_loss, dhead = jax.lax.cond(
            is_last & f_valid, head_branch, skip_branch)
        ll_acc = ll_acc + ll
        g_head = jax.tree.map(
            lambda g, d: g + d.astype(g.dtype), g_head, dhead)

        # ---- backward: stage idx runs microbatch mb = t - (2pp - 2 - idx)
        mb = t - (2 * pp - 2 - idx)
        b_valid = (mb >= 0) & (mb < M)
        x_saved = jax.lax.dynamic_index_in_dim(
            stash, jnp.where(b_valid, mb % R, 0), 0, keepdims=False)
        cot_y = jnp.where(is_last, dy_loss, bwd_msg)
        (_, _), stage_vjp = jax.vjp(stage_fn, local_params, x_saved)
        dparams, dx = stage_vjp(
            (cot_y, jnp.asarray(aux_cot, aux.dtype)))
        bsel = jnp.where(b_valid, 1.0, 0.0)
        g_layers = jax.tree.map(
            lambda g, d: g + bsel * d.astype(g.dtype), g_layers, dparams)
        dx = bsel * dx
        # Each valid (stage 0, tick) writes a distinct microbatch slot;
        # the where guards fill/drain ticks from clobbering slot 0.
        g_x = jnp.where(
            (idx == 0) & b_valid,
            jax.lax.dynamic_update_index_in_dim(
                g_x, dx.astype(jnp.float32), jnp.clip(mb, 0, M - 1), 0),
            g_x)

        # ---- move activations downstream, cotangents upstream
        fwd_next = jax.lax.ppermute(y, axis_name, shift)
        bwd_next = jax.lax.ppermute(dx, axis_name, rshift)
        return (fwd_next, bwd_next, stash, ll_acc, aux_acc,
                g_layers, g_head, g_x), None

    zero_mb = jnp.zeros_like(x_mbs[0])
    init = (
        zero_mb, zero_mb,
        jnp.zeros((R,) + x_mbs.shape[1:], x_mbs.dtype),
        jnp.zeros((), f32), jnp.zeros((), f32),
        jax.tree.map(lambda a: jnp.zeros(a.shape, f32), local_params),
        jax.tree.map(lambda a: jnp.zeros(a.shape, f32), head_params),
        jnp.zeros_like(x_mbs, jnp.float32),
    )
    (_, _, _, ll_acc, aux_acc, g_layers, g_head, g_x), _ = jax.lax.scan(
        tick, init, jnp.arange(T))
    return ll_acc, aux_acc, g_layers, g_head, g_x
