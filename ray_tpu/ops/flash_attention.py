"""Flash (blockwise, online-softmax) causal attention as Pallas TPU kernels.

The reference has no fused attention of its own (it defers to torch); on TPU
the memory-bound step is reading the [S, S] score matrix from HBM, so we
never materialize it.  All three kernels use a 3-D grid — (batch*heads,
out_block, streamed_block) — with the streamed operand (K/V for the q-side
kernels, Q/dO for the k-side kernel) delivered one VMEM tile per inner grid
step, so VMEM stays O(block) no matter the sequence length; Pallas
double-buffers the inner-dim DMAs automatically.  Online-softmax /
gradient accumulators live in f32 VMEM scratch across inner steps.

Backward is the FlashAttention-2 recurrence, also in Pallas — NOT a dense
vjp.  Residuals are q, k, v, o, lse (all O(S) off-chip).  Two kernels:

  * dq kernel    — grid over q blocks; streams K/V blocks, recomputes the
    probability tile from (q, k, lse) and accumulates dq.
  * dk/dv kernel — grid over k blocks; streams Q/dO blocks, recomputes the
    probability tile and accumulates dk and dv via dim-0 contractions
    (implicitly-transposed matmuls the MXU executes natively).

lse and D ride into the kernels as [*, seq, _LANES] tiles (row value
broadcast along a narrow minor dim) so they slice as native sublane column
vectors — the same layout trick as jax.experimental.pallas.ops.tpu
.flash_attention's l/m tensors, but 8 lanes wide instead of 128.

Causal skipping: dead diagonal blocks are jumped with `pl.when`, so the
wall-clock cost of the mask is ~half the non-causal kernel, not equal to it.

On the CPU backend the same kernels run in Pallas interpret mode, keeping
CPU tests honest.

Design analog: the reference defers attention to torch SDPA/flash-attn CUDA
kernels; this is the TPU-native replacement (SURVEY §5.7).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 8     # minor-dim width of the lse/D carrier tensors
_SCR = 128     # lane width of VMEM scratch accumulators

# The smallest block the TPU compiler tiles.
_MIN_BLOCK = 8


def _default_blocks(S: int, strict: bool = True) -> tuple:
    """The (block_q, block_k) a call without explicit blocks runs with:
    1024, halved until it divides S.  (1024, 1024) is what both training
    cells of the benchmark run (S = 1024; ``flash_fwd/dq/dkv_roofline``
    18.0 / 22.3 / 21.6 in PERF_LEDGER.jsonl), and the [1024, 1024] f32
    probability tile (4 MB) fits VMEM.  This is the one place the choice
    is made: a sweep on the chip changes this table in this file."""
    b = 1024
    while S % b:
        b //= 2
    if strict and b < _MIN_BLOCK:
        # Mosaic rejects sub-tile blocks with an opaque compile error, so
        # fail loudly here: the caller pads such a sequence length.
        # strict=False (interpret mode) permits them: the interpreter has
        # no tiling constraint.
        S_pad, pb = _suggest_blocks(S)
        raise ValueError(
            f"flash_attention: sequence length {S} only admits block sizes "
            f"({b}, {b}) < {_MIN_BLOCK}, which the TPU compiler rejects. "
            f"Pad the sequence to {S_pad} and use block_q={pb}, "
            f"block_k={pb} (mask the tail), or pass explicit "
            f"block_q/block_k >= {_MIN_BLOCK} that divide {S}.")
    return b, b


def _suggest_blocks(S: int) -> tuple:
    """For an S no TPU-legal block divides: the nearest padded sequence
    length and the largest block that divides it, (padded_S, block)."""
    pad = 128 if S > 16 else _MIN_BLOCK
    S_pad = -(-S // pad) * pad
    return S_pad, max([b for b in (128, 256, 512, 1024)
                       if b <= S_pad and S_pad % b == 0] or [pad])


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, o_scr, *,
                causal: bool, sm_scale: float):
    # q_ref: [block_q, H]; k_ref/v_ref: [block_k, H] (streamed on grid dim 2)
    # o_ref: [block_q, H]; lse_ref: [block_q, _LANES]
    # scratch: m/l [block_q, _SCR], o [block_q, H] — all f32
    block_q, head_dim = q_ref.shape
    block_k = k_ref.shape[0]
    qi, kb = pl.program_id(1), pl.program_id(2)
    num_kb = pl.num_programs(2)
    q_start, k_start = qi * block_q, kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full((block_q, _SCR), _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros((block_q, _SCR), jnp.float32)
        o_scr[:] = jnp.zeros((block_q, head_dim), jnp.float32)

    live = True if not causal else k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        # matmuls run in the input dtype (bf16-native on the MXU) with f32
        # accumulation; softmax statistics stay f32.
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_scr[:] = o_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0:1], 1e-30)
        o_ref[:] = (o_scr[:] / l).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l)
        lse_ref[:] = jnp.broadcast_to(lse, (block_q, _LANES))


def _layout_views(shape, layout):
    """(B, N, S, H, fold, unfold) for a q-shape under the given layout —
    the ONE place the fwd and bwd impls get their layout handling from."""
    if layout == "bnsh":
        B, N, S, H = shape

        def fold(x):
            return x.reshape(B * N, S, H)

        def unfold(x):
            return x.reshape(B, N, S, H)
    else:
        B, S, N, H = shape

        def fold(x):
            return x.transpose(0, 2, 1, 3).reshape(B * N, S, H)

        def unfold(x):
            return x.reshape(B, N, S, H).transpose(0, 2, 1, 3)
    return B, N, S, H, fold, unfold


def _flash_fwd_impl(q, k, v, *, causal: bool, block_q: int, block_k: int,
                    sm_scale: Optional[float], interpret: bool,
                    layout: str = "bsnh"):
    """layout "bsnh": q,k,v [B, S, N, H] (folding costs a transpose).
    layout "bnsh": q,k,v [B, N, S, H] — folding to the kernel's
    [B*N, S, H] view is a FREE reshape; models that keep attention in
    bnsh (the GPT block does) skip ~25% of attention wall-clock that
    the bsnh relayouts cost at bench scale.
    Returns (o in the input layout, lse [B*N, S] f32)."""
    B, N, S, H, _fold, _unfold = _layout_views(q.shape, layout)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(H)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (
        f"seq {S} must divide blocks ({block_q},{block_k})")

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    kernel = functools.partial(_fwd_kernel, causal=causal, sm_scale=scale)
    of, lse = pl.pallas_call(
        kernel,
        grid=(B * N, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
            jax.ShapeDtypeStruct((B * N, S, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _SCR), jnp.float32),
            pltpu.VMEM((block_q, _SCR), jnp.float32),
            pltpu.VMEM((block_q, H), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return _unfold(of), lse[:, :, 0]


# ---------------------------------------------------------------- backward
#
# FlashAttention-2 recurrence.  With P = exp(S*scale - lse) the true softmax
# probabilities and D_i = sum_h dO_ih * O_ih:
#   dV = P^T dO;   dP = dO V^T;   dS = P * (dP - D) * scale
#   dQ = dS K;     dK = dS^T Q

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal: bool, sm_scale: float):
    # q_ref/do_ref/dq_ref: [block_q, H]; k_ref/v_ref: [block_k, H] (streamed);
    # lse_ref/delta_ref: [block_q, _LANES]; dq_scr: [block_q, H] f32
    block_q, head_dim = q_ref.shape
    block_k = k_ref.shape[0]
    qi, kb = pl.program_id(1), pl.program_id(2)
    num_kb = pl.num_programs(2)
    q_start, k_start = qi * block_q, kb * block_k

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros((block_q, head_dim), jnp.float32)

    live = True if not causal else k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]
        k = k_ref[:]
        v = v_ref[:]
        s = lax.dot_general(                       # q @ k^T
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk]
        dp = lax.dot_general(                      # do @ v^T
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, causal: bool, sm_scale: float):
    # k_ref/v_ref/dk_ref/dv_ref: [block_k, H]; q_ref/do_ref: [block_q, H]
    # (streamed); lse_ref/delta_ref: [block_q, _LANES]
    block_k, head_dim = k_ref.shape
    block_q = q_ref.shape[0]
    ki, jb = pl.program_id(1), pl.program_id(2)
    num_qb = pl.num_programs(2)
    k_start, q_start = ki * block_k, jb * block_q

    @pl.when(jb == 0)
    def _init():
        dk_scr[:] = jnp.zeros((block_k, head_dim), jnp.float32)
        dv_scr[:] = jnp.zeros((block_k, head_dim), jnp.float32)

    live = True if not causal else q_start + block_q - 1 >= k_start

    @pl.when(live)
    def _compute():
        k = k_ref[:]
        v = v_ref[:]
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk]
        # dv += p^T @ do   (contract dim 0 of both: implicit transpose)
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jb == num_qb - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, o, lse, g, *, causal: bool, block_q: int,
                    block_k: int, sm_scale: Optional[float],
                    interpret: bool, layout: str = "bsnh"):
    B, N, S, H, _fold, _unfold = _layout_views(q.shape, layout)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(H)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (
        f"seq {S} must divide blocks ({block_q},{block_k})")
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(g)
    # D_i = sum_h dO_ih O_ih — cheap elementwise reduce, leave it to XLA.
    delta = jnp.sum(dof.astype(jnp.float32) *
                    _fold(o).astype(jnp.float32), axis=-1)      # [B*N, S]
    lse_l = jnp.broadcast_to(lse[:, :, None], (B * N, S, _LANES))
    delta_l = jnp.broadcast_to(delta[:, :, None], (B * N, S, _LANES))

    dq_kernel = functools.partial(_dq_kernel, causal=causal, sm_scale=scale)
    dqf = pl.pallas_call(
        dq_kernel,
        grid=(B * N, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, H), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(qf, kf, vf, dof, lse_l, delta_l)

    dkv_kernel = functools.partial(_dkv_kernel, causal=causal, sm_scale=scale)
    dkf, dvf = pl.pallas_call(
        dkv_kernel,
        grid=(B * N, S // block_k, S // block_q),
        in_specs=[
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
            jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, H), jnp.float32),
            pltpu.VMEM((block_k, H), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(kf, vf, qf, dof, lse_l, delta_l)

    return _unfold(dqf), _unfold(dkf), _unfold(dvf)


# ---------------------------------------------------------------- public API

def _dense_reference(q, k, v, causal, sm_scale):
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqnh,bknh->bnqk", q, k).astype(jnp.float32) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    layout: str = "bsnh"):
    """Fused causal attention.

    layout "bsnh" (default): q,k,v [batch, seq, heads, head_dim].
    layout "bnsh": q,k,v [batch, heads, seq, head_dim] — the kernels'
    native view; models that produce attention inputs head-major skip
    the fold transposes entirely (~25% of attention time at short seq).
    block_q/block_k are the kernel's own parameters; left None they are
    what `_default_blocks` gives for S.
    """
    out, _ = _fwd(q, k, v, causal, block_q, block_k, sm_scale, interpret,
                  layout)
    return out


def _resolve(q, block_q, block_k, interpret, layout):
    if interpret is None:
        # The interpreter is for the CPU backend, where the tests run.  On
        # any other backend the kernel is compiled, and a kernel that does
        # not compile there is an error, not a slower run.
        interpret = jax.default_backend() == "cpu"
    if block_q is None or block_k is None:
        S = q.shape[2 if layout == "bnsh" else 1]
        bq, bk = _default_blocks(S, strict=not interpret)
        block_q = block_q or bq
        block_k = block_k or bk
    return block_q, block_k, interpret


def _fwd(q, k, v, causal, block_q, block_k, sm_scale, interpret,
         layout="bsnh"):
    bq, bk, interp = _resolve(q, block_q, block_k, interpret, layout)
    out, lse = _flash_fwd_impl(q, k, v, causal=causal, block_q=bq,
                               block_k=bk, sm_scale=sm_scale,
                               interpret=interp, layout=layout)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, sm_scale, interpret, layout, res, g):
    q, k, v, o, lse = res
    bq, bk, interp = _resolve(q, block_q, block_k, interpret, layout)
    return _flash_bwd_impl(q, k, v, o, lse, g, causal=causal, block_q=bq,
                           block_k=bk, sm_scale=sm_scale, interpret=interp,
                           layout=layout)


flash_attention.defvjp(_fwd, _bwd)
