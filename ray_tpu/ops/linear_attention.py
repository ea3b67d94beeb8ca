"""Linear attention by the gated delta rule (Gated DeltaNet,
arXiv:2412.06464): what a layer keeps of its past is not pages that grow with
the sequence but ONE matrix a head, ``S`` [dk, dv] in float32, and the last
``K - 1`` inputs of a short causal convolution.  A position does

    S' = alpha * S                      alpha in (0, 1]: the decay
    S  = S' + beta * k (v - S'^T k)^T   the delta rule; beta in (0, 2)
    o  = S^T q

with ``k`` of unit length, so the write replaces what ``S'`` held along ``k``
by a share ``beta`` of ``v`` (over 1 it flips the sign along ``k``: the
negative eigenvalues of arXiv:2411.12537).  Three forms of the same
arithmetic, all float32:

``gated_delta_recurrent``  the lines above, a position at a time: what the
    tests hold the other two to.
``gated_delta_chunked``    a whole sequence in chunks of 64 (the WY form of
    arXiv:2406.06484): inside a chunk the positions' writes are solved for
    together with one triangular system and matrix products, and the state
    moves a chunk at a time.  A prefill's form.  Positions from ``length`` on
    (a rung's padded tail) get alpha = 1 and beta = 0 and leave the state
    alone.
``gated_delta_step``       one position for every slot of a decode batch,
    on the states of one layer as the pool stores them.

The pool stores a slot's state FOLDED (``fold_state``): the TPU tiles an
array's last axis in 128 lanes, and a ``[dk, 192]`` matrix would lie in 256
of them, a third of it air, read and written every step.  So the dv value
columns of a head go into panels of 128 lanes: a head's whole 128s each a
panel, and the columns left over (64 of 192) side by side with the next
heads' in panels of their own, ``[panels, dk, 128]`` with nothing padded.
Every column of ``S`` meets the delta rule alone (``u_j = sum_i S_ij k_i``,
``S_ij += k_i w_j``), so the step runs on the panels as they lie: what
belongs to a head (k, q, alpha, beta) is spread over its columns' lanes
(``_keys_to_panels``, ``_values_to_panels``), and no state is unfolded.  Where
dv's remainder does not divide 128, or the heads do not fill its panels, a
head's dv columns are one panel and the layout is plain.

The step reads the OLD state for both of its sums, ``S^T k`` and ``S^T q``,
then ``o = alpha S^T q + (k . q) w`` with ``w = beta (v - alpha S^T k)``,
which is ``S_new^T q`` written out, and the new state is ``alpha S + k w^T``.
WHO RUNS IT (``step_pool``, the token step's one entry, on the pool of every
linear layer's states ``[L, B, panels, dk, lanes]``; ``state_step_kind`` says
which).  On the chip the Pallas kernel ``ops/linear_state.py``: a slot's
panels of the layer come into fast memory once, both sums, ``w``, ``o`` and
the new state are taken from that copy, and it goes back where it lay; the
pool is the kernel's operand and its result in one buffer, no slab of a
layer is sliced out, selected against or written back, and a parked slot's
rows are copied through.  One body for both decays, a head's scalar being a
head's key channels all equal.  On the CPU (and wherever the pool is not
one the kernel is written for, or a caller has replaced one of this
module's two steps) the jnp forms ``gated_delta_step`` / ``kda_step`` on the
layer's slab, which are also what the kernel is tested against: one fusion
takes the two sums, a second reads the states again and writes the new
ones, and ``step_pool`` selects the parked slots' old rows back and writes
the slab into the pool.

THE DECAY A KEY CHANNEL (Kimi Delta Attention, arXiv:2510.26692): ``alpha``
is a vector over a head's dk key channels and scales the state's ROWS,

    S' = Diag(alpha) S;   S = S' + beta k (v - S'^T k)^T;   o = S^T q

with beta in (0, 1).  ``gated_delta_recurrent`` takes either decay; the
other two forms, ``kda_chunked`` and ``kda_step``, stand beside the scalar
ones (whose programs stay as they are; with every channel of a head equal the
two rules agree).
What changes is the chunked form.  Between positions j <= i of a chunk the
decay is no longer one number that multiplies ``k_i . k_j`` but ``sum_c k_ic
k_jc exp(G_ic - G_jc)`` (``G`` the running sum of ``log alpha`` a channel): a
product of ``k exp(G)`` with ``k exp(-G)``, whose second factor overflows
float32 within a chunk of 64 once a channel decays faster than ~0.25 a
position.  So a chunk is cut into SUB-CHUNKS of 16 (the paper's secondary
chunking, ``_decayed_products``): a sub-chunk's rows against the positions
BEFORE it are one matrix product with both factors referred to the
sub-chunk's start (``exp(G_i - G_start)`` and ``exp(G_start - G_j)``, both
at most 1: nothing overflows, and what underflows was that small), and
against the positions of the sub-chunk itself the exponents are taken pair
by pair, 16 x 16 x dk of them, never above 0.  The step (``kda_step``)
scales the OLD state by a key's alpha inside both of its sums and in the
write-back, on the folded panels as they lie: alpha is spread over the lanes
like the keys.

``causal_conv`` / ``causal_conv_step`` are the depthwise convolution over
time (width ``K``, no bias) for a sequence and for a decode batch with the
``K - 1`` inputs a slot keeps (``conv_tail``), in its two forms: ahead of the
rule, over the fused q | k | v channels and followed by SiLU (width 4 in both
published models), and, with ``silu=False``, the convolution as it is, which
is the whole of a gated short-convolution layer's mixing over time (LFM2's
``Lfm2ShortConv``: width 3 over ``B * z``, ``models/llama.py::
_conv_operator``); such a layer keeps of its past the tail alone, no state.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import linear_state
# bound here by this name: a test replaces it on this module alone
from ray_tpu.ops.kernel_source import kernels_compiled as _kernel_backend

CHUNK = 64
SSM_CHUNK = 128          # of the rule without the correction: products alone
SUB_CHUNK = 16           # of a chunk, where the decay is a key channel's
GROUP = 8                # chunks whose products are made together, there
LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ convolution

def causal_conv(x: jax.Array, w: jax.Array, silu: bool = True,
                bias=None) -> jax.Array:
    """SiLU of the causal depthwise convolution of x [S, C] over time with
    w [K, C] (``w[K - 1]`` meets the position itself, zeros lie before the
    sequence) plus ``bias`` [C] where there is one, computed in float32, in
    x's dtype; without ``silu`` the convolution itself."""
    K, S = w.shape[0], x.shape[0]
    xp = jnp.pad(x.astype(jnp.float32), ((K - 1, 0), (0, 0)))
    y = sum(xp[i:i + S] * w[i].astype(jnp.float32) for i in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return (jax.nn.silu(y) if silu else y).astype(x.dtype)


def conv_tail(x: jax.Array, length: jax.Array, K: int) -> jax.Array:
    """The ``K - 1`` inputs before position ``length`` of x [S, C], oldest
    first, side by side [(K - 1) * C]: what ``causal_conv_step`` needs to go
    on from ``length`` (zeros where the sequence had not begun)."""
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return jax.lax.dynamic_slice_in_dim(xp, length, K - 1, 0).reshape(-1)


def causal_conv_step(x: jax.Array, w: jax.Array, tail: jax.Array,
                     silu: bool = True,
                     bias=None) -> Tuple[jax.Array, jax.Array]:
    """One position a slot: x [B, C] after the ``tail`` [B, (K - 1) * C] of
    ``conv_tail``.  Returns (the convolution's SiLU [B, C], with ``bias``
    [C] added first where there is one, or without ``silu`` the convolution
    itself; the next tail)."""
    K, C = w.shape
    window = [tail[:, i * C:(i + 1) * C] for i in range(K - 1)] + [x]
    y = sum(a.astype(jnp.float32) * w[i].astype(jnp.float32)
            for i, a in enumerate(window))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return (jax.nn.silu(y) if silu else y).astype(x.dtype), \
        jnp.concatenate(window[1:], axis=-1).astype(tail.dtype)


# ------------------------------------------------------- the folded state

def _panel_plan(N: int, dv: int) -> Tuple[int, int, int]:
    """(a panel's lanes, whole panels a head, heads side by side in a panel
    of left-over columns; 0: none are left over)."""
    rest = dv % LANES
    if not rest:
        return LANES, dv // LANES, 0
    if LANES % rest or N % (LANES // rest):
        return dv, 1, 0                  # plain: a head's columns, one panel
    # (heads narrower than a panel have no whole one: ``side`` of them lie
    # side by side in each, Mamba-2's 64 values a head two to a panel)
    return LANES, dv // LANES, LANES // rest


def state_shape(N: int, dk: int, dv: int) -> Tuple[int, int, int]:
    """A slot's folded state: [panels, dk, lanes], N * dk * dv values."""
    W, whole, side = _panel_plan(N, dv)
    return N * whole + (N // side if side else 0), dk, W


def _values_to_panels(v: jax.Array, N: int, dv: int) -> jax.Array:
    """[..., N, dv] -> [..., panels, lanes]: a head's whole panels, then the
    left-over columns of ``side`` heads side by side."""
    W, whole, side = _panel_plan(N, dv)
    lead = v.shape[:-2]
    full = v[..., :whole * W].reshape(*lead, N * whole, W)
    if not side:
        return full
    return jnp.concatenate(
        [full, v[..., whole * W:].reshape(*lead, N // side, W)], axis=-2)


def _panels_to_values(o: jax.Array, N: int, dv: int) -> jax.Array:
    """``_values_to_panels`` undone: [..., panels, lanes] -> [..., N, dv]."""
    W, whole, side = _panel_plan(N, dv)
    lead = o.shape[:-2]
    full = o[..., :N * whole, :].reshape(*lead, N, whole * W)
    if not side:
        return full
    return jnp.concatenate(
        [full, o[..., N * whole:, :].reshape(*lead, N, W // side)], axis=-1)


def _keys_to_panels(k: jax.Array, N: int, dv: int) -> jax.Array:
    """[..., N, dk] -> [..., panels, dk, lanes]: each lane holds the key of
    the head its column belongs to.  A select between broadcasts of small
    arrays (a panel's first head, its second, ...), for the compiler to
    fuse into what reads the state."""
    W, whole, side = _panel_plan(N, dv)
    full = jnp.repeat(k, whole, axis=-2) if whole != 1 else k
    if not side:
        return jnp.broadcast_to(full[..., None], (*full.shape, W))
    # head ``m * side + at`` of left-over panel m holds lanes ``at * W / side``
    # on; a whole panel is one head's throughout
    paired = k.reshape(*k.shape[:-2], N // side, side, k.shape[-1])
    heads = [jnp.concatenate([full, paired[..., at, :]], axis=-2)
             for at in range(side)]
    lane_head = jnp.arange(W) // (W // side)
    out = jnp.broadcast_to(heads[0][..., None], (*heads[0].shape, W))
    for at in range(1, side):
        out = jnp.where(lane_head == at, heads[at][..., None], out)
    return out


def fold_state(S: jax.Array) -> jax.Array:
    """[N, dk, dv] -> [panels, dk, lanes], the pool's layout of a slot."""
    N, dk, dv = S.shape
    return jnp.swapaxes(
        _values_to_panels(jnp.swapaxes(S, 0, 1), N, dv), 0, 1)


def unfold_state(P: jax.Array, N: int, dv: int) -> jax.Array:
    """``fold_state`` undone."""
    return jnp.swapaxes(
        _panels_to_values(jnp.swapaxes(P, 0, 1), N, dv), 0, 1)


# ------------------------------------------------------------- the rule

def l2_normalise(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """A head's vector over its length, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_recurrent(q, k, v, g, beta, state=None):
    """The rule as it is written, a position at a time.  q, k [S, N, dk]
    (k of unit length, q scaled), v [S, N, dv], g = log alpha [S, N] or, a
    value a key channel, [S, N, dk] (row i of a head's state then decays by
    alpha_i), beta [S, N], all float32; ``state`` [N, dk, dv] or zeros.
    Returns (o [S, N, dv], the state after the last position)."""
    N, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((N, dk, dv), jnp.float32)

    def position(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = S * jnp.exp(g_t).reshape(N, -1, 1)
        u = jnp.einsum("nij,ni->nj", S, k_t, precision=_HIGHEST)
        S = S + jnp.einsum("ni,nj->nij", k_t, b_t[:, None] * (v_t - u),
                           precision=_HIGHEST)
        return S, jnp.einsum("nij,ni->nj", S, q_t, precision=_HIGHEST)

    state, o = jax.lax.scan(position, state, (q, k, v, g, beta))
    return o, state


def gated_delta_chunked(q, k, v, g, beta, length=None, chunk: int = CHUNK):
    """The rule over a whole sequence from an empty state, ``chunk``
    positions at a time.  Arguments as ``gated_delta_recurrent``'s;
    positions from ``length`` on (None: there are none) leave the state
    alone.  Returns (o [S, N, dv], the state after position ``length - 1``
    [N, dk, dv])."""
    S_len, N, dk = q.shape
    dv = v.shape[-1]
    if length is not None:
        real = (jnp.arange(S_len) < length)[:, None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    pad = -S_len % chunk
    C = (S_len + pad) // chunk

    def chunks(a):       # [S, N, ...] -> [N, C, chunk, ...]
        a = jnp.pad(a, ((0, pad), *((0, 0),) * (a.ndim - 1)))
        return jnp.moveaxis(a.reshape(C, chunk, *a.shape[1:]), 2, 0)
    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)
    gc = jnp.cumsum(g, axis=-1)                          # [N, C, c]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))                 # [N, C, c, c]
    kb, vb = k * beta[..., None], v * beta[..., None]
    # the writes of a chunk's positions, each seeing those before it: the
    # unit lower triangular system (I + A) W = [v beta | k beta alpha]
    A = jnp.tril(mm("ncid,ncjd->ncij", kb, k) * decay, -1)
    T = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk), jnp.broadcast_to(jnp.eye(chunk), A.shape),
        lower=True, unit_diagonal=True)
    value = mm("ncij,ncjd->ncid", T, vb)
    k_decayed = mm("ncij,ncjd->ncid", T, kb * jnp.exp(gc)[..., None])
    within = jnp.where(lower, mm("ncid,ncjd->ncij", q, k) * decay, 0.0)

    def one_chunk(S, xs):
        q_c, k_c, value_c, kd_c, within_c, gc_c = xs     # [N, c, ...]
        v_new = value_c - mm("nid,ndj->nij", kd_c, S)
        o = mm("nid,ndj->nij", q_c * jnp.exp(gc_c)[..., None], S) \
            + mm("nij,njd->nid", within_c, v_new)
        last = gc_c[:, -1]
        S = S * jnp.exp(last)[:, None, None] + mm(
            "nid,nij->ndj", k_c * jnp.exp(last[:, None] - gc_c)[..., None],
            v_new)
        return S, o

    state, o = jax.lax.scan(
        one_chunk, jnp.zeros((N, dk, dv), jnp.float32),
        jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0),
                     (q, k, value, k_decayed, within, gc)))
    # [C, N, c, dv] -> [S, N, dv]
    o = jnp.moveaxis(o, 1, 2).reshape(C * chunk, N, dv)
    return o[:S_len], state


def gated_delta_step(q, k, v, g, beta, folded):
    """One position for each slot on the folded states: q, k [B, N, dk], v
    [B, N, dv], g, beta [B, N], ``folded`` [B, panels, dk, lanes].  Returns
    (o [B, N, dv], the folded states after it)."""
    N, dv = v.shape[-2], v.shape[-1]

    def per_head(a):     # [B, N] -> its head's value for every column
        return _values_to_panels(
            jnp.broadcast_to(a[..., None], v.shape), N, dv)
    Kp, Qp = _keys_to_panels(k, N, dv), _keys_to_panels(q, N, dv)
    alpha, Bp = per_head(jnp.exp(g)), per_head(beta)
    kq = per_head(jnp.sum(k * q, axis=-1))
    r_k = jnp.sum(folded * Kp, axis=-2)                  # S^T k
    r_q = jnp.sum(folded * Qp, axis=-2)                  # S^T q
    w = Bp * (_values_to_panels(v, N, dv) - alpha * r_k)
    o = alpha * r_q + kq * w
    folded = alpha[..., None, :] * folded + Kp * w[..., None, :]
    return _panels_to_values(o, N, dv), folded


def decay_and_beta(a, b, A_log, dt_bias, neg_eigval: bool):
    """The rule's two gates from their projections a, b [..., N] (float32
    inside): g = log alpha = -exp(A_log) softplus(a + dt_bias), and beta =
    sigmoid(b), doubled where the layer allows negative eigenvalues."""
    g = -jnp.exp(A_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    return g, 2.0 * beta if neg_eigval else beta


# ------------------------------------------- the rule, a decay a key channel

def kda_gate(f, A_log, dt_bias):
    """The per-channel decay's logarithm from its projection f [..., N * dk]
    (float32 inside): g = -exp(A_log[head]) softplus(f + dt_bias) [..., N,
    dk]; alpha = exp(g) in (0, 1)."""
    N = A_log.shape[-1]
    f = jax.nn.softplus(f.astype(jnp.float32) + dt_bias)
    return -jnp.exp(A_log)[:, None] * f.reshape(*f.shape[:-1], N, -1)


def _decayed_products(rows, k, G, sub: int):
    """For each of ``rows``, ``M[i, j] = sum_c rows[i, c] k[j, c] exp(G[i,
    c] - G[j, c])`` for the positions j <= i of a chunk and 0 for the
    others: each of rows, k, G [..., c, dk] (G the inclusive running sum of
    log alpha, so no exponent taken here is above 0) -> [..., c, c] each.
    By sub-chunks of ``sub`` positions (module docstring): against the
    positions before a sub-chunk one product with both factors referred to
    the sub-chunk's first position's predecessor, inside it pair by pair;
    the keys' factors and the pairs' exponents are made once for all
    ``rows``."""
    *lead, c, dk = k.shape
    n = c // sub

    def split(a):        # [..., c, dk] -> [..., n, sub, dk]
        return a.reshape(*lead, n, sub, dk)
    k_s, G_s = split(k), split(G)
    # G just before each sub-chunk: the chunk's start (0) for the first
    before = jnp.concatenate(
        [jnp.zeros_like(G_s[..., :1, -1, :]), G_s[..., :-1, -1, :]],
        axis=-2)                                         # [..., n, dk]
    since = jnp.exp(G_s - before[..., None, :])
    right = k[..., None, :, :] * jnp.exp(jnp.minimum(
        before[..., None, :] - G[..., None, :, :], 0.0))  # [..., n, c, dk]
    at = jnp.arange(c) // sub
    past = at[None, None, :] < jnp.arange(n)[:, None, None]
    # inside a sub-chunk: every pair's own exponents
    pair = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    paired = k_s[..., None, :, :] * jnp.exp(jnp.where(
        pair, G_s[..., :, None, :] - G_s[..., None, :, :], -jnp.inf))

    def product(rows_s):
        earlier = jnp.einsum("...aid,...ajd->...aij", rows_s * since, right,
                             precision=_HIGHEST)         # [..., n, sub, c]
        own = jnp.sum(rows_s[..., :, None, :] * paired, axis=-1)
        own = own[..., :, :, None, :] * jnp.eye(n)[:, None, :, None]
        return jnp.where(past, earlier, 0.0).reshape(*lead, c, c) \
            + own.reshape(*lead, c, c)
    return tuple(product(split(a)) for a in rows)


def kda_chunked(q, k, v, g, beta, length=None, chunk: int = CHUNK,
                sub: int = SUB_CHUNK, group: int = GROUP):
    """``gated_delta_chunked`` with g [S, N, dk] = log alpha a key channel,
    a chunk's two c x c matrices by sub-chunks (``_decayed_products``).
    What a chunk needs before the state reaches it (the two matrices, the
    triangular solve and its two products) is made for ``group`` chunks
    together, one group after the other in the program's text: made for all
    of a long sequence's chunks at once, the pair-by-pair exponents of the
    sub-chunks are hundreds of megabytes that no longer stay in the chip's
    fast memory (the 1024 rung's scan took four times the 512 rung's), and
    made in a loop the groups' results are copied into its stacked output
    (a loop of two groups cost more than it saved; PERF.md section 6, PR
    51)."""
    S_len, N, dk = q.shape
    dv = v.shape[-1]
    if length is not None:
        real = jnp.arange(S_len) < length
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
    pad = -S_len % chunk
    C = (S_len + pad) // chunk

    def chunks(a):       # [S, N, ...] -> [N, C, chunk, ...]
        a = jnp.pad(a, ((0, pad), *((0, 0),) * (a.ndim - 1)))
        return jnp.moveaxis(a.reshape(C, chunk, *a.shape[1:]), 2, 0)
    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)

    def prepared(q, k, v, g, beta):      # of some chunks, [N, chunks, c, ...]
        G = jnp.cumsum(g, axis=-2)
        kb, vb = k * beta[..., None], v * beta[..., None]
        # (I + A) W = [v beta | k beta exp(G)], A strictly lower triangular
        A, within = _decayed_products((kb, q), k, G, sub)
        A = jnp.tril(A, -1)
        T = jax.scipy.linalg.solve_triangular(
            A + jnp.eye(chunk), jnp.broadcast_to(jnp.eye(chunk), A.shape),
            lower=True, unit_diagonal=True)
        return (mm("ncij,ncjd->ncid", T, vb),
                mm("ncij,ncjd->ncid", T, kb * jnp.exp(G)), within, G)
    value, k_decayed, within, G = (
        jnp.concatenate(parts, axis=1) for parts in zip(*(
            prepared(*(a[:, at:at + group] for a in (q, k, v, g, beta)))
            for at in range(0, C, group))))

    def one_chunk(S, xs):
        q_c, k_c, value_c, kd_c, within_c, G_c = xs      # [N, c, ...]
        v_new = value_c - mm("nid,ndj->nij", kd_c, S)
        o = mm("nid,ndj->nij", q_c * jnp.exp(G_c), S) \
            + mm("nij,njd->nid", within_c, v_new)
        last = G_c[:, -1]                                # [N, dk]
        S = S * jnp.exp(last)[:, :, None] + mm(
            "nid,nij->ndj", k_c * jnp.exp(last[:, None] - G_c), v_new)
        return S, o

    state, o = jax.lax.scan(
        one_chunk, jnp.zeros((N, dk, dv), jnp.float32),
        jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0),
                     (q, k, value, k_decayed, within, G)))
    o = jnp.moveaxis(o, 1, 2).reshape(C * chunk, N, dv)
    return o[:S_len], state


def kda_step(q, k, v, g, beta, folded):
    """``gated_delta_step`` with g [B, N, dk] = log alpha a key channel.
    The old state's row i counts alpha_i in both sums and in the
    write-back: ``S'^T k = S^T (alpha k)``, ``S'^T q = S^T (alpha q)``, then
    ``o = S'^T q + (k . q) w`` with ``w = beta (v - S'^T k)`` and the state
    ``Diag(alpha) S + k w^T``, on the panels as they lie."""
    N, dv = v.shape[-2], v.shape[-1]

    def per_head(a):     # [B, N] -> its head's value for every column
        return _values_to_panels(
            jnp.broadcast_to(a[..., None], v.shape), N, dv)
    alpha = jnp.exp(g)
    r_k = jnp.sum(folded * _keys_to_panels(alpha * k, N, dv), axis=-2)
    r_q = jnp.sum(folded * _keys_to_panels(alpha * q, N, dv), axis=-2)
    w = per_head(beta) * (_values_to_panels(v, N, dv) - r_k)
    o = r_q + per_head(jnp.sum(k * q, axis=-1)) * w
    folded = _keys_to_panels(alpha, N, dv) * folded \
        + _keys_to_panels(k, N, dv) * w[..., None, :]
    return _panels_to_values(o, N, dv), folded


# ------------------------- the rule without the correction (Mamba-2's)

def ssm_recurrent(q, k, v, g, state=None):
    """The state-space rule as it is written, a position at a time: q, k
    [S, dk] (ONE query and ONE key a position for all heads: Mamba-2's ``C``
    and ``B`` with one group), v [S, N, dv] (a head's input times its step
    size), g = log a [S, N], all float32; ``state`` [N, dk, dv] or zeros.
    ``S_n <- a_n S_n + k v_n^T``, ``o_n = S_n^T q``.  Returns (o [S, N, dv],
    the state after the last position)."""
    N, dv, dk = v.shape[1], v.shape[2], q.shape[1]
    if state is None:
        state = jnp.zeros((N, dk, dv), jnp.float32)

    def position(S, row):
        q_t, k_t, v_t, g_t = row
        S = S * jnp.exp(g_t)[:, None, None] \
            + k_t[None, :, None] * v_t[:, None, :]
        return S, jnp.einsum("nij,i->nj", S, q_t, precision=_HIGHEST)

    state, o = jax.lax.scan(position, state, (q, k, v, g))
    return o, state


def ssm_chunked(q, k, v, g, length=None, chunk: int = SSM_CHUNK):
    """``ssm_recurrent`` over a whole sequence from an empty state, ``chunk``
    positions at a time (State Space Duality's blocks, arXiv:2405.21060): a
    chunk's positions meet each other through ONE matrix of products ``q_i .
    k_j`` that all heads share, times a head's decay between the two, and
    the chunks before it through the state; products alone, no triangular
    solve, because nothing a position writes depends on what the state
    held.  Positions from ``length`` on (None: there are none) leave the
    state alone (a = 1 and nothing written).  Returns (o [S, N, dv], the
    state after position ``length - 1`` [N, dk, dv])."""
    S_len, N, dv = v.shape
    dk = q.shape[-1]
    if length is not None:
        real = jnp.arange(S_len) < length
        g = jnp.where(real[:, None], g, 0.0)
        v = jnp.where(real[:, None, None], v, 0.0)
    chunk = min(chunk, S_len)
    pad = -S_len % chunk
    C = (S_len + pad) // chunk

    def chunks(a):       # [S, ...] -> [C, chunk, ...]
        a = jnp.pad(a, ((0, pad), *((0, 0),) * (a.ndim - 1)))
        return a.reshape(C, chunk, *a.shape[1:])
    q, k, v, g = map(chunks, (q, k, v, g))

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)
    G = jnp.cumsum(g, axis=1)                            # [C, c, N]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # a head's decay from position j to position i >= j of a chunk; the
    # exponent is never above 0
    decay = jnp.exp(jnp.where(lower[None, :, :, None],
                              G[:, :, None] - G[:, None], -jnp.inf))
    within = mm("cid,cjd->cij", q, k)[..., None] * decay  # [C, c, c, N]
    inside = mm("cijn,cjnp->cinp", within, v)
    # what a chunk adds to the state that reaches its end, and how much of
    # the state before it is left there
    last = G[:, -1]                                      # [C, N]
    written = mm("cjd,cjnp->cndp", k,
                 v * jnp.exp(last[:, None] - G)[..., None])

    def one_chunk(S, xs):
        q_c, G_c, last_c, written_c = xs
        o = mm("id,ndp->inp", q_c, S) * jnp.exp(G_c)[..., None]
        return S * jnp.exp(last_c)[:, None, None] + written_c, o

    state, before = jax.lax.scan(
        one_chunk, jnp.zeros((N, dk, dv), jnp.float32),
        (q, G, last, written))
    o = (inside + before).reshape(C * chunk, N, dv)
    return o[:S_len], state


def ssm_step(q, k, v, g, folded):
    """One position for each slot on the folded states: q, k [B, dk], v [B,
    N, dv], g [B, N], ``folded`` [B, panels, dk, lanes].  ``o = a S^T q + (k
    . q) v`` is the new state's read-out written out, the new state ``a S +
    k v^T``.  Returns (o [B, N, dv], the folded states after it)."""
    N, dv = v.shape[-2], v.shape[-1]
    alpha = _values_to_panels(
        jnp.broadcast_to(jnp.exp(g)[..., None], v.shape), N, dv)
    Vp = _values_to_panels(v, N, dv)
    r_q = jnp.sum(folded * q[:, None, :, None], axis=-2)     # S^T q
    o = alpha * r_q + jnp.sum(k * q, axis=-1)[:, None, None] * Vp
    folded = alpha[..., None, :] * folded \
        + k[:, None, :, None] * Vp[..., None, :]
    return _panels_to_values(o, N, dv), folded


# ------------------------------------------------ the step, on the pool

# the module's own two steps: a caller that replaces one (a numerics tool
# planting a fault) gets its step run, on every backend
_OWN_STEPS = (gated_delta_step, kda_step, ssm_step)


def state_step_kind(pool, N: int, dv: int, shared: bool = False) -> str:
    """What ``step_pool`` steps the states of ``N`` heads of ``dv`` values
    in ``pool`` (an array or its shape) with, "kernel" (``ops/
    linear_state.py``) or "rule" (``gated_delta_step`` / ``kda_step`` /
    ``ssm_step`` on the layer's slab), from what it can observe: the
    backend, whether the kernel is written for the pool
    (``linear_state.supported``: float32, folded into panels of 128 lanes,
    key channels of whole sublane tiles; ``shared``: one key and one query
    for all heads, the rule without the correction), and whether this
    module's steps are still its own.  Measured on the chip at the
    published shapes (``scripts/linear_state_sweep.py``; PERF.md section 6,
    PR 52 and PR 61)."""
    _, whole, side = _panel_plan(N, dv)
    if _kernel_backend() \
            and (gated_delta_step, kda_step, ssm_step) == _OWN_STEPS \
            and linear_state.supported(pool.shape, pool.dtype, N, whole,
                                       side, shared):
        return "kernel"
    return "rule"


def step_pool(q, k, v, g, beta, pool, layer, live):
    """One position for each slot on the states where the pool stores them:
    q, k [B, N, dk], v [B, N, dv], beta [B, N], g = log alpha [B, N] (a
    head's) or [B, N, dk] (a key channel's), ``pool`` [L, B, panels, dk,
    lanes] float32 (every linear layer's folded states), ``layer`` the
    layer's index into it, ``live`` [B] bool: a slot that is not keeps its
    rows to the bit.  With ``beta`` None the rule is the one WITHOUT the
    correction (``ssm_step``): q, k [B, dk] are one query and one key a slot
    for all heads, which no program spreads over the heads in HBM.  Returns
    (o [B, N, dv], the pool)."""
    N, dv = v.shape[-2], v.shape[-1]
    shared = beta is None
    if state_step_kind(pool, N, dv, shared) == "rule":
        held = pool[layer]
        if shared:
            o, state = ssm_step(q, k, v, g, held)
        else:
            step = kda_step if g.ndim == 3 else gated_delta_step
            o, state = step(q, k, v, g, beta, held)
        state = jnp.where(live[:, None, None, None], state, held)
        return o, jax.lax.dynamic_update_index_in_dim(pool, state, layer, 0)

    def per_head(a):     # [B, N] -> its head's value for every column
        return _values_to_panels(
            jnp.broadcast_to(a[..., None], v.shape), N, dv)
    _, whole, side = _panel_plan(N, dv)
    if shared:
        # what varies along a panel's lanes: v, the head's decay, k . q
        rows = jnp.stack([
            _values_to_panels(v, N, dv), per_head(jnp.exp(g)),
            jnp.broadcast_to(jnp.sum(k * q, axis=-1)[:, None, None],
                             (v.shape[0], *pool.shape[2:3], LANES))], axis=1)
        cols = linear_state.columns(k[:, None], q[:, None])
    else:
        if g.ndim == 2:  # a head's decay: its key channels' all equal
            g = jnp.broadcast_to(g[..., None], k.shape)
        rows = jnp.stack([_values_to_panels(v, N, dv), per_head(beta),
                          per_head(jnp.sum(k * q, axis=-1))], axis=1)
        cols = linear_state.columns(jnp.exp(g), k, q)
    o, pool = linear_state.state_step(
        pool, layer, live, cols, rows, heads=N, whole=whole, side=side,
        shared=shared)
    return _panels_to_values(o, N, dv), pool
