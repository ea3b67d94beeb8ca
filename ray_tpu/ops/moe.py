"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

The reference has no MoE/expert parallelism (SURVEY §2.4 lists it as a
must-build for the TPU framework).  This is the GShard/Switch recipe, which
is the idiomatic TPU formulation: instead of a hand-written ragged
all-to-all (the GPU/NCCL way), tokens are routed into a dense
capacity-bounded dispatch tensor and moved between data- and expert-sharded
layouts by two einsums.  When the expert dim carries the ``ep`` mesh axis,
XLA lowers those einsums to all-to-all collectives over ICI — the dispatch
is compiler-emitted, fused, and overlappable, with no runtime library.

Shapes (S = tokens per batch row, E = experts, C = per-expert capacity):
  router logits  [B, S, E]        (f32 for a stable softmax)
  dispatch       [B, S, E, C]     0/1, token -> (expert, slot)
  combine        [B, S, E, C]     gate-weighted dispatch
  expert input   [E, B, C, D]     = einsum(x, dispatch)   <- all-to-all
  expert output  [E, B, C, D]     FFN per expert
  result         [B, S, D]        = einsum(ye, combine)   <- all-to-all back

Tokens beyond an expert's capacity are dropped (their combine weight is 0 and
the residual connection carries them through unchanged) — standard Switch
behavior; raise ``capacity_factor`` to trade memory for fewer drops.

Two paths live here, with different callers:

* ``moe_router`` / ``moe_mlp``: the capacity path above.  It DROPS what
  exceeds an expert's capacity, has GELU experts with biases, and is used
  only by ``models/gpt.py`` (training, expert parallelism over ``ep``,
  the GPipe pipeline).
* ``moe_dropless``: token-choice top-k with NO capacity, SwiGLU experts
  without biases.  Every assignment of a token that is somebody's is
  computed: assignments are sorted by expert and the experts run as
  grouped matmuls over the ragged groups
  (``ops/grouped_matmul.py``, a Pallas kernel that is handed one layer's
  groups and finds that layer's experts in the stack of all layers by an
  offset where it copies them from: work goes with the assignments, not with
  experts x tokens, and an expert nobody chose is neither read nor
  visited).  Used by ``models/llama.py`` (``_ffn`` when
  ``LlamaConfig.num_experts > 0``), and so by the paged serving engine,
  whose padded prefill and idle decode slots would take capacity from real
  tokens under the first path: only without a capacity are a token's
  logits independent of what else is in the batch (which is also why
  those rows can be routed nowhere and cost the experts nothing).  Single
  device: it has no ``ep`` sharding and no auxiliary loss (inference only).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_matmul import grouped_matmul


def moe_router(x, router_w, *, top_k: int, capacity: int):
    """Top-k routing with per-expert capacity.

    x [B,S,D] (any float dtype), router_w [D,E] (f32).
    Returns (dispatch [B,S,E,C] bool-ish, combine [B,S,E,C], aux_loss scalar).
    """
    B, S, D = x.shape
    E = router_w.shape[-1]
    C = capacity

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)                      # [B,S,E]
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)            # [B,S,k]
    # Renormalize the selected gates so the combine weights sum to 1.
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # Sequentially assign slots: k=0 choices get priority, then k=1, ...
    # (matches t5x/GShard ordering so top-1 picks are never bumped by
    # someone's secondary expert).
    counts = jnp.zeros((B, E), jnp.int32)
    dispatch = jnp.zeros((B, S, E, C), x.dtype)
    combine = jnp.zeros((B, S, E, C), jnp.float32)
    for i in range(top_k):
        oh = jax.nn.one_hot(gate_idx[:, :, i], E, dtype=jnp.int32)  # [B,S,E]
        pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None, :]       # [B,S,E]
        counts = counts + jnp.sum(oh, axis=1)
        within = (pos < C) & (oh > 0)                               # [B,S,E]
        slot = jax.nn.one_hot(jnp.clip(pos, 0, C - 1), C,
                              dtype=jnp.float32)                    # [B,S,E,C]
        sel = within.astype(jnp.float32)[..., None] * slot
        dispatch = dispatch + sel.astype(x.dtype)
        combine = combine + gate_vals[:, :, i, None, None] * sel

    # Switch load-balance loss: E * sum_e fraction_dispatched_e * mean_prob_e
    # (computed on top-1 assignments; differentiable through probs).
    top1 = jax.nn.one_hot(gate_idx[:, :, 0], E, dtype=jnp.float32)
    frac = jnp.mean(top1, axis=(0, 1))                              # [E]
    mean_prob = jnp.mean(probs, axis=(0, 1))                        # [E]
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_mlp(x, p, *, top_k: int, capacity_factor: float,
            lc: Optional[Callable] = None,
            ep_axis: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel FFN (drop-in for the dense MLP body of a block).

    x [B,S,D]; p = {"router": [D,E] f32, "wi": [E,D,M], "bi": [E,M],
    "wo": [E,M,D], "bo": [E,D]} (expert dim carries the "expert" logical
    axis -> ep mesh axis).  ``lc(array, logical_axes)`` applies sharding
    constraints (identity when running unsharded / inside shard_map).

    Two expert-parallel modes:
      * GSPMD (default): expert weights/activations carry the "expert"
        logical axis; XLA emits the dispatch all-to-alls.
      * shard_map (``ep_axis`` set): weights arrive PRE-SHARDED on their
        leading expert dim ([E/ep, ...]); each ep member runs its local
        experts on the (ep-replicated) token batch and an all_gather over
        ``ep_axis`` reassembles expert outputs.  This is how MoE composes
        inside manually-mapped programs like the GPipe pipeline, where
        GSPMD constraints don't apply.
    Returns (y [B,S,D], aux_loss).
    """
    if lc is None:
        lc = lambda a, ax: a  # noqa: E731
    B, S, D = x.shape
    E = p["router"].shape[-1]
    dt = x.dtype
    capacity = max(1, int(capacity_factor * S * top_k / E))

    dispatch, combine, aux = moe_router(
        x, p["router"].astype(jnp.float32), top_k=top_k, capacity=capacity)

    # Data-sharded -> expert-sharded: XLA emits the all-to-all here.
    if ep_axis is not None:
        # Slice the dispatch tensor to this member's experts BEFORE the
        # contraction: 1/ep of the dispatch FLOPs and no full-E [E,B,C,D]
        # buffer per pipeline tick.
        e_local = p["wi"].shape[0]
        idx = jax.lax.axis_index(ep_axis)
        disp_local = jax.lax.dynamic_slice_in_dim(
            dispatch, idx * e_local, e_local, 2)
        xe = jnp.einsum("bsd,bsec->ebcd", x, disp_local.astype(dt))
    else:
        xe = jnp.einsum("bsd,bsec->ebcd", x, dispatch.astype(dt))
        xe = lc(xe, ("expert", "batch", None, "embed"))
    h = jnp.einsum("ebcd,edm->ebcm", xe, p["wi"].astype(dt)) \
        + p["bi"].astype(dt)[:, None, None, :]
    h = lc(h, ("expert", "batch", None, "mlp"))
    h = jax.nn.gelu(h)
    ye = jnp.einsum("ebcm,emd->ebcd", h, p["wo"].astype(dt)) \
        + p["bo"].astype(dt)[:, None, None, :]
    ye = lc(ye, ("expert", "batch", None, "embed"))
    if ep_axis is not None:
        ye = jax.lax.all_gather(ye, ep_axis, axis=0, tiled=True)
    # Expert-sharded -> data-sharded: the return all-to-all.
    y = jnp.einsum("ebcd,bsec->bsd", ye, combine.astype(dt))
    return lc(y, ("batch", "seq", "embed")), aux


def _route(logits, bias, top_k: int, scoring: str, norm_topk_prob: bool,
           routed_scaling: float, norm_eps: float = 1e-20,
           groups: Tuple[int, int] = (1, 1)):
    """A token's experts and their gates from the router's float32 logits
    [T, E] -> (gates [T, k], experts [T, k]).  ``softmax``: the ``top_k``
    largest of a softmax over all experts are the gates, as they are unless
    ``norm_topk_prob`` renormalises them to sum to 1.  ``sigmoid``
    (DeepSeek-V3's ``noaux_tc``): the scores are sigmoids; the experts are
    the ``top_k`` largest of score + ``bias`` [E] (the balancing bias, which
    only SELECTS); the gates are the chosen experts' scores WITHOUT it,
    renormalised if ``norm_topk_prob`` (divided by their sum + ``norm_eps``:
    DeepSeek-V3's and Kimi's 1e-20, LFM2's 1e-6) and times
    ``routed_scaling``.  ``groups`` (n, k) limits the sigmoid selection to
    groups: the E experts lie in n equal groups in their order, a group's
    mark is the sum of its two largest score + bias (its largest score
    where there is no bias), the k groups that mark highest are kept and
    the ``top_k`` experts are the largest of THOSE groups' (an expert of
    another group is chosen never, whatever it scores); (1, 1): no limit,
    and nothing of it is traced."""
    if scoring == "softmax":
        gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                       top_k)                    # [T, k]
        if norm_topk_prob:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return gates, experts
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias
    if groups[0] > 1:
        n, best = groups
        grouped = choice.reshape(choice.shape[0], n, -1)
        mark = jnp.max(grouped, axis=-1) if bias is None else \
            jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)       # [T, n]
        _, kept = jax.lax.top_k(mark, best)
        kept = jnp.any(kept[:, :, None] == jnp.arange(n), axis=1)
        choice = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(choice.shape)
    _, experts = jax.lax.top_k(choice, top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + norm_eps)
    return gates * routed_scaling, experts


def moe_dropless(x, p, *, top_k: int, norm_topk_prob: bool = False,
                 live: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None,
                 scoring: str = "softmax", routed_scaling: float = 1.0,
                 shared: Optional[dict] = None, first_expert: int = 0,
                 norm_eps: float = 1e-20, groups: Tuple[int, int] = (1, 1)
                 ) -> Tuple[jax.Array, jax.Array]:
    """Dropless top-k expert FFN over flat tokens.

    x [T, D]; p = {"router": [D, E], "wgu": [E, 2, D, M] (SwiGLU gate and
    up), "wd": [E, M, D]}.  With ``layer`` (an index, traced or not) every
    leaf of ``p`` has a leading layers dim and the layer's experts are read
    out of the stack where they lie: a model that scans its layers hands
    over the whole stack and the scan's index, because a layer's slice of
    it would be a copy of every expert's weights ahead of each step.

    The gates are ``_route``'s: the ``top_k`` largest of a float32 softmax
    over all E experts, used as they are unless ``norm_topk_prob``
    renormalises them to sum to 1, or with ``scoring="sigmoid"`` sigmoid
    scores selected with ``p["router_bias"]`` [E] added (if the tree has
    it), renormalised over their sum + ``norm_eps`` and scaled by
    ``routed_scaling``, inside the best of ``groups`` where that is a
    limit.  ``shared`` ({"wgu": [2, D, Ms],
    "wd": [Ms, D]}, this layer's) is a SwiGLU expert that every token goes
    through, ungated, added to the routed sum.  ``live`` [T] bool marks the
    tokens that are somebody's (not padding, not an idle decode slot); all
    of them when None.  A row that is not live is routed NOWHERE: its
    assignments are sorted behind the last group, where the grouped matmuls
    visit no row, are in no group's size and add nothing in the combine, so
    the routed part of its output is exactly zero (the shared expert, a
    dense product, still runs on every row).  Each token's experts are its
    own, so a live row's result is the same bits with ``live`` and without.

    Assignments are sorted by expert and the experts run as two grouped
    matmuls over the ragged groups (``grouped_matmul``): gate and up in
    one, over ``wgu`` seen as 2E groups of [D, M] (each expert's rows
    twice), then down.  The kernel is handed this layer's group sizes and
    the stacks as they are stored, reshaped to [L * 2E, D, M] and
    [L * E, M, D]; ``layer`` reaches it as a scalar that it adds to the
    group where it copies the weights from, so the other layers are never
    walked, and a group without rows is not in its grid: a touched
    expert's matrices cross
    from HBM once, in whole tiles of megabytes, at 85-90% of the HBM's
    rate from one to eight rows an expert (the compiler's ``ragged_dot``
    kernel, which stood here, paid 2-8 us more than its bytes for every
    group with rows: PERF.md section 6, PR 41).  The
    products run in the WEIGHTS' own type, the activations (a few rows an
    expert) cast to it, and accumulate in float32: casting the weights
    instead moves all of them every step for the few that are read.

    THE HELD EXPERTS.  Where the router is wider than the stack (``router``
    [D, R] with R > E), the stack holds experts ``first_expert ..
    first_expert + E - 1`` of the R the router scores: one chip's share of a
    layer whose experts are divided over several.  The routing is over all
    R, gates and all; the assignments that fall on a held expert are
    computed as above and the others add NOTHING here (they are another
    chip's part of the sum: sorted behind the last group, where the grouped
    matmuls visit no row, and left out of the combine), so the shares of all
    the chips, with the shared expert counted once, add up to the uncut
    layer (``tests/test_moe_held.py``).  No exchange runs and nothing stands
    in for the absent chips.  ``load`` is then over the held experts, and
    its sum against ``live tokens x top_k`` is the share of assignments
    kept.  A dead row's assignments go the same way, whatever their expert.

    Returns (y [T, D] in x's type, load [E] int32: the kept assignments per
    held expert, which are the groups' sizes).  The parts carry the scopes
    ``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine`` and
    ``moe_shared`` for the profiler.
    """
    T, D = x.shape
    A = T * top_k
    if layer is None:               # one layer's experts: a stack of one
        p, layer = jax.tree.map(lambda a: a[None], p), 0
    L, E, _, _, M = p["wgu"].shape
    wgu = p["wgu"].reshape(L * E * 2, D, M)
    wd = p["wd"].reshape(L * E, M, D)

    with jax.named_scope("moe_router"):
        # float32 for real: on a TPU the default precision of a float32
        # product is one bfloat16 pass, and the eighth and ninth expert
        # of a token can be that close
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            p["router"][layer].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        bias = p["router_bias"][layer] if "router_bias" in p else None
        # (the epsilon and the groups by name and only where they are not
        # the defaults: the numerics tools plant routers of the six-argument
        # form)
        gates, experts = _route(
            logits, bias, top_k, scoring, norm_topk_prob, routed_scaling,
            **({} if norm_eps == 1e-20 else {"norm_eps": norm_eps}),
            **({} if tuple(groups) == (1, 1) else {"groups": groups}))
    # An assignment is KEPT where its row is somebody's and its expert is
    # held; the others get E, one past the held experts (which count from
    # 0): nobody's here.
    kept = None if live is None else \
        jnp.broadcast_to(live[:, None], experts.shape)
    if p["router"].shape[-1] != E or first_expert:
        held = (experts >= first_expert) & (experts < first_expert + E)
        kept = held if kept is None else kept & held
    if kept is not None:
        experts = jnp.where(kept, experts - first_expert, E)
    with jax.named_scope("moe_dispatch"):
        flat = experts.reshape(A)                  # assignment -> expert
        sizes = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0,
                        dtype=jnp.int32)                         # [E]
        order = jnp.argsort(flat, stable=True)     # sorted by expert
        token = order // top_k                     # sorted row -> token
        # Rows for the gate/up matmul: expert e's n_e rows for its gate
        # group, the same rows again for its up group.  Row q of that
        # layout lies in group g (found by counting the group ends it has
        # passed) at rank r, and is sorted row start[e] + r.
        start = jnp.cumsum(sizes) - sizes
        sizes2 = jnp.repeat(sizes, 2)
        ends2 = jnp.cumsum(sizes2)
        q = jnp.arange(2 * A)
        g = jnp.sum(q[:, None] >= ends2[None, :], axis=1)
        row = start[g // 2] + q - (ends2 - sizes2)[g]
        xs2 = x[token[row]].astype(wgu.dtype)                    # [2A, D]
        # where sorted row j's gate and up results will be found
        expert = flat[order]
        gate_at = jnp.arange(A) + start[expert]
        up_at = gate_at + sizes[expert]
    with jax.named_scope("moe_experts"):
        gu = grouped_matmul(xs2, wgu, sizes2, layer)             # [2A, M]
        hidden = jax.nn.silu(gu[gate_at]) * gu[up_at]            # [A, M]
        ys = grouped_matmul(hidden.astype(wd.dtype), wd, sizes,
                            layer)                               # [A, D]
    with jax.named_scope("moe_combine"):
        # back to token order by the inverse permutation (a gather, not a
        # scatter-add), then the gate-weighted sum of each token's k
        back = ys[jnp.argsort(order)].reshape(T, top_k, D)
        weighed = back.astype(jnp.float32) * gates[:, :, None]
        if kept is not None:     # a row no group visited holds anything
            weighed = jnp.where(kept[:, :, None], weighed, 0.0)
        y = jnp.sum(weighed, axis=1)
    if shared is not None:
        with jax.named_scope("moe_shared"):
            gu = jnp.einsum("td,cdm->ctm", x, shared["wgu"])
            y = y + jnp.einsum("tm,md->td", jax.nn.silu(gu[0]) * gu[1],
                               shared["wd"]).astype(jnp.float32)
    return y.astype(x.dtype), sizes
