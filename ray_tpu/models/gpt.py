"""GPT-2-class decoder-only transformer, TPU-first.

The reference's north-star training benchmark is GPT-2 DDP under Ray Train
(`release/air_tests/air_benchmarks/`); this is the equivalent flagship model,
but designed for the MXU rather than ported: bf16 compute / f32 params & o
ptimizer state, layers stacked into one scanned [L, ...] pytree (single XLA
while-loop, constant compile time in depth, and the layer dim doubles as the
pipeline-parallel shard axis), logical-axis annotations on every param so the
same definition runs dp/fsdp/tp/pp/sp via `ray_tpu.parallel` rule tables,
`jax.checkpoint` rematerialization per layer, and a pluggable attention body
(dense causal or ring attention from `ray_tpu.ops`).
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import (_dense_causal_attention_bnsh,
                                   _flash_attention_bnsh, resolve_attention)
from ray_tpu.parallel.collectives import (add_bias_first, gathered_einsum,
                                          scattered_einsum, tp_size)
from ray_tpu.parallel.sharding import LogicalAxisRules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 padded to a multiple of 128
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16        # compute dtype (params stay f32)
    remat: bool = True
    # "full" recomputes the whole block in bwd (min memory); "dots" saves
    # matmul outputs and recomputes only elementwise ops; "attn" saves
    # only attention outputs (never re-runs the flash kernel in bwd);
    # "attn_dots" saves both (fastest when it fits HBM).
    remat_policy: str = "full"   # "full" | "dots" | "attn" | "attn_dots"
    # "auto" is resolved by ops/attention.py::resolve_attention (flash from
    # S = 1024 on a TPU, dense otherwise); explicit values pin the
    # implementation.
    attention: str = "auto"  # "auto"|"dense"|"flash"|"ring" (ring: sp>1)
    # Sequence-block size for the blocked cross-entropy head (0 = apply the
    # head over the full sequence).  With a block, head matmul + CE run per
    # chunk under jax.checkpoint, so no [B, S, V] logits tensor is ever
    # live — peak head memory drops V/block-fold for one extra head-matmul
    # recompute in backward.
    ce_block: int = 0
    # MoE (0 = dense FFN).  Experts shard over the ep mesh axis; routing is
    # GShard/Switch-style capacity-bounded dispatch (ray_tpu/ops/moe.py).
    num_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01       # load-balance loss weight

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def mlp_dim(self) -> int:
        return self.mlp_ratio * self.embed_dim

    @staticmethod
    def gpt2_small() -> "GPTConfig":
        return GPTConfig()

    @staticmethod
    def tiny(vocab: int = 256, seq: int = 128) -> "GPTConfig":
        return GPTConfig(vocab_size=vocab, max_seq_len=seq, num_layers=2,
                         num_heads=4, embed_dim=64)


def gpt_init(rng: jax.Array, cfg: GPTConfig) -> Dict[str, Any]:
    """Initialize params. Per-layer weights are stacked on a leading [L] dim."""
    k = jax.random.split(rng, 8)
    D, H, M, L, V = (cfg.embed_dim, cfg.head_dim, cfg.mlp_dim,
                     cfg.num_layers, cfg.vocab_size)
    nh = cfg.num_heads
    scale = 0.02
    # residual-branch projections get the GPT-2 depth-scaled init
    rscale = scale / np.sqrt(2 * L)

    def norm(shape):
        return {"scale": jnp.ones(shape, jnp.float32),
                "bias": jnp.zeros(shape, jnp.float32)}

    if cfg.num_experts:
        E = cfg.num_experts
        ek = jax.random.split(k[6], 3)
        mlp = {
            "router": scale * jax.random.normal(ek[0], (L, D, E),
                                                jnp.float32),
            "wi": scale * jax.random.normal(ek[1], (L, E, D, M), jnp.float32),
            "bi": jnp.zeros((L, E, M), jnp.float32),
            "wo": rscale * jax.random.normal(ek[2], (L, E, M, D),
                                             jnp.float32),
            "bo": jnp.zeros((L, E, D), jnp.float32),
        }
    else:
        mlp = {
            "wi": scale * jax.random.normal(k[4], (L, D, M), jnp.float32),
            "bi": jnp.zeros((L, M), jnp.float32),
            "wo": rscale * jax.random.normal(k[5], (L, M, D), jnp.float32),
            "bo": jnp.zeros((L, D), jnp.float32),
        }

    return {
        "wte": scale * jax.random.normal(k[0], (V, D), jnp.float32),
        "wpe": scale * jax.random.normal(k[1], (cfg.max_seq_len, D),
                                         jnp.float32),
        "layers": {
            "ln1": norm((L, D)),
            "attn": {
                "wqkv": scale * jax.random.normal(
                    k[2], (L, D, 3, nh, H), jnp.float32),
                "wo": rscale * jax.random.normal(
                    k[3], (L, nh, H, D), jnp.float32),
                "bo": jnp.zeros((L, D), jnp.float32),
            },
            "ln2": norm((L, D)),
            "mlp": mlp,
        },
        "ln_f": norm((D,)),
    }


def gpt_param_axes(cfg: GPTConfig) -> Dict[str, Any]:
    """Logical-axis annotation pytree matching `gpt_init`'s output."""
    if cfg.num_experts:
        # Router stays expert-replicated (every token scores every expert);
        # expert weights shard on the leading E dim -> ep mesh axis.
        mlp = {
            "router": ("layers", "embed", None),
            "wi": ("layers", "expert", "embed", "mlp"),
            "bi": ("layers", "expert", "mlp"),
            "wo": ("layers", "expert", "mlp", "embed"),
            "bo": ("layers", "expert", "embed"),
        }
    else:
        mlp = {
            "wi": ("layers", "embed", "mlp"),
            "bi": ("layers", "mlp"),
            "wo": ("layers", "mlp", "embed"),
            "bo": ("layers", "norm"),
        }
    return {
        # wte sharded on embed (not vocab): token lookup is a gather, and a
        # vocab-sharded gather forces SPMD full rematerialization; the tied
        # LM head contracts over embed so fsdp-sharding it is free (psum).
        "wte": (None, "embed"),
        "wpe": (None, "embed"),
        "layers": {
            "ln1": {"scale": ("layers", "norm"), "bias": ("layers", "norm")},
            "attn": {
                "wqkv": ("layers", "embed", None, "heads", "kv"),
                "wo": ("layers", "heads", "kv", "embed"),
                "bo": ("layers", "norm"),
            },
            "ln2": {"scale": ("layers", "norm"), "bias": ("layers", "norm")},
            "mlp": mlp,
        },
        "ln_f": {"scale": ("norm",), "bias": ("norm",)},
    }


from jax.ad_checkpoint import checkpoint_name as _checkpoint_name


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _tp_mesh(rules: Optional[LogicalAxisRules]):
    """The current mesh where it splits a layer over ``tp`` and the caller
    shards by ``rules``, else None: what decides the block's form."""
    if rules is None:
        return None
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if tp_size(mesh) > 1 else None


def _block(cfg: GPTConfig, rules: Optional[LogicalAxisRules],
           attn_fn: Callable, x, layer_params, moe_ep_axis=None):
    """One transformer block. `layer_params` has the [L] dim already sliced.

    Returns (x, aux) — aux is the MoE load-balance loss for this layer
    (0.0 for a dense FFN) so the scan over layers can accumulate it.
    ``moe_ep_axis`` switches the MoE to its shard_map expert-parallel mode
    (weights pre-sharded on the expert dim; see ops/moe.py).

    Where the current mesh has ``tp > 1`` the residual stream ``x`` comes
    and goes sharded along the sequence over ("sp", "tp") (``res_seq``):
    the norms, bias adds and residual adds work on a device's own rows, each
    pair's first product gathers the rows of tp and its last scatters the
    partial sums, in chunks that travel while the products run
    (``parallel/collectives.py``).  With no ``tp`` the same einsums stand
    alone and the compiler shards them.
    """
    lc = (lambda a, ax: with_logical_constraint(a, rules, ax)) if rules \
        else (lambda a, ax: a)
    ring = _tp_mesh(rules)
    if ring is None:
        first = last = lambda eq, a, w, shard, by_step=False: \
            jnp.einsum(eq, a, w)
        plus = operator.add
        res = ("batch", "seq", "embed")
    else:
        first = functools.partial(gathered_einsum, mesh=ring)
        last = functools.partial(scattered_einsum, mesh=ring)
        plus = add_bias_first
        res = ("batch", "res_seq", "embed")
    p = layer_params
    dt = cfg.dtype

    h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
    if getattr(attn_fn, "_layout", "bsnh") == "bnsh":
        # Head-major attention path: the qkv projection WRITES [B,N,S,H]
        # (layout picked in the matmul epilogue, nearly free) so the flash
        # kernels get their native view with zero standalone relayouts.
        qkv = first("bsd,dcnh->bcnsh", h, p["attn"]["wqkv"].astype(dt), "n")
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        q = lc(q, ("batch", "heads", "seq", "kv"))
        k = lc(k, ("batch", "heads", "seq", "kv"))
        v = lc(v, ("batch", "heads", "seq", "kv"))
        if ring is not None:
            # kept for the backward as the kernel reads them (gpt_hidden)
            q, k, v = (_checkpoint_name(a, "attn_in") for a in (q, k, v))
        o = _checkpoint_name(attn_fn(q, k, v), "attn_out")
        o = last("bnsh,nhd->bsd", o, p["attn"]["wo"].astype(dt), "n")
    else:
        qkv = first("bsd,dcnh->bscnh", h, p["attn"]["wqkv"].astype(dt), "n")
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = lc(q, ("batch", "seq", "heads", "kv"))
        k = lc(k, ("batch", "seq", "heads", "kv"))
        v = lc(v, ("batch", "seq", "heads", "kv"))
        o = _checkpoint_name(attn_fn(q, k, v), "attn_out")
        o = last("bsnh,nhd->bsd", o, p["attn"]["wo"].astype(dt), "n")
    x = plus(x + o, p["attn"]["bo"].astype(dt))
    x = lc(x, res)

    h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
    if cfg.num_experts:
        from ray_tpu.ops.moe import moe_mlp
        h, aux = moe_mlp(h, p["mlp"], top_k=cfg.expert_top_k,
                         capacity_factor=cfg.capacity_factor, lc=lc,
                         ep_axis=moe_ep_axis)
    else:
        aux = jnp.zeros((), jnp.float32)
        # row by row from here to the last product: under the ring the
        # rows stay as they came, one array a step
        h = first("bsd,dm->bsm", h, p["mlp"]["wi"].astype(dt), "m",
                  by_step=True)
        h = jax.tree.map(lambda rows: jax.nn.gelu(lc(
            rows + p["mlp"]["bi"].astype(dt), ("batch", "seq", "mlp"))), h)
        h = plus(last("bsm,md->bsd", h, p["mlp"]["wo"].astype(dt), "m",
                      by_step=True), p["mlp"]["bo"].astype(dt))
    x = x + h
    return lc(x, res), aux


def gpt_hidden(params: Dict[str, Any], tokens: jax.Array,
               cfg: GPTConfig,
               rules: Optional[LogicalAxisRules] = None,
               mesh=None) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] int32 -> (final hidden [B, S, D] after ln_f in compute
    dtype, moe_aux_loss scalar) — the trunk without the LM head, so the
    blocked-CE loss can apply head+loss per sequence chunk.

    Layers run under one `lax.scan` over the stacked [L] params — XLA sees a
    single while-loop body (fast compiles, and the [L] dim shards over pp).
    With ``cfg.attention == "ring"`` and a mesh, attention runs as ring
    attention shard_mapped over the `sp` axis (KV rotating via ppermute).
    The residual stream the scan carries is [B, S, D] with the batch over
    (dp, fsdp) and the sequence over sp; where the current mesh has
    ``tp > 1`` its sequence lies over (sp, tp) from the embedding to the
    final norm (``_block`` says why), and is whole again for the head.
    """
    dt = cfg.dtype
    S = tokens.shape[1]
    attention = resolve_attention(cfg.attention, S)
    if attention == "ring" and mesh is not None:
        from jax.sharding import PartitionSpec as P
        from ray_tpu.ops.ring_attention import ring_attention_sharded
        spec = P(("dp", "fsdp"), "sp", "tp", None)
        attn_fn = jax.shard_map(
            functools.partial(ring_attention_sharded, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
    elif attention == "flash":
        attn_fn = _flash_attention_bnsh(rules, mesh)
    else:
        attn_fn = _dense_causal_attention_bnsh

    x = params["wte"].astype(dt)[tokens] \
        + params["wpe"].astype(dt)[:S][None]
    sharded_rows = _tp_mesh(rules) is not None
    if rules is not None:
        x = with_logical_constraint(x, rules, ("batch", "seq", "embed"))
    if sharded_rows:
        # after the lookup's own constraint: the tables' gradients keep
        # the tables' layout (a step's results lie as its arguments do)
        x = with_logical_constraint(x, rules, ("batch", "res_seq", "embed"))

    block = functools.partial(_block, cfg, rules, attn_fn)
    if cfg.remat:
        cp = jax.checkpoint_policies
        if cfg.remat_policy == "dots":
            policy = cp.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "attn":
            # Save the attention outputs (tagged via checkpoint_name in
            # _block): the backward pass recomputes the cheap projections
            # and MLP but never re-runs the attention kernel — the single
            # most expensive recompute under "full"/"dots" when attention
            # is the Pallas flash kernel.
            policy = cp.save_only_these_names("attn_out")
        elif cfg.remat_policy == "attn_dots":
            policy = cp.save_from_both_policies(
                cp.dots_with_no_batch_dims_saveable,
                cp.save_only_these_names("attn_out"))
        else:
            policy = None
        if sharded_rows and policy is not None:
            # what the kernel reads, in its layout, in place of the
            # chunks' products it was put in order from: the same bytes,
            # and the rematerialised forward puts nothing in order again
            policy = cp.save_from_both_policies(
                policy, cp.save_only_these_names("attn_in"))
        block = jax.checkpoint(block, policy=policy)

    def scan_body(carry, layer_params):
        return block(carry, layer_params)

    x, aux = jax.lax.scan(scan_body, x, params["layers"])
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if sharded_rows:
        # the head takes whole sequences, as it does with no tp
        x = with_logical_constraint(x, rules, ("batch", "seq", "embed"))
    return x, jnp.sum(aux)


def gpt_forward_with_aux(params: Dict[str, Any], tokens: jax.Array,
                         cfg: GPTConfig,
                         rules: Optional[LogicalAxisRules] = None,
                         mesh=None,
                         keep_dtype: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] int32 -> (logits [B, S, V] f32, moe_aux_loss scalar)."""
    x, aux = gpt_hidden(params, tokens, cfg, rules, mesh)
    logits = jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(cfg.dtype))
    # keep_dtype avoids materializing [B,S,V] in f32 (6.6GB of HBM traffic
    # at bench scale) — the fused loss upcasts inside its reductions.
    if not keep_dtype:
        logits = logits.astype(jnp.float32)
    return logits, aux


def gpt_forward(params: Dict[str, Any], tokens: jax.Array, cfg: GPTConfig,
                rules: Optional[LogicalAxisRules] = None,
                mesh=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V] (f32); see
    `gpt_forward_with_aux` for the MoE aux-loss variant."""
    logits, _ = gpt_forward_with_aux(params, tokens, cfg, rules, mesh)
    return logits


# --------------------------------------------------------- paged decode
#
# Serving path (ray_tpu.serve.engine): decode reads K/V from the paged
# pools of ops/paged_attention.py instead of re-running the prefix, so
# one replica steps MANY sequences per forward at O(1) compute per
# token.  The math mirrors _block's head-major branch exactly — with
# cfg.dtype=float32 the paged greedy decode reproduces gpt_forward's
# token-by-token argmax bit-for-bit, which the CPU equivalence tests
# assert.


def init_paged_cache(cfg: GPTConfig, num_pages: int, page_size: int,
                     dtype: Any = None, slots: int = 0
                     ) -> Tuple[jax.Array, jax.Array]:
    """Zeroed K/V page pools of all layers, [L, P, page, N*H]
    (token-major, matching ops.paged_attention's layouts).  Page 0 is the
    scratch sink for padded/inactive writes — allocators must never hand
    it out.  ``slots`` (the engine's decode slots) sizes nothing here: this
    model keeps no row a slot."""
    dt = dtype or cfg.dtype
    shape = (cfg.num_layers, num_pages, page_size,
             cfg.num_heads * cfg.head_dim)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def _cast_leaves(tree: Dict[str, Any], dtype, *names: str) -> Dict[str, Any]:
    """``tree`` with the leaves ``names`` as ``dtype``, the rest as they are."""
    return {**tree, **{n: tree[n].astype(dtype) for n in names}}


def gpt_serving_params(params: Dict[str, Any],
                       cfg: GPTConfig) -> Dict[str, Any]:
    """``params`` with every leaf in the dtype ``gpt_prefill`` and
    ``gpt_decode_step`` read it in, for a caller that keeps the tree between
    calls: the leaves those two cast with ``.astype(cfg.dtype)`` (both
    embedding tables, every projection and its bias) are cast here, once,
    and the casts in the steps then cost nothing (``astype`` to an array's
    own dtype returns the array).  The layer norms' scales and biases are
    read in f32 and handed back as the caller's own arrays.  Casting twice
    is casting once, so the steps return the same bits for this tree as for
    ``params``."""
    dt, layers = cfg.dtype, params["layers"]
    return {**_cast_leaves(params, dt, "wte", "wpe"),
            "layers": {**layers,
                       "attn": _cast_leaves(layers["attn"], dt,
                                            "wqkv", "wo", "bo"),
                       "mlp": _cast_leaves(layers["mlp"], dt,
                                           "wi", "bi", "wo", "bo")}}


def gpt_prefill(params: Dict[str, Any], cfg: GPTConfig, tokens: jax.Array,
                length: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                page_table: jax.Array, slot: jax.Array = 0
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill ONE padded sequence: run the trunk densely, scatter every
    layer's K/V into the sequence's pages, and return the next-token
    logits at the last real position.

    ``tokens`` [1, S] (S a multiple of the page size, S <= max_seq_len),
    ``length`` scalar int32 true length, ``page_table`` [1, maxp];
    ``k_pages``/``v_pages`` [L, P, page, N*H], carried through the layer
    scan and written in place.  Padding positions write
    to scratch page 0 (see ops.paged_attention.prefill_kv) and, being
    causal, never influence positions < length.  ``slot`` (the decode slot
    the sequence will be stepped in) is for a model that keeps a row a
    slot; this one takes no notice of it.  Returns
    (logits [1, V] f32, k_pages, v_pages)."""
    from ray_tpu.ops.paged_attention import prefill_kv
    dt = cfg.dtype
    B, S = tokens.shape
    x = params["wte"].astype(dt)[tokens] \
        + params["wpe"].astype(dt)[:S][None]

    def body(carry, inp):
        (x, kp, vp), (p, layer) = carry, inp
        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        qkv = jnp.einsum("bsd,dcnh->bcnsh", h, p["attn"]["wqkv"].astype(dt))
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]        # [B, N, S, H]
        kp, vp = prefill_kv(kp, vp, layer, k[0], v[0], length,
                            page_table[0])
        o = _dense_causal_attention_bnsh(q, k, v)
        o = jnp.einsum("bnsh,nhd->bsd", o, p["attn"]["wo"].astype(dt))
        x = x + o + p["attn"]["bo"].astype(dt)
        h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
        h = jnp.einsum("bsd,dm->bsm", h, p["mlp"]["wi"].astype(dt)) \
            + p["mlp"]["bi"].astype(dt)
        h = jax.nn.gelu(h)
        h = jnp.einsum("bsm,md->bsd", h, p["mlp"]["wo"].astype(dt)) \
            + p["mlp"]["bo"].astype(dt)
        return (x + h, kp, vp), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, k_pages, v_pages),
        (params["layers"], jnp.arange(cfg.num_layers)))
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    last = x[0, length - 1]                              # [D]
    logits = jnp.einsum("d,vd->v", last,
                        params["wte"].astype(dt)).astype(jnp.float32)
    return logits[None], k_pages, v_pages


def gpt_decode_step(params: Dict[str, Any], cfg: GPTConfig,
                    token: jax.Array, pos: jax.Array, k_pages: jax.Array,
                    v_pages: jax.Array, page_table: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for a BATCH of sequences against the paged cache.

    ``token`` [B] int32 current tokens, ``pos`` [B] their positions,
    ``page_table`` [B, maxp].  Writes each token's K/V at ``pos`` then
    attends positions [0, pos] through the page tables — sequences of
    different lengths batch freely, and inactive slots (pos 0, all-zero
    page-table row) harmlessly churn scratch page 0.  Returns
    (next-token logits [B, V] f32, k_pages, v_pages)."""
    from ray_tpu.ops.paged_attention import append_kv, paged_attention
    dt = cfg.dtype
    x = params["wte"].astype(dt)[token] + params["wpe"].astype(dt)[pos]

    def body(carry, inp):
        (x, kp, vp), (p, layer) = carry, inp
        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        qkv = jnp.einsum("bd,dcnh->bcnh", h, p["attn"]["wqkv"].astype(dt))
        q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]   # [B, N, H]
        kp, vp = append_kv(kp, vp, layer, k_new, v_new, pos, page_table)
        o = paged_attention(q, kp, vp, layer, pos + 1, page_table)
        o = jnp.einsum("bnh,nhd->bd", o, p["attn"]["wo"].astype(dt))
        x = x + o + p["attn"]["bo"].astype(dt)
        h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
        h = jnp.einsum("bd,dm->bm", h, p["mlp"]["wi"].astype(dt)) \
            + p["mlp"]["bi"].astype(dt)
        h = jax.nn.gelu(h)
        h = jnp.einsum("bm,md->bd", h, p["mlp"]["wo"].astype(dt)) \
            + p["mlp"]["bo"].astype(dt)
        return (x + h, kp, vp), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, k_pages, v_pages),
        (params["layers"], jnp.arange(cfg.num_layers)))
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = jnp.einsum("bd,vd->bv", x,
                        params["wte"].astype(dt)).astype(jnp.float32)
    return logits, k_pages, v_pages


def gpt_paged_read(cfg: GPTConfig, k_pages) -> str:
    """What ``gpt_decode_step`` reads the pages with, "kernel" or "gather"
    (``ops/paged_attention.py::paged_read_kind`` of its queries and the
    pool; GPT-2's EQUAL heads of 64 are no whole lane tile and share no K/V head: the
    gather)."""
    from ray_tpu.ops.paged_attention import paged_read_kind
    return paged_read_kind(jax.ShapeDtypeStruct(
        (1, cfg.num_heads, cfg.head_dim), cfg.dtype), k_pages)


def served(config: Optional[GPTConfig] = None, seq: int = 0):
    """The serving engine's record of this model (``models/serving.py``)."""
    from ray_tpu.models.serving import ServedModel, greedy
    cfg = config or GPTConfig.tiny(seq=seq)
    return ServedModel(
        config=cfg, init=gpt_init, stored=gpt_serving_params,
        new_pools=functools.partial(init_paged_cache, cfg),
        prefill=gpt_prefill, step=gpt_decode_step,
        prefill_attention=lambda cfg, rung, start=False: "dense",
        paged_read=gpt_paged_read, block=0, feed=greedy)


def gpt_loss(params, batch: Dict[str, jax.Array], cfg: GPTConfig,
             rules: Optional[LogicalAxisRules] = None, mesh=None,
             forward_fn: Optional[Callable] = None) -> jax.Array:
    """Next-token cross-entropy. batch: {"tokens": [B, S+1] int32}.

    `forward_fn(params, tokens) -> logits` overrides the forward pass (the
    pipelined variant in `ray_tpu.models.gpt_pipeline` plugs in here).  The
    blocked head (``cfg.ce_block``) applies only to the default forward —
    the pipelined path has its own per-microbatch drain that already bounds
    logits memory to one microbatch."""
    toks = batch["tokens"]
    targets = toks[:, 1:]
    if forward_fn is not None:
        logits = forward_fn(params, toks[:, :-1])
        return -jnp.mean(token_loglikes(logits, targets))
    x, aux = gpt_hidden(params, toks[:, :-1], cfg, rules, mesh)
    ll = ce_head_loglike_sum(x, params["wte"].astype(cfg.dtype), targets,
                             cfg.ce_block, "vd")
    return -ll / targets.size + cfg.moe_aux_coef * aux


def ce_head_loglike_sum(x: jax.Array, head: jax.Array, targets: jax.Array,
                        block: int, head_layout: str) -> jax.Array:
    """Sum of next-token loglikes from the final hidden states ``x``
    [B, S, D]: the head product and the cross-entropy, blocked over the
    sequence where ``block`` is set.  The ``ce_head`` scope names these
    operations, forward and backward, in a profile.  The plain form keeps
    its logits in the compute dtype: the fused loss upcasts inside its
    reductions (see gpt_forward_with_aux)."""
    with jax.named_scope("ce_head"):
        if block:
            return blocked_ce_loglike_sum(x, head, targets, block,
                                          head_layout)
        eq = "bsd,vd->bsv" if head_layout == "vd" else "bsd,dv->bsv"
        return jnp.sum(token_loglikes(jnp.einsum(eq, x, head), targets))


def blocked_ce_loglike_sum(x: jax.Array, head: jax.Array,
                           targets: jax.Array, block: int,
                           head_layout: str = "vd") -> jax.Array:
    """Sum of next-token loglikes with head matmul + CE fused per sequence
    chunk: a `lax.scan` over S/block chunks whose body (chunk logits ->
    chunk loglike sum) runs under `jax.checkpoint`, so neither forward nor
    backward ever holds a [B, S, V] tensor — the live set is one
    [B, block, V] chunk.  Backward recomputes each chunk's logits (one
    extra head matmul, ~+8% head FLOPs) and accumulates d(head) across
    chunks via the scan-constant gradient path.

    Design analog: the reference materializes full logits and calls
    torch F.cross_entropy (python/ray/train examples); on TPU the fused
    blocked head converts ~6.6 GB of [B,S,V] HBM traffic into MXU-resident
    chunks.  ``head_layout``: "vd" ([V, D], tied GPT embedding) or "dv".
    """
    B, S, D = x.shape
    if S % block or S == block:
        # Non-dividing block: one full-sequence chunk under checkpoint
        # would cost the recompute with zero memory benefit — use the
        # plain fused loss instead.  That silently materializes the full
        # [B, S, V] logits the caller configured ce_block to avoid, so
        # say it loudly (this branch runs at trace time, once per shape)
        # — or refuse outright under RT_STRICT_CE_BLOCK=1.
        import os
        msg = (f"ce_block={block} does not evenly split sequence length "
               f"S={S} into multiple chunks; falling back to full "
               f"[B={B}, S={S}, V] logits — the blocked head's memory "
               f"win is LOST. Pick ce_block so that S % ce_block == 0 "
               f"and ce_block < S.")
        if os.environ.get("RT_STRICT_CE_BLOCK") == "1":
            raise ValueError(msg)
        import warnings
        warnings.warn(msg, RuntimeWarning, stacklevel=2)
        full_eq = "bsd,vd->bsv" if head_layout == "vd" else "bsd,dv->bsv"
        return jnp.sum(token_loglikes(jnp.einsum(full_eq, x, head),
                                      targets))
    nb = S // block
    eq = "bcd,vd->bcv" if head_layout == "vd" else "bcd,dv->bcv"

    @jax.checkpoint
    def chunk_ll(xc, tc):
        logits = jnp.einsum(eq, xc, head)
        return jnp.sum(token_loglikes(logits, tc))

    xb = jnp.moveaxis(x.reshape(B, nb, block, D), 1, 0)
    tb = jnp.moveaxis(targets.reshape(B, nb, block), 1, 0)
    total, _ = jax.lax.scan(
        lambda acc, args: (acc + chunk_ll(*args), None),
        jnp.zeros((), jnp.float32), (xb, tb))
    return total


def token_loglikes(logits, targets) -> jax.Array:
    """Fused cross-entropy core: ll_i = logit[target_i] - logsumexp_i.

    Written so XLA fuses the f32 upcast into the reductions and never
    materializes an f32 [..., V] tensor; shared by the standard and the
    pipelined (per-microbatch drain) loss paths.  Returns f32 [...]."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    z = (logits - m).astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1)) + m[..., 0].astype(
        jnp.float32)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return tgt.astype(jnp.float32) - lse


# ---------------------------------------------------------------- train step

def make_train_state(rng, cfg: GPTConfig, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
    """(params, opt_state, optimizer) with AdamW."""
    import optax
    params = gpt_init(rng, cfg)
    tx = optax.adamw(learning_rate, b1=0.9, b2=0.95,
                     weight_decay=weight_decay)
    return params, tx.init(params), tx


def make_train_step(cfg: GPTConfig, tx,
                    rules: Optional[LogicalAxisRules] = None,
                    mesh=None, donate: bool = True,
                    forward_fn: Optional[Callable] = None,
                    loss_fn: Optional[Callable] = None):
    """Returns jittable (params, opt_state, batch) -> (params, opt_state,
    metrics).  Under a Mesh + sharded inputs, XLA emits the collectives
    (gradient reduction across dp/fsdp, the gathers of fsdp's weights, sp's
    and the head's) — the TPU equivalent of the reference's DDP allreduce
    hook — but for tp's activation sums, which ``_block`` writes out as
    chunks that travel beside the projections (parallel/collectives.py).

    ``loss_fn(params, batch) -> scalar`` overrides the whole loss (the
    pipelined trainer plugs its fused-epilogue loss in here), so the
    optimizer/metric plumbing lives in exactly one place."""
    if loss_fn is None:
        def loss_fn(params, batch):
            return gpt_loss(params, batch, cfg, rules, mesh, forward_fn)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        import optax
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())
