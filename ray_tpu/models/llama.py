"""LLaMA-family decoder-only transformer, TPU-first.

Second flagship model family beside GPT-2 (``models/gpt.py``): RMSNorm
pre-norm, rotary position embeddings (no learned positions), SwiGLU MLP,
untied LM head, and grouped-query attention (kv_heads <= heads).  Same
TPU-first construction as GPT: bf16 compute / f32 params, layers stacked
on a scanned [L, ...] dim (single XLA while-loop; the dim doubles as the
pp shard axis), logical-axis annotations on every param so one definition
runs dp/fsdp/tp/sp via the ``ray_tpu.parallel`` rule tables, per-layer
``jax.checkpoint`` with the same policy menu as GPT, and the same
pluggable attention body (dense / Pallas flash).

The reference has no model zoo of its own (its flagship benchmarks wrap
torchvision/HF models); this family exists so Train/Tune/Serve have a
modern-architecture model to exercise, matching
``release/air_tests/air_benchmarks``' role.

With ``num_experts > 0`` the feed-forward is a mixture of SwiGLU experts
routed top-k per token without capacity (``ops/moe.py``'s dropless path),
and with ``qk_norm`` the q and k projections are RMS-normed over all heads
before the rotation: together OLMoE's block.  Both are written once
(``_ffn``, ``_qk``) and called from the training block, the paged prefill
and the paged decode.  The expert model serves; it does not train here:
``llama_loss`` refuses it, because the load-balancing loss, ``ep``
sharding and the all-to-all are not written.

With ``ut_steps > 1`` the whole layer stack is run that many times over the
same weights (Ouro's looped model): pass ``t`` starts from the last pass's
output after the model's final norm, and when served keeps a cache of its
own, so the pools hold ``ut_steps * num_layers`` layers and pass ``t``,
layer ``l`` reads and writes pool layer ``t * num_layers + l``.  With
``post_norm`` a sublayer's output is RMS-normed (``ln1_post``, ``ln2_post``)
before it is added to the residual stream.  Both are written once
(``_add_sublayer``, ``_passes``) and called from the training trunk, the
paged prefill and the paged decode; with ``ut_steps == 1`` and no
``post_norm`` neither adds an operation.  The looped model serves; it does
not train here: ``llama_loss`` refuses it, because its published objective
(an entropy-regularised expectation over exit steps) is not written, and
neither are the exit gate's two leaves: at the published threshold of 1
the gate never exits early.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from ray_tpu.models.gpt import (_cast_leaves, ce_head_loglike_sum,
                                resolve_attention)
from ray_tpu.parallel.sharding import (LogicalAxisRules,
                                       with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 4            # GQA: kv_heads < heads shares K/V
    embed_dim: int = 768
    mlp_dim: int = 2048              # SwiGLU hidden (~8/3 * embed, /128 pad)
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"   # same menu as GPTConfig
    attention: str = "auto"          # "auto" | "dense" | "flash"
    ce_block: int = 0                # blocked-CE chunk (see GPTConfig)
    num_experts: int = 0             # 0 = dense; else mlp_dim is an expert's
    experts_per_token: int = 0       # top-k of the router's softmax
    norm_topk_prob: bool = False     # renormalise the k gates to sum to 1
    qk_norm: bool = False            # RMSNorm q and k over all heads
    ut_steps: int = 1                # passes over the layer stack
    post_norm: bool = False          # RMSNorm a sublayer's output too

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @staticmethod
    def llama_125m() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(vocab: int = 256, seq: int = 128) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab, max_seq_len=seq, num_layers=2,
                           num_heads=4, num_kv_heads=2, embed_dim=64,
                           mlp_dim=192)


def llama_init(rng: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Params with per-layer weights stacked on a leading [L] dim."""
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(f"num_heads={cfg.num_heads} must be divisible by "
                         f"num_kv_heads={cfg.num_kv_heads}")
    k = jax.random.split(rng, 8)
    D, H, M, L, V = (cfg.embed_dim, cfg.head_dim, cfg.mlp_dim,
                     cfg.num_layers, cfg.vocab_size)
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    scale = 0.02
    rscale = scale / np.sqrt(2 * L)
    E = cfg.num_experts
    if E and not 0 < cfg.experts_per_token <= E:
        raise ValueError(f"experts_per_token={cfg.experts_per_token} must "
                         f"be in 1..num_experts={E}")
    ex = (E,) if E else ()           # the experts' leading dim
    # SwiGLU: gate and up projections fused on a leading 2-dim.
    mlp = {"wgu": scale * jax.random.normal(k[4], (L, *ex, 2, D, M),
                                            jnp.float32),
           "wd": rscale * jax.random.normal(k[5], (L, *ex, M, D),
                                            jnp.float32)}
    if E:
        mlp["router"] = scale * jax.random.normal(k[7], (L, D, E),
                                                  jnp.float32)
    norms = {"q_norm": jnp.ones((L, nh, H), jnp.float32),
             "k_norm": jnp.ones((L, nkv, H), jnp.float32)} \
        if cfg.qk_norm else {}
    post = {name: {"scale": jnp.ones((L, D), jnp.float32)}
            for name in ("ln1_post", "ln2_post")} if cfg.post_norm else {}
    return {
        "wte": scale * jax.random.normal(k[0], (V, D), jnp.float32),
        "layers": {
            "ln1": {"scale": jnp.ones((L, D), jnp.float32)},
            "attn": {
                "wq": scale * jax.random.normal(k[1], (L, D, nh, H),
                                                jnp.float32),
                "wkv": scale * jax.random.normal(k[2], (L, D, 2, nkv, H),
                                                 jnp.float32),
                "wo": rscale * jax.random.normal(k[3], (L, nh, H, D),
                                                 jnp.float32),
                **norms,
            },
            "ln2": {"scale": jnp.ones((L, D), jnp.float32)},
            "mlp": mlp,
            **post,
        },
        "ln_f": {"scale": jnp.ones((D,), jnp.float32)},
        "lm_head": scale * jax.random.normal(k[6], (D, V), jnp.float32),
    }


def llama_param_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Logical-axis annotations matching ``llama_init`` (same rule table
    as GPT: heads/mlp -> tp, embed -> fsdp, layers -> pp; experts carry
    "expert" -> ep, the router stays replicated over them)."""
    ex = ("expert",) if cfg.num_experts else ()
    mlp = {"wgu": ("layers", *ex, None, "embed", "mlp"),
           "wd": ("layers", *ex, "mlp", "embed")}
    if cfg.num_experts:
        mlp["router"] = ("layers", "embed", None)
    norms = {"q_norm": ("layers", "heads", "kv"),
             "k_norm": ("layers", "heads", "kv")} if cfg.qk_norm else {}
    post = {name: {"scale": ("layers", "norm")}
            for name in ("ln1_post", "ln2_post")} if cfg.post_norm else {}
    return {
        "wte": (None, "embed"),
        "layers": {
            "ln1": {"scale": ("layers", "norm")},
            "attn": {
                "wq": ("layers", "embed", "heads", "kv"),
                "wkv": ("layers", "embed", None, "heads", "kv"),
                "wo": ("layers", "heads", "kv", "embed"),
                **norms,
            },
            "ln2": {"scale": ("layers", "norm")},
            "mlp": mlp,
            **post,
        },
        "ln_f": {"scale": ("norm",)},
        "lm_head": ("embed", None),
    }


def _rms_norm(x, scale, eps, axis=-1):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=axis, keepdims=True)
                            + eps)
    return (y * scale).astype(x.dtype)


def rope_tables(S: int, H: int, theta: float) -> tuple:
    """(cos, sin) [S, H/2] f32 tables for rotary embeddings."""
    inv_freq = 1.0 / theta ** (np.arange(0, H, 2, dtype=np.float32) / H)
    t = np.arange(S, dtype=np.float32)
    freqs = np.outer(t, inv_freq)
    return jnp.asarray(np.cos(freqs)), jnp.asarray(np.sin(freqs))


def apply_rope(x, cos, sin):
    """Rotate [..., S, H] pairs (x split halves convention, like LLaMA's
    reshape-free implementations).  cos/sin broadcast over leading dims."""
    H = x.shape[-1]
    x1, x2 = x[..., : H // 2], x[..., H // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


def _dense_causal_attention_gqa(q, k, v, rep: int):
    """Head-major grouped-query dense attention: q [B, N, S, H] with
    N = G*rep query heads sharing k/v [B, G, S, H].  Scores/output keep
    the (group, rep) split so K/V never replicate in memory."""
    import numpy as _np
    B, N, S, H = q.shape
    G = N // rep
    qg = q.reshape(B, G, rep, S, H)
    scores = jnp.einsum("bgrqh,bgkh->bgrqk", qg, k) / _np.sqrt(H)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask[None, None, None],
                       scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrqk,bgkh->bgrqh", probs, v)
    return o.reshape(B, N, S, H)


def _qk(cfg: LlamaConfig, p, q, k, cos, sin):
    """What happens to the head-major q [B, N, ..., H] and k [B, NKV, ...,
    H] between their projections and attention: with ``cfg.qk_norm`` an
    RMSNorm with a learned scale over the WHOLE projection, all heads
    together (OLMoE norms before it splits into heads), then the rotation
    at ``cos``/``sin``'s positions."""
    if cfg.qk_norm:
        def norm(a, scale):          # scale [N, H], a's heads on axis 1
            scale = scale.reshape(scale.shape[0], *(1,) * (a.ndim - 3), -1)
            return _rms_norm(a, scale, cfg.rms_eps, axis=(1, -1))
        q = norm(q, p["attn"]["q_norm"])
        k = norm(k, p["attn"]["k_norm"])
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


def _add_sublayer(cfg: LlamaConfig, p, post: str, x, y):
    """The residual stream ``x`` plus a sublayer's output ``y``: as it is,
    or with ``cfg.post_norm`` RMS-normed first by the layer's ``post``
    scale (``ln1_post`` after attention, ``ln2_post`` after the
    feed-forward): the sandwich norm of a looped model, which keeps a
    stream that runs the stack several times from growing."""
    if cfg.post_norm:
        with jax.named_scope("loop_norm"):
            y = _rms_norm(y, p[post]["scale"], cfg.rms_eps)
    return x + y


def _passes(cfg: LlamaConfig, params, layers_pass, carry):
    """``cfg.ut_steps`` passes over all layers, each followed by the
    model's final norm, which is what the next pass starts from.
    ``layers_pass(carry, t) -> (carry, ys)`` scans the layers once;
    ``carry`` is a tuple that starts with the stream ``x`` (a served
    model's pools follow it and are carried through both loops, so that
    they are still updated in place); ``t`` is the pass, None where there
    is one pass only and nothing is wrapped.  Returns the last carry and
    every pass's ``ys`` along one leading [ut_steps * num_layers] axis."""
    def final_norm(x):
        return _rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)

    if cfg.ut_steps == 1:
        (x, *rest), ys = layers_pass(carry, None)
        return (final_norm(x), *rest), ys

    def one_pass(carry, t):
        (x, *rest), ys = layers_pass(carry, t)
        with jax.named_scope("loop_norm"):
            x = final_norm(x)
        return (x, *rest), ys

    carry, ys = jax.lax.scan(one_pass, carry, jnp.arange(cfg.ut_steps))
    return carry, jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)


def _pool_layers(cfg: LlamaConfig, t):
    """The pool layers that pass ``t`` (None: the only one) reads and
    writes, by layer: ``t * num_layers + l``."""
    layers = jnp.arange(cfg.num_layers)
    return layers if t is None else t * cfg.num_layers + layers


def _scanned_layers(cfg: LlamaConfig, params):
    """(what ``lax.scan`` slices a layer at a time, what the layers share).
    A dense model's layers are all sliced.  An expert model's experts stay
    whole and the layer gets its index in their place: a layer's slice of
    the stack is a copy of every expert (1.6 GB at OLMoE's widths) ahead
    of grouped matmuls that read a few of them, where they lie."""
    layers = params["layers"]
    if not cfg.num_experts:
        return layers, None
    return {**layers, "mlp": jnp.arange(cfg.num_layers)}, layers["mlp"]


def _ffn(cfg: LlamaConfig, p, h, live=None, lc=lambda a, ax: a,
         experts=None):
    """The block's feed-forward on the normed hidden ``h`` [..., D]:
    SwiGLU, dense or (``cfg.num_experts``) top-k experts without capacity;
    then ``p["mlp"]`` is the layer's index into ``experts``, the stacked
    experts of all layers (``_scanned_layers``).  Returns (y [..., D],
    load): ``load`` [E] int32 counts per expert the assignments of the
    tokens that ``live`` [...] marks (all when None), and is None for a
    dense model."""
    dt = cfg.dtype
    if cfg.num_experts:
        from ray_tpu.ops.moe import moe_dropless
        y, load = moe_dropless(
            h.reshape(-1, h.shape[-1]), experts, layer=p["mlp"],
            top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
            live=None if live is None else live.reshape(-1))
        return y.reshape(h.shape), load
    gu = jnp.einsum("...d,cdm->c...m", h, p["mlp"]["wgu"].astype(dt))
    a = lc(jax.nn.silu(gu[0]) * gu[1], ("batch", "seq", "mlp"))
    return jnp.einsum("...m,md->...d", a, p["mlp"]["wd"].astype(dt)), None


def _block(cfg: LlamaConfig, rules: Optional[LogicalAxisRules],
           attn_fn: Callable, cos, sin, experts, x, p):
    lc = (lambda a, ax: with_logical_constraint(a, rules, ax)) if rules \
        else (lambda a, ax: a)
    dt = cfg.dtype
    rep = cfg.num_heads // cfg.num_kv_heads

    h = _rms_norm(x, p["ln1"]["scale"], cfg.rms_eps)
    # Head-major [B, N, S, H] throughout: native layout for the flash
    # kernels, picked in the projection epilogue for free.
    q = jnp.einsum("bsd,dnh->bnsh", h, p["attn"]["wq"].astype(dt))
    kv = jnp.einsum("bsd,dcnh->bcnsh", h, p["attn"]["wkv"].astype(dt))
    k, v = kv[:, 0], kv[:, 1]
    q, k = _qk(cfg, p, q, k, cos, sin)
    if rep > 1 and getattr(attn_fn, "_gqa_native", False):
        # Grouped dense path: fold the share-group dim into the einsum —
        # K/V stay at kv_heads width (no jnp.repeat materializing rep
        # copies of the KV tensors in HBM).
        o = _checkpoint_name(
            _dense_causal_attention_gqa(q, k, v, rep), "attn_out")
    else:
        if rep > 1:   # flash kernel expects equal head counts
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        q = lc(q, ("batch", "heads", "seq", "kv"))
        k = lc(k, ("batch", "heads", "seq", "kv"))
        v = lc(v, ("batch", "heads", "seq", "kv"))
        o = _checkpoint_name(attn_fn(q, k, v), "attn_out")
    x = _add_sublayer(cfg, p, "ln1_post", x, jnp.einsum(
        "bnsh,nhd->bsd", o, p["attn"]["wo"].astype(dt)))
    x = lc(x, ("batch", "seq", "embed"))

    h = _rms_norm(x, p["ln2"]["scale"], cfg.rms_eps)
    x = _add_sublayer(cfg, p, "ln2_post", x,
                      _ffn(cfg, p, h, lc=lc, experts=experts)[0])
    return lc(x, ("batch", "seq", "embed"))


def llama_hidden(params: Dict[str, Any], tokens: jax.Array,
                 cfg: LlamaConfig,
                 rules: Optional[LogicalAxisRules] = None,
                 mesh=None) -> jax.Array:
    """tokens [B, S] int32 -> final hidden [B, S, D] after rms_norm (compute
    dtype) — the trunk without the LM head (see gpt_hidden)."""
    dt = cfg.dtype
    S = tokens.shape[1]
    if resolve_attention(cfg.attention, S) == "flash":
        from ray_tpu.models.gpt import _flash_attention_bnsh
        attn_fn = _flash_attention_bnsh(rules, mesh)
    else:
        from ray_tpu.models.gpt import _dense_causal_attention_bnsh

        def attn_fn(q, k, v):
            return _dense_causal_attention_bnsh(q, k, v)
        attn_fn._gqa_native = True

    cos, sin = rope_tables(S, cfg.head_dim, cfg.rope_theta)
    x = params["wte"].astype(dt)[tokens]
    if rules is not None:
        x = with_logical_constraint(x, rules, ("batch", "seq", "embed"))

    layers, experts = _scanned_layers(cfg, params)
    block = functools.partial(_block, cfg, rules, attn_fn, cos, sin, experts)
    if cfg.remat:
        cp = jax.checkpoint_policies
        policy = {
            "dots": cp.dots_with_no_batch_dims_saveable,
            "attn": cp.save_only_these_names("attn_out"),
            "attn_dots": cp.save_from_both_policies(
                cp.dots_with_no_batch_dims_saveable,
                cp.save_only_these_names("attn_out")),
        }.get(cfg.remat_policy)
        block = jax.checkpoint(block, policy=policy)

    (x,), _ = _passes(cfg, params, lambda carry, t: jax.lax.scan(
        lambda c, lp: ((block(c[0], lp),), None), carry, layers), (x,))
    return x


def llama_forward(params: Dict[str, Any], tokens: jax.Array,
                  cfg: LlamaConfig,
                  rules: Optional[LogicalAxisRules] = None,
                  mesh=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V] (compute dtype; the fused
    loss upcasts inside its reductions, same contract as gpt_forward)."""
    x = llama_hidden(params, tokens, cfg, rules, mesh)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))


# ---------------------------------------------------------------------------
# Paged KV-cache decode (serving path) — LLaMA variant of gpt.py's
# init_paged_cache/gpt_prefill/gpt_decode_step.  GQA keeps the pools at
# kv_heads width (not heads), rope is applied at each token's
# absolute position before the K is scattered (the pools hold POST-rope
# keys, so decode attention is a plain dot against the cache), and the
# math mirrors _block's grouped dense branch exactly — with
# cfg.dtype=float32 paged greedy decode reproduces llama_forward's
# token-by-token argmax, which the CPU equivalence tests assert.


def llama_init_paged_cache(cfg: LlamaConfig, num_pages: int,
                           page_size: int, dtype: Any = None):
    """Zeroed K/V page pools of all layers, [L, P, page, NKV*H]
    (token-major: see ops.paged_attention), ``L`` a layer for every pass
    of a looped model: ``ut_steps * num_layers``.  Page 0 is the scratch
    sink for padded/inactive writes — allocators must never hand it out."""
    dt = dtype or cfg.dtype
    shape = (cfg.ut_steps * cfg.num_layers, num_pages, page_size,
             cfg.num_kv_heads * cfg.head_dim)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def llama_serving_params(params: Dict[str, Any],
                         cfg: LlamaConfig) -> Dict[str, Any]:
    """``params`` with every leaf in the dtype ``llama_prefill`` and
    ``llama_decode_step`` read it in, for a caller that keeps the tree
    between calls: the leaves those two cast with ``.astype(cfg.dtype)``
    (embedding table, head, the attention projections, a dense model's
    feed-forward) are cast here, once, and the casts in the steps then cost
    nothing (``astype`` to an array's own dtype returns the array).  Every
    other leaf is handed back as the caller's own array: the norms' scales,
    the router and the stacked experts are read in f32 (``_rms_norm``;
    ``moe_dropless`` casts the few rows of activations to the experts'
    dtype, never the experts).  Casting twice is casting once, so the steps
    return the same bits for this tree as for ``params``."""
    dt, layers = cfg.dtype, params["layers"]
    mlp = layers["mlp"] if cfg.num_experts else \
        _cast_leaves(layers["mlp"], dt, "wgu", "wd")
    return {**_cast_leaves(params, dt, "wte", "lm_head"),
            "layers": {**layers, "mlp": mlp,
                       "attn": _cast_leaves(layers["attn"], dt,
                                            "wq", "wkv", "wo")}}


def _paged_results(logits, k_pages, v_pages, load):
    """(logits, pools) and, from an expert model, the experts' load."""
    if load is None:
        return logits, k_pages, v_pages
    return logits, k_pages, v_pages, load


def llama_prefill(params: Dict[str, Any], cfg: LlamaConfig,
                  tokens: jax.Array, length: jax.Array,
                  k_pages: jax.Array, v_pages: jax.Array,
                  page_table: jax.Array):
    """Prefill ONE padded sequence (see gpt_prefill): dense trunk,
    per-layer post-rope K/V scattered into the sequence's pages, f32
    next-token logits at position length-1.  ``tokens`` [1, S] with S a
    multiple of the page size; ``page_table`` [1, maxp];
    ``k_pages``/``v_pages`` [L, P, page, NKV*H], carried through the layer
    scan and written in place.  An expert model returns a
    fourth result, ``load`` [L, E] int32: per layer and expert, the
    assignments of the prompt's real positions."""
    from ray_tpu.ops.paged_attention import prefill_kv
    dt = cfg.dtype
    rep = cfg.num_heads // cfg.num_kv_heads
    S = tokens.shape[1]
    cos, sin = rope_tables(S, cfg.head_dim, cfg.rope_theta)
    x = params["wte"].astype(dt)[tokens]
    live = (jnp.arange(S) < length)[None]                # the real positions
    layers, experts = _scanned_layers(cfg, params)

    def body(carry, inp):
        (x, kp, vp), (p, layer) = carry, inp
        h = _rms_norm(x, p["ln1"]["scale"], cfg.rms_eps)
        q = jnp.einsum("bsd,dnh->bnsh", h, p["attn"]["wq"].astype(dt))
        kv = jnp.einsum("bsd,dcnh->bcnsh", h, p["attn"]["wkv"].astype(dt))
        k, v = kv[:, 0], kv[:, 1]                        # [B, NKV, S, H]
        q, k = _qk(cfg, p, q, k, cos, sin)
        kp, vp = prefill_kv(kp, vp, layer, k[0], v[0], length,
                            page_table[0])
        o = _dense_causal_attention_gqa(q, k, v, rep)
        x = _add_sublayer(cfg, p, "ln1_post", x, jnp.einsum(
            "bnsh,nhd->bsd", o, p["attn"]["wo"].astype(dt)))
        h = _rms_norm(x, p["ln2"]["scale"], cfg.rms_eps)
        y, load = _ffn(cfg, p, h, live, experts=experts)
        return (_add_sublayer(cfg, p, "ln2_post", x, y), kp, vp), load

    (x, k_pages, v_pages), load = _passes(
        cfg, params, lambda carry, t: jax.lax.scan(
            body, carry, (layers, _pool_layers(cfg, t))),
        (x, k_pages, v_pages))
    last = x[0, length - 1]                              # [D]
    logits = jnp.einsum("d,dv->v", last,
                        params["lm_head"].astype(dt)).astype(jnp.float32)
    return _paged_results(logits[None], k_pages, v_pages, load)


def llama_decode_step(params: Dict[str, Any], cfg: LlamaConfig,
                      token: jax.Array, pos: jax.Array,
                      k_pages: jax.Array, v_pages: jax.Array,
                      page_table: jax.Array):
    """One decode step for a BATCH of sequences (see gpt_decode_step).
    ``token``/``pos`` [B]; rope rotates q and the new K at each
    sequence's absolute position; the paged attention's GQA grouping
    keeps K/V at kv_heads width.  Inactive slots (pos 0, all-zero
    page-table row) harmlessly churn scratch page 0.  An expert model
    returns a fourth result, ``load`` [L, E] int32: per layer and expert,
    the assignments of the live slots (``pos > 0``: a sequence that decodes
    has a prompt behind it)."""
    from ray_tpu.ops.paged_attention import append_kv, paged_attention
    dt = cfg.dtype
    cos_t, sin_t = rope_tables(cfg.max_seq_len, cfg.head_dim,
                               cfg.rope_theta)
    cos, sin = cos_t[pos][:, None], sin_t[pos][:, None]  # [B, 1, H/2]
    x = params["wte"].astype(dt)[token]
    live = pos > 0
    layers, experts = _scanned_layers(cfg, params)

    def body(carry, inp):
        (x, kp, vp), (p, layer) = carry, inp
        h = _rms_norm(x, p["ln1"]["scale"], cfg.rms_eps)
        q = jnp.einsum("bd,dnh->bnh", h, p["attn"]["wq"].astype(dt))
        kv = jnp.einsum("bd,dcnh->bcnh", h, p["attn"]["wkv"].astype(dt))
        k_new, v_new = kv[:, 0], kv[:, 1]                # [B, NKV, H]
        q, k_new = _qk(cfg, p, q, k_new, cos, sin)
        kp, vp = append_kv(kp, vp, layer, k_new, v_new, pos, page_table)
        o = paged_attention(q, kp, vp, layer, pos + 1, page_table)
        x = _add_sublayer(cfg, p, "ln1_post", x, jnp.einsum(
            "bnh,nhd->bd", o, p["attn"]["wo"].astype(dt)))
        h = _rms_norm(x, p["ln2"]["scale"], cfg.rms_eps)
        y, load = _ffn(cfg, p, h, live, experts=experts)
        return (_add_sublayer(cfg, p, "ln2_post", x, y), kp, vp), load

    (x, k_pages, v_pages), load = _passes(
        cfg, params, lambda carry, t: jax.lax.scan(
            body, carry, (layers, _pool_layers(cfg, t))),
        (x, k_pages, v_pages))
    logits = jnp.einsum("bd,dv->bv", x,
                        params["lm_head"].astype(dt)).astype(jnp.float32)
    return _paged_results(logits, k_pages, v_pages, load)


def llama_loss(params, batch: Dict[str, jax.Array], cfg: LlamaConfig,
               rules: Optional[LogicalAxisRules] = None,
               mesh=None) -> jax.Array:
    """Next-token CE over {"tokens": [B, S+1]} — shares the fused
    ``token_loglikes`` core (and the blocked-CE head via ``cfg.ce_block``)
    with GPT.  Refuses an expert model: without the load-balancing loss
    (and ``ep`` sharding and the all-to-all) it would train a router that
    collapses; those belong with the four-chip training path.  Refuses a
    looped model too: next-token CE on the last pass alone is not the
    objective such a model is published with."""
    if cfg.ut_steps > 1:
        raise NotImplementedError(
            "models/llama.py serves its looped model (ut_steps > 1) but "
            "does not train it: the expectation over exit steps with its "
            "entropy term, and the exit gate it trains, are not written")
    if cfg.num_experts:
        raise NotImplementedError(
            "models/llama.py serves its expert model but does not train "
            "it: the router's load-balancing loss is not written")
    toks = batch["tokens"]
    targets = toks[:, 1:]
    x = llama_hidden(params, toks[:, :-1], cfg, rules, mesh)
    ll = ce_head_loglike_sum(x, params["lm_head"].astype(cfg.dtype),
                             targets, cfg.ce_block, "dv")
    return -ll / targets.size


def make_train_step(cfg: LlamaConfig, tx,
                    rules: Optional[LogicalAxisRules] = None,
                    mesh=None, donate: bool = True):
    """Jitted (params, opt_state, batch) -> (params, opt_state, metrics);
    delegates to the GPT train-step plumbing with this family's loss."""
    from ray_tpu.models import gpt as _gpt
    return _gpt.make_train_step(
        cfg, tx, rules, mesh, donate=donate,
        loss_fn=lambda p, b: llama_loss(p, b, cfg, rules, mesh))
