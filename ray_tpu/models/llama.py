"""LLaMA-family decoder-only transformer, TPU-first.

Second flagship model family beside GPT-2 (``models/gpt.py``): RMSNorm
pre-norm, rotary position embeddings (no learned positions), SwiGLU MLP,
an LM head of its own (``tie_embeddings``: the table's transpose, one leaf
fewer), and grouped-query attention (kv_heads <= heads).  Same
TPU-first construction as GPT: bf16 compute / f32 params, layers stacked
on a scanned [L, ...] dim (single XLA while-loop; the dim doubles as the
pp shard axis), logical-axis annotations on every param so one definition
runs dp/fsdp/tp/sp via the ``ray_tpu.parallel`` rule tables, per-layer
``jax.checkpoint`` with the same policy menu as GPT, and the same
pluggable attention body (dense / Pallas flash).

The layer is written ONCE (``_layer``: ``_attention`` and ``_ffn``, each a
``_sublayer`` of the residual stream) and the training trunk,
``llama_prefill``, ``llama_decode_step`` and ``llama_block_step`` all run it.
They differ in what the attention does with its keys and values, and that
alone is handed in, as an ``AttentionState``: ``_no_cache``,
``_prefill_state``, ``_token_state``, ``_block_state``, for K/V pages and,
where written, latent pages and a linear layer's recurrent rows.  A cache of
another kind (a window's ring) is one more state, its pool's shape beside
``llama_init_paged_cache`` and its functions under ``ops/``: not the layer,
and not the engine, which asks ``served`` for this module's programs and
knows none by name.

The reference has no model zoo of its own (its flagship benchmarks wrap
torchvision/HF models); this family exists so Train/Tune/Serve have a
modern-architecture model to exercise, matching
``release/air_tests/air_benchmarks``' role.

With ``num_experts > 0`` the feed-forward is a mixture of SwiGLU experts
routed top-k per token without capacity (``ops/moe.py``'s dropless path),
and with ``qk_norm`` the q and k projections are RMS-normed over all heads
before the rotation: together OLMoE's block (``_ffn``, ``_qk``).  The
expert model serves; it does not train here:
``llama_loss`` refuses it, because the load-balancing loss, ``ep``
sharding and the all-to-all are not written.

With ``ut_steps > 1`` the whole layer stack is run that many times over the
same weights (Ouro's looped model): pass ``t`` starts from the last pass's
output after the model's final norm, and when served keeps a cache of its
own, so the pools hold ``ut_steps * num_layers`` layers and pass ``t``,
layer ``l`` reads and writes pool layer ``t * num_layers + l``.  With
``post_norm`` a sublayer's output is RMS-normed (``ln1_post``, ``ln2_post``)
before it is added to the residual stream (``_add_sublayer``,
``_passes``); with ``ut_steps == 1`` and no ``post_norm`` neither adds an
operation.  The looped model serves; it does
not train here: ``llama_loss`` refuses it, because its published objective
(an entropy-regularised expectation over exit steps) is not written, and
neither are the exit gate's two leaves: at the published threshold of 1
the gate never exits early.

With ``kv_lora_rank`` the attention is latent (MLA, DeepSeek-V2/V3's): the
query goes through a normed bottleneck, keys and values are expanded per head
from ONE normed ``kv_lora_rank``-wide vector a position, and the positions
come from a ``qk_rope_dim``-wide key all heads share (YaRN tables with
``rope_yarn``).  Served, a position's cache is that vector and that key, one
page pool ``[L, P, page, Wp]`` (``Wp`` = ``kv_lora_rank + qk_rope_dim`` in
whole 128-lane tiles, zeros past the values) and no V pool
(``ops/paged_attention.py``'s latent kind): the prefill expands keys and
values for its dense attention, the decode step absorbs ``wkv_b`` into the
query and the output and reads the pool as it lies.  With
``first_dense_layers`` the stack is two groups, dense feed-forwards of
``dense_mlp_dim`` under ``params["dense_layers"]`` (few, and unrolled) ahead
of the scanned expert layers.  With ``hc_mult`` the residual stream is
``hc_mult`` rows wide and every sublayer reads a learned mix of the rows and
writes back through a Sinkhorn-projected mixing matrix (manifold-constrained
hyper-connections, arXiv:2512.24880; ``_sublayer``).  ``shared_experts``,
``router_scoring``, ``router_bias`` and ``routed_scaling`` are
``ops/moe.py``'s.  Such a model serves and runs ``llama_forward``; it does
not train here.

With a ``layer_pattern`` the stack is layers of two kinds in a repeating
period, "full" (anywhere in the period) and ONE of "linear", "conv" and
"ssm" (four kinds are written; ``_check`` refuses two of the last three in
one stack; Olmo-Hybrid's period is three "linear" then one "full"): a
linear layer's
attention is the gated delta rule (``_linear_attention``;
``ops/linear_attention.py`` has the rule's chunked scan, its one-position
step and the short causal convolution ahead of it), which keeps of its past
one float32 matrix a head and the convolution's last inputs, not pages.
``params["layers"]`` is then a tuple, one group a position of the period,
each stacked over the periods, and the layer scan goes over periods
(``_scan_periods``).  Served, the third kind of state (``recurrent``) lives
beside the pages: ``llama_init_paged_cache`` makes K/V pages for the full
layers alone and, where the V pool would be, ``RecurrentPools``: that pool
and a state row and a convolution tail a decode SLOT for every linear layer,
the state folded so that no lane of the TPU's tiles is air.  A prefill learns
its slot (``llama_prefill``'s last argument) and leaves there what stands
after the prompt's last real position, whatever the rung; the token step
steps every live slot's rows where they lie.  ``pre_norm=False`` takes the
norm off a sublayer's input (OLMo 2 norms the output only: ``post_norm``)
and ``rope_theta=0`` rotates nothing.  Such a model serves and runs
``llama_forward``; ``llama_loss`` refuses it (the scan's backward pass is
not written) and so does the block step.

Such a stack's FULL layers may keep latent pages (``kv_lora_rank``): the pair
of pools is then (latent pages, ``RecurrentPools`` with no V pool), and a
program uses two of ``AttentionState``'s three kinds.  Latent attention
without ``q_lora_rank`` projects its queries directly, and with ``rope_theta``
0 (and no ``rope_yarn``) rotates nothing, the shared key as little as the
queries.  With ``linear_gate_rank`` the linear layers are Kimi Delta
Attention's (``_kda_gates_and_output``): the decay a KEY CHANNEL through a
bottleneck, beta in (0, 1), the read-out gated by a sigmoid through another
(``ops/linear_attention.py``'s ``kda_*``).  Behind ``first_dense_layers`` the
stack's feed-forwards may be experts: the layers are then not one shape a
position of the period, ``params["layers"]`` is a tuple with a group a LAYER
(each a stack of one, an expert layer's experts under its own ``mlp``) and the
layers run in a Python loop (``_unrolled_layers``).  With ``expert_share`` (i,
n) the program holds share i of n of every expert layer's experts and computes
their part of the routed sum alone (``ops/moe.py``, the held experts): Kimi
Linear's block, one chip's share of it.

The THIRD kind, "conv", is LFM2's gated short convolution (``_conv_operator``;
transformers' ``Lfm2ShortConv``): ``[B | C | z] = h W_in``, ``u = B * z``, a
causal depthwise convolution of ``u`` over ``linear_conv`` (3) positions with
NO activation after it, ``y = C * conv(u)``, ``y W_out``.  Such a layer keeps
of its past the last ``linear_conv - 1`` positions of ``u`` and nothing else:
``RecurrentPools.state`` is None (no matrix, nothing that grows) and
``RecurrentPools.conv`` holds a tail a decode slot for every conv layer, which
a prefill leaves as it stands after the prompt's last real position, whatever
the rung, zeros on the left of a prompt shorter than the tail.  Its stack is
either dense feed-forwards scanned over periods or, behind
``first_dense_layers``, experts (no shared one needed) in ``_unrolled_layers``:
LFM2-24B-A2B's block with ``qk_norm_per_head``, heads of ``head_size`` 64,
``tie_embeddings`` and sigmoid routing renormalised over ``router_norm_eps``.

With ``index_heads`` latent attention is SPARSE (DeepSeek-V3.2's: a lightning
indexer beside it).  ``_index_project`` makes, from the layer's input and the
same normed query bottleneck that ``wq_b`` reads, ``index_heads`` queries of
``index_head_dim``, a weight a head and ONE LayerNormed key a position (the
first ``qk_rope_dim`` columns of queries and key rotated by the attention's
tables); ``I[t, s] = sum_j w[t, j] ReLU(q[t, j] . k[s])`` scores every causal
position for a query, and the attention's softmax runs over the
``index_topk`` positions that score highest, exactly those.  Served, a
position keeps a SECOND row, the indexer's key, in a pool of its own where
the others have their V pool (``llama_init_paged_cache``: ``(latent pages,
key pages)``, one page table for both).  The token step scores a sequence's
live positions from the key pool, takes their top ``index_topk`` and reads
those latent rows alone (``_mla_absorbed`` with ``selected``); the scopes
``dsa_index``, ``dsa_select`` and ``dsa_read`` name the three parts in both
programs.  Up to ``index_topk`` positions of context nothing is deselected
and the layer is plain latent attention.  ``expert_groups`` (n, k) chooses a
token's experts inside its k best of n groups (``ops/moe.py::_route``).

A model whose whole past is addressable by position in latent pages
(``llama_prefill_chunks``) takes a prompt IN CHUNKS: ``llama_prefill`` with a
``start`` runs the positions ``start ..`` of the prompt, writes their rows
to the sequence's pages and attends over the PAGES (``_mla_chunk``: the
table's rows gathered and expanded per head once a layer, then
``ops/latent_prefill.py``'s walk, queries outermost with an online softmax,
the selection a mask a query that all heads share; on the chip ONE kernel
whose scores never leave fast memory), so no call is wider than the widest
compiled and the scores ``[heads, S, S]`` never exist (8.6 GB at 128 heads
and 4,096 positions).  An indexer model's prefill always runs so (``start`` 0
where none is given); every other model without a ``start`` keeps
``_mla_expanded`` or the flash kernel.  Such a model serves; the training
trunk refuses the indexer.

The FOURTH kind, "ssm", is Mamba-2's mixer (``_ssm_mixer``; transformers'
``GraniteMoeHybridMambaLayer`` with one group; State Space Duality,
arXiv:2405.21060): ``[z | xBC | dt] = h W_in``, a causal depthwise
convolution of ``xBC`` over ``linear_conv`` positions WITH a bias, then SiLU,
``[x | B | C] = xBC``; ``delta = softplus(dt + dt_bias)`` and ``a =
exp(-exp(A_log) delta)`` a head; a head's state ``S_n`` [``linear_key_dim``,
``linear_value_dim``] does ``S_n <- a_n S_n + B (delta_n x_n)^T``, ``y_n =
S_n^T C + D_n x_n``: the delta rule's row a slot WITHOUT its correction, ONE
key ``B`` and ONE query ``C`` for all ``linear_heads`` heads, a step size
that scales the input and a skip; then ``y * silu(z)`` RMS-normed over ALL
channels at once (the gate first, one norm) and ``W_out``.  It keeps what a
linear layer keeps, a float32 state row and a convolution tail a decode slot
in ``RecurrentPools`` (the ``recurrent`` entries of the three states take the
rule by the layer's leaves; no fourth kind of cache): the prefill runs
``ops/linear_attention.py::ssm_chunked`` (products alone, no triangular
solve: nothing a position writes depends on what the state held) on the
padded rung, where a padded position gets a = 1 and an input of 0, and the
token step is ``step_pool`` with no beta: the kernel of
``ops/linear_state.py`` in its shared kind, which reads the one key and
query a slot and spreads nothing over the heads in HBM.  Heads of 64 values
lie two to a 128-lane panel (``_panel_plan``).  Under a ``layer_pattern`` the
feed-forwards may be experts in EVERY layer, no leading dense layer
(``_group_a_layer``: a group a layer, ``_unrolled_layers``), and the period's
full layer may stand anywhere in it.  ``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier`` (the softmax's scale where
it is not ``head_dim ** -0.5``) and ``logits_scaling`` (a divisor) are four
scalars that add no operation at their defaults: Granite 4.0-H's block, one
chip's share of it.  Such a model serves and runs ``llama_forward``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from ray_tpu.models.gpt import _cast_leaves, ce_head_loglike_sum
from ray_tpu.ops.attention import (_dense_causal_attention_bnsh,
                                   _flash_attention_bnsh, resolve_attention)
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
    rope_theta: float = 10000.0      # 0: nothing is rotated
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
    kv_lora_rank: int = 0            # > 0: latent attention (MLA), and the
    q_lora_rank: int = 0             #   width of the query's bottleneck,
    qk_nope_dim: int = 0             #   a head's unrotated q/k width,
    qk_rope_dim: int = 0             #   the shared rotated key's width,
    v_head_dim: int = 0              #   a head's value width
    # YaRN: (factor, original length, beta_fast, beta_slow, mscale,
    # mscale_all_dim), DeepSeek-V3's reading of them
    rope_yarn: Optional[Tuple[float, ...]] = None
    first_dense_layers: int = 0      # of num_layers, ahead of the experts'
    dense_mlp_dim: int = 0           # their SwiGLU's width
    shared_experts: int = 0          # a shared expert of this x mlp_dim
    router_scoring: str = "softmax"  # or "sigmoid" (gates from the scores)
    router_bias: bool = False        # added to the scores to SELECT only
    routed_scaling: float = 1.0      # sigmoid routing: the gates' factor
    router_norm_eps: float = 1e-20   #   and what their sum is renormalised over
    hc_mult: int = 0                 # rows of a hyper-connected residual
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    head_size: int = 0               # a head's width; 0: embed_dim / heads
    qk_norm_per_head: bool = False   # RMSNorm q and k, each head by itself
    block_length: int = 0            # > 0: generation by diffusion over
    denoise_steps: int = 0           #   blocks of this many positions, in
    confidence_threshold: float = 0.0   # this many passes (0 = static:
    mask_token: int = 0              #   the count alone), masks of this id
    pre_norm: bool = True            # RMSNorm a sublayer's input
    # one period of the stack's layer kinds, "full" and one of "linear" |
    # "conv" | "ssm" (the stack repeats it; empty: every layer is full
    # attention; a "conv" layer is a gated short convolution over
    # ``linear_conv`` positions and takes none of the other linear_* fields;
    # an "ssm" layer is Mamba-2's mixer with ONE key and query for all heads,
    # ``linear_key_dim`` its state's width), and a linear or ssm layer's
    layer_pattern: Tuple[str, ...] = ()
    linear_heads: int = 0            #   heads (keys' and values' alike),
    linear_key_dim: int = 0          #   a head's q/k width,
    linear_value_dim: int = 0        #   a head's value width,
    linear_conv: int = 4             #   convolution over this many positions
    linear_neg_eigval: bool = False  #   beta in (0, 2) and not (0, 1)
    # > 0: Kimi Delta Attention: the decay a KEY CHANNEL, it and the output's
    # gate (a sigmoid) through bottlenecks of this width
    linear_gate_rank: int = 0
    # (i, n): this program holds share i of n of every expert layer, experts
    # i * num_experts / n on; the router keeps num_experts outputs
    expert_share: Tuple[int, int] = (0, 1)
    tie_embeddings: bool = False     # logits from the table: no lm_head leaf
    # > 0: a lightning indexer beside latent attention (DeepSeek-V3.2's
    # sparse attention): ``index_heads`` heads of ``index_head_dim`` score
    # every causal position for a query, and the attention's softmax runs
    # over the ``index_topk`` positions that score highest
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # (n, k): the routed experts lie in n equal groups and a token's experts
    # are chosen inside its k best groups (``ops/moe.py::_route``)
    expert_groups: Tuple[int, int] = (1, 1)
    # Granite's four scalars, none of which adds an operation where it is
    # left as it stands: the embedding times this, a sublayer's output times
    # this before it joins the stream, the softmax's scale (0: head_dim to
    # the -1/2), the logits DIVIDED by this
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0

    @property
    def head_dim(self) -> int:
        return self.head_size or self.embed_dim // self.num_heads

    @staticmethod
    def tiny(vocab: int = 256, seq: int = 128) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab, max_seq_len=seq, num_layers=2,
                           num_heads=4, num_kv_heads=2, embed_dim=64,
                           mlp_dim=192)


def _check(cfg: LlamaConfig) -> None:
    if cfg.kv_lora_rank and not (cfg.qk_nope_dim and cfg.qk_rope_dim
                                 and cfg.v_head_dim):
        raise ValueError("latent attention needs qk_nope_dim, qk_rope_dim "
                         "and v_head_dim beside kv_lora_rank (q_lora_rank "
                         "0: the queries are projected directly)")
    if cfg.kv_lora_rank and bool(cfg.rope_theta) != bool(cfg.rope_yarn):
        raise ValueError("latent attention rotates by YaRN's tables "
                         "(rope_yarn) or, with rope_theta 0 and no "
                         "rope_yarn, rotates nothing")
    if not cfg.kv_lora_rank and cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(f"num_heads={cfg.num_heads} must be divisible by "
                         f"num_kv_heads={cfg.num_kv_heads}")
    if cfg.num_experts and not \
            0 < cfg.experts_per_token <= cfg.num_experts:
        raise ValueError(f"experts_per_token={cfg.experts_per_token} must "
                         f"be in 1..num_experts={cfg.num_experts}")
    share, shares = cfg.expert_share
    if not 0 <= share < shares or (shares > 1 and (
            not cfg.num_experts or cfg.num_experts % shares)):
        raise ValueError(f"expert_share={cfg.expert_share} is share i of n "
                         f"equal shares of num_experts={cfg.num_experts}")
    if not 0 <= cfg.first_dense_layers < max(cfg.num_layers, 1) or (
            cfg.first_dense_layers and not (cfg.num_experts
                                            and cfg.dense_mlp_dim)):
        raise ValueError("first_dense_layers are dense layers of "
                         "dense_mlp_dim ahead of at least one expert layer")
    if cfg.router_scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"router_scoring={cfg.router_scoring!r}")
    groups, best = cfg.expert_groups
    if not 1 <= best <= groups or (groups > 1 and (
            cfg.router_scoring != "sigmoid" or cfg.num_experts % groups
            or best * (cfg.num_experts // groups) < cfg.experts_per_token)):
        raise ValueError(
            f"expert_groups={cfg.expert_groups} is (n, k): num_experts="
            f"{cfg.num_experts} in n equal groups of which a token's k best "
            "hold at least experts_per_token experts, under sigmoid routing "
            "(the one scoring the group limit is written for)")
    if cfg.index_heads:
        unwritten = [name for name in ("layer_pattern", "block_length",
                                       "hc_mult") if getattr(cfg, name)]
        if cfg.ut_steps > 1:
            unwritten.append("ut_steps > 1")
        if not (cfg.kv_lora_rank and cfg.q_lora_rank and cfg.rope_yarn):
            unwritten.append("attention that is not latent with a query "
                             "bottleneck and YaRN's tables (kv_lora_rank, "
                             "q_lora_rank, rope_yarn)")
        if unwritten:
            raise ValueError("the indexer (index_heads) reads the latent "
                             "attention's normed query bottleneck and keeps "
                             "a key a position beside the latent pages; it "
                             "is not written for " + ", ".join(unwritten))
        if not (cfg.index_topk > 0
                and cfg.qk_rope_dim <= cfg.index_head_dim):
            raise ValueError("the indexer needs index_topk positions to "
                             "keep and heads of index_head_dim, of which "
                             "the first qk_rope_dim columns are rotated")
    elif cfg.index_head_dim or cfg.index_topk:
        raise ValueError("index_head_dim and index_topk belong to "
                         "index_heads")
    if cfg.hc_mult and (cfg.post_norm or cfg.ut_steps > 1
                        or not cfg.pre_norm):
        raise ValueError("a hyper-connected residual (hc_mult) is not "
                         "written for post_norm, ut_steps > 1 or no "
                         "pre_norm")
    if cfg.qk_norm and cfg.qk_norm_per_head:
        raise ValueError("qk_norm pools all heads, qk_norm_per_head each "
                         "head by itself: one or the other")
    if (cfg.head_size or cfg.qk_norm_per_head) and cfg.kv_lora_rank:
        raise ValueError("latent attention has head widths of its own "
                         "(qk_nope_dim, qk_rope_dim, v_head_dim) and norms "
                         "its bottlenecks, not head_size or "
                         "qk_norm_per_head")
    if cfg.layer_pattern:
        kinds, others = set(cfg.layer_pattern), ("hc_mult", "block_length")
        if not any(kinds <= {kind, "full"}
                   for kind in ("linear", "conv", "ssm")) \
                or kinds == {"full"} \
                or cfg.num_layers % len(cfg.layer_pattern):
            raise ValueError(
                f"layer_pattern={cfg.layer_pattern} is one period of 'full' "
                "layers, anywhere in it, and layers of ONE other kind, at "
                "least one of them linear (the delta rule), or at least one "
                "a 'conv' (a gated short convolution), or at least one an "
                "'ssm' (Mamba-2's state-space rule), and "
                f"num_layers={cfg.num_layers} whole periods")
        if cfg.linear_conv < 2 or (kinds & {"linear", "ssm"} and not (
                cfg.linear_heads and cfg.linear_key_dim
                and cfg.linear_value_dim)):
            raise ValueError("linear layers need linear_heads, "
                             "linear_key_dim and linear_value_dim, ssm "
                             "layers too, and they and conv layers a "
                             "linear_conv of 2 or more")
        if "ssm" in kinds and (cfg.linear_gate_rank
                               or cfg.linear_neg_eigval):
            raise ValueError("an ssm layer's rule has no correction: "
                             "neither linear_gate_rank nor "
                             "linear_neg_eigval")
        if "conv" in kinds and (cfg.kv_lora_rank or cfg.linear_gate_rank
                                or cfg.linear_heads):
            raise ValueError("a stack with conv layers is written with K/V "
                             "pages for its full layers (no kv_lora_rank) "
                             "and takes of the linear_* fields linear_conv "
                             "alone")
        if cfg.ut_steps > 1 or any(getattr(cfg, o) for o in others):
            raise ValueError("a stack with linear, conv or ssm layers "
                             "(layer_pattern) is not written for "
                             "ut_steps > 1 or " + " or ".join(others)
                             + "; its full layers keep K/V or latent pages, "
                             "and its feed-forwards may be experts, behind "
                             "leading dense layers or in every layer")
        if cfg.linear_gate_rank and cfg.linear_neg_eigval:
            raise ValueError("a decay a key channel (linear_gate_rank) has "
                             "beta in (0, 1): no linear_neg_eigval")
    elif cfg.linear_gate_rank:
        raise ValueError("linear_gate_rank belongs to a layer_pattern")
    if cfg.block_length:
        if cfg.kv_lora_rank or cfg.ut_steps > 1 or cfg.hc_mult:
            raise ValueError("generation by blocks (block_length) is not "
                             "written for latent attention, ut_steps > 1 "
                             "or hc_mult")
        if not 1 <= cfg.denoise_steps <= cfg.block_length:
            raise ValueError(f"denoise_steps={cfg.denoise_steps} must be "
                             f"in 1..block_length={cfg.block_length}")
        if not 0 <= cfg.mask_token < cfg.vocab_size:
            raise ValueError(f"mask_token={cfg.mask_token} is not an id of "
                             f"the vocabulary of {cfg.vocab_size}")
        if not 0.0 <= cfg.confidence_threshold < 1.0:
            raise ValueError("confidence_threshold is a probability under "
                             f"1 (0: static), not "
                             f"{cfg.confidence_threshold}")
    elif cfg.denoise_steps or cfg.confidence_threshold or cfg.mask_token:
        raise ValueError("denoise_steps, confidence_threshold and "
                         "mask_token belong to a block_length")
    if cfg.attention_multiplier and cfg.kv_lora_rank:
        raise ValueError("latent attention has a softmax scale of its own "
                         "(mla_softmax_scale), not attention_multiplier")
    if cfg.residual_multiplier != 1.0 and cfg.hc_mult:
        raise ValueError("a hyper-connected residual (hc_mult) weighs a "
                         "sublayer's output itself: no residual_multiplier")


def _slot_layers(cfg: LlamaConfig) -> int:
    """How many of the stack's layers are not full attention (linear
    attention or a short convolution: those that keep rows a slot, no
    pages)."""
    if not cfg.layer_pattern:
        return 0
    return (len(cfg.layer_pattern) - cfg.layer_pattern.count("full")) * (
        cfg.num_layers // len(cfg.layer_pattern))


def _held_experts(cfg: LlamaConfig) -> int:
    """The experts of a layer that this program holds: its share of the
    ``num_experts`` the router scores (all of them: one share)."""
    return cfg.num_experts // cfg.expert_share[1]


def _layer_kinds(cfg: LlamaConfig) -> Tuple[str, ...]:
    """Every layer's kind, in order: the pattern's periods end to end."""
    return cfg.layer_pattern * (cfg.num_layers // len(cfg.layer_pattern))


def _group_a_layer(cfg: LlamaConfig) -> bool:
    """Whether a pattern stack keeps a group a LAYER (each a stack of one,
    run by ``_unrolled_layers``) and not a group a position of the period:
    where its feed-forwards are experts, behind leading dense layers or in
    every layer (an expert layer's experts are its own ``mlp``, handed to the
    grouped matmuls whole, never a scan's slice)."""
    return bool(cfg.layer_pattern
                and (cfg.first_dense_layers or cfg.num_experts))


def _mixer_channels(cfg: LlamaConfig, kind: str) -> int:
    """The channels a ``kind`` layer's short convolution runs over: a linear
    layer's q | k | v, an ssm layer's x | B | C."""
    N, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    return {"linear": N * (2 * dk + dv), "ssm": N * dv + 2 * dk}[kind]


def _init_group(rng: jax.Array, cfg: LlamaConfig, L: int,
                experts: int, M: int, kind: str = "full") -> Dict[str, Any]:
    """``L`` layers of one ``kind`` stacked on a leading dim: ``experts`` of
    width ``M`` each (0: one dense SwiGLU of ``M``; of ``cfg.num_experts``
    the program's share, ``_held_experts``: the router keeps them all);
    a "linear" kind's leaves under ``"linear"``, a "conv" kind's under
    ``"conv"`` and an "ssm" kind's under ``"ssm"`` where the others have
    ``"attn"``."""
    k = jax.random.split(rng, 8)
    D, H = cfg.embed_dim, cfg.head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    scale = 0.02
    rscale = scale / np.sqrt(2 * cfg.num_layers)

    def normal(key, shape, std=scale):
        return std * jax.random.normal(key, shape, jnp.float32)

    ex = (experts,) if experts else ()   # the experts' leading dim
    # SwiGLU: gate and up projections fused on a leading 2-dim.
    mlp = {"wgu": normal(k[4], (L, *ex, 2, D, M)),
           "wd": normal(k[5], (L, *ex, M, D), rscale)}
    extra = {}
    if experts:
        routed = experts * cfg.expert_share[1]       # the router's width
        mlp["router"] = normal(k[7], (L, D, routed))
        if cfg.router_bias:
            mlp["router_bias"] = normal(jax.random.fold_in(k[7], 1),
                                        (L, routed), 0.01)
        if cfg.shared_experts:
            Ms = cfg.shared_experts * M
            extra["shared"] = {
                "wgu": normal(jax.random.fold_in(k[4], 1), (L, 2, D, Ms)),
                "wd": normal(jax.random.fold_in(k[5], 1), (L, Ms, D),
                             rscale)}
    if kind == "conv":
        # LFM2's gated short convolution: B | C | z in one projection, the
        # taps as torch's Conv1d draws them (uniform within K^-1/2,
        # ``taps[K - 1]`` meets the position itself), the way out
        K = cfg.linear_conv
        attn = {"win": normal(k[1], (L, D, 3 * D)),
                "taps": jax.random.uniform(
                    jax.random.fold_in(k[2], 1), (L, K, D), jnp.float32,
                    -K ** -0.5, K ** -0.5),
                "wout": normal(k[3], (L, D, D), rscale)}
    elif kind == "ssm":
        # Mamba-2's leaves as its layer draws them (transformers'
        # ``GraniteMoeHybridMambaLayer``): z | x B C | dt in one projection,
        # the convolution with a BIAS (torch's Conv1d: both uniform within
        # K^-1/2), A uniform over (1, 16) kept as its logarithm, dt
        # log-uniform over (0.001, 0.1) kept as its inverse softplus, the
        # skip D and the gated norm's scale ones
        N, dk, dv, K = (cfg.linear_heads, cfg.linear_key_dim,
                        cfg.linear_value_dim, cfg.linear_conv)
        C = _mixer_channels(cfg, "ssm")
        dt = jnp.exp(jax.random.uniform(
            jax.random.fold_in(k[2], 2), (L, N), jnp.float32,
            np.log(0.001), np.log(0.1)))
        attn = {"win": normal(k[1], (L, D, N * dv + C + N)),
                "conv": jax.random.uniform(
                    jax.random.fold_in(k[2], 1), (L, K, C), jnp.float32,
                    -K ** -0.5, K ** -0.5),
                "conv_bias": jax.random.uniform(
                    jax.random.fold_in(k[2], 4), (L, C), jnp.float32,
                    -K ** -0.5, K ** -0.5),
                "A_log": jnp.log(jax.random.uniform(
                    jax.random.fold_in(k[2], 3), (L, N), jnp.float32,
                    1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((L, N), jnp.float32),
                "norm": jnp.ones((L, N * dv), jnp.float32),
                "wout": normal(k[3], (L, N * dv, D), rscale)}
    elif kind == "linear":
        # The gated delta rule's leaves (transformers' Qwen3NextGatedDeltaNet
        # draws them so): the convolution as torch's Conv1d, uniform within
        # fan_in^-1/2 = K^-1/2; A uniform over (0, 16); dt log-uniform over
        # (0.001, 0.1) and kept as its inverse softplus.
        N, dk, dv, K = (cfg.linear_heads, cfg.linear_key_dim,
                        cfg.linear_value_dim, cfg.linear_conv)
        C = N * (2 * dk + dv)            # q | k | v, the convolved channels
        r = cfg.linear_gate_rank     # Kimi Delta Attention: a dt a channel
        dt = jnp.exp(jax.random.uniform(
            jax.random.fold_in(k[2], 2), (L, N * dk if r else N),
            jnp.float32, np.log(0.001), np.log(0.1)))
        # q | k | v over the convolved channels; then the output's gate z
        # and the rule's two gates b | a, or under ``r`` their bottlenecks
        wqkv, kz = normal(k[1], (L, D, C)), jax.random.fold_in(k[1], 1)
        gates = {"wg_a": normal(kz, (L, D, r)),
                 "wg_b": normal(jax.random.fold_in(kz, 1), (L, r, N * dv)),
                 "wf_a": normal(jax.random.fold_in(k[2], 4), (L, D, r)),
                 "wf_b": normal(jax.random.fold_in(k[2], 5), (L, r, N * dk)),
                 "wb": normal(k[2], (L, D, N))} if r else \
            {"wz": normal(kz, (L, D, N * dv)),
             "wba": normal(k[2], (L, D, 2 * N))}          # beta's | alpha's
        attn = {"wqkv": wqkv, **gates,
                "conv": jax.random.uniform(
                    jax.random.fold_in(k[2], 1), (L, K, C), jnp.float32,
                    -K ** -0.5, K ** -0.5),
                "A_log": jnp.log(jax.random.uniform(
                    jax.random.fold_in(k[2], 3), (L, N), jnp.float32,
                    1e-3, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": jnp.ones((L, dv), jnp.float32),
                "wo": normal(k[3], (L, N, dv, D), rscale)}
    elif cfg.kv_lora_rank:
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        query = {"wq_a": normal(k[1], (L, D, rq)),
                 "q_a_norm": jnp.ones((L, rq), jnp.float32),
                 "wq_b": normal(jax.random.fold_in(k[1], 1),
                                (L, rq, nh, dn + dr))} if rq else \
            {"wq": normal(k[1], (L, D, nh, dn + dr))}    # no bottleneck
        attn = {**query,
                "wkv_a": normal(k[2], (L, D, rkv + dr)),
                "kv_a_norm": jnp.ones((L, rkv), jnp.float32),
                "wkv_b": normal(jax.random.fold_in(k[2], 1),
                                (L, rkv, nh, dn + dv)),
                "wo": normal(k[3], (L, nh, dv, D), rscale)}
        if cfg.index_heads:
            # the lightning indexer: queries from the query bottleneck, one
            # key a position (LayerNorm with scale and bias), a weight a head
            ki = jax.random.split(jax.random.fold_in(k[1], 2), 3)
            hi, di = cfg.index_heads, cfg.index_head_dim
            attn.update({
                "index_wq": normal(ki[0], (L, rq, hi, di)),
                "index_wk": normal(ki[1], (L, D, di)),
                "index_k_norm": jnp.ones((L, di), jnp.float32),
                "index_k_bias": jnp.zeros((L, di), jnp.float32),
                "index_w": normal(ki[2], (L, D, hi))})
    else:
        norms = {"q_norm": jnp.ones((L, nh, H), jnp.float32),
                 "k_norm": jnp.ones((L, nkv, H), jnp.float32)} \
            if cfg.qk_norm else {}
        if cfg.qk_norm_per_head:     # one scale for every head's H values
            norms = {"q_norm": jnp.ones((L, H), jnp.float32),
                     "k_norm": jnp.ones((L, H), jnp.float32)}
        attn = {"wq": normal(k[1], (L, D, nh, H)),
                "wkv": normal(k[2], (L, D, 2, nkv, H)),
                "wo": normal(k[3], (L, nh, H, D), rscale),
                **norms}
    for name in ("ln1_post", "ln2_post") if cfg.post_norm else ():
        extra[name] = {"scale": jnp.ones((L, D), jnp.float32)}
    n = cfg.hc_mult
    for at, name in enumerate(("hc_attn", "hc_mlp") if n else ()):
        # One projection for the three coefficients (pre | post | res) and
        # their scalars and biases, all f32.  The mixing matrix starts
        # near the identity: its bias favours the diagonal.
        key = jax.random.fold_in(k[6], at)
        bias = normal(jax.random.fold_in(key, 1), (L, 2 * n + n * n), 0.5)
        extra[name] = {
            "proj": normal(key, (L, n * D, 2 * n + n * n)),
            "alpha": jnp.full((L, 3), 0.25, jnp.float32),
            "bias": bias.at[:, 2 * n:].add(2.0 * jnp.eye(n).reshape(-1))}
    for name in ("ln1", "ln2") if cfg.pre_norm else ():
        extra[name] = {"scale": jnp.ones((L, D), jnp.float32)}
    return {kind if kind != "full" else "attn": attn, "mlp": mlp, **extra}


def llama_init(rng: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Params with per-layer weights stacked on a leading [L] dim; with
    ``first_dense_layers`` those under ``dense_layers`` and the expert
    layers that follow them under ``layers``; with a ``layer_pattern``
    ``layers`` is a tuple, one group a POSITION of the pattern, each stacked
    over the periods [num_layers / len(pattern)]: layer ``l`` is row ``l //
    len(pattern)`` of group ``l % len(pattern)``; with a ``layer_pattern``
    AND experts (behind ``first_dense_layers`` or in every layer:
    ``_group_a_layer``) it is a tuple with a group a LAYER, each a
    stack of one (the leading dense layers' feed-forwards and the others'
    experts are not one shape to stack over periods; ``_unrolled_layers``),
    an expert layer's experts and router under its own ``mlp``."""
    _check(cfg)
    k = jax.random.split(rng, 8)
    D, V, Ld = cfg.embed_dim, cfg.vocab_size, cfg.first_dense_layers
    scale = 0.02
    dense = {"dense_layers": _init_group(
        jax.random.fold_in(rng, 1), cfg, Ld, 0, cfg.dense_mlp_dim)} \
        if Ld and not cfg.layer_pattern else {}

    def layers():        # (drawn after the table, as they always were)
        if not cfg.layer_pattern:
            return _init_group(rng, cfg, cfg.num_layers - Ld,
                               _held_experts(cfg), cfg.mlp_dim)
        if _group_a_layer(cfg):
            return tuple(
                _init_group(jax.random.fold_in(rng, 16 + at), cfg, 1,
                            *((0, cfg.dense_mlp_dim) if at < Ld else
                              (_held_experts(cfg), cfg.mlp_dim)), kind)
                for at, kind in enumerate(_layer_kinds(cfg)))
        periods = cfg.num_layers // len(cfg.layer_pattern)
        return tuple(
            _init_group(jax.random.fold_in(rng, 16 + at), cfg, periods, 0,
                        cfg.mlp_dim, kind)
            for at, kind in enumerate(cfg.layer_pattern))
    return {
        "wte": scale * jax.random.normal(k[0], (V, D), jnp.float32),
        **dense,
        "layers": layers(),
        "ln_f": {"scale": jnp.ones((D,), jnp.float32)},
        **({} if cfg.tie_embeddings else {
            "lm_head": scale * jax.random.normal(k[6], (D, V), jnp.float32)}),
    }


def _group_axes(cfg: LlamaConfig, experts: bool,
                kind: str = "full") -> Dict[str, Any]:
    ex = ("expert",) if experts else ()
    mlp = {"wgu": ("layers", *ex, None, "embed", "mlp"),
           "wd": ("layers", *ex, "mlp", "embed")}
    extra = {}
    if experts:
        mlp["router"] = ("layers", "embed", None)
        if cfg.router_bias:
            mlp["router_bias"] = ("layers", None)
        if cfg.shared_experts:
            extra["shared"] = {"wgu": ("layers", None, "embed", "mlp"),
                               "wd": ("layers", "mlp", "embed")}
    if kind == "conv":
        attn = {"win": ("layers", "embed", "heads"),
                "taps": ("layers", None, "heads"),
                "wout": ("layers", "heads", "embed")}
    elif kind == "ssm":
        attn = {"win": ("layers", "embed", "heads"),
                "conv": ("layers", None, "heads"),
                "conv_bias": ("layers", "heads"),
                "A_log": ("layers", None), "dt_bias": ("layers", None),
                "D": ("layers", None), "norm": ("layers", "norm"),
                "wout": ("layers", "heads", "embed")}
    elif kind == "linear":
        gates = {"wg_a": ("layers", "embed", None),
                 "wg_b": ("layers", None, "heads"),
                 "wf_a": ("layers", "embed", None),
                 "wf_b": ("layers", None, "heads"),
                 "wb": ("layers", "embed", None)} \
            if cfg.linear_gate_rank else \
            {"wz": ("layers", "embed", "heads"),
             "wba": ("layers", "embed", None)}
        attn = {"wqkv": ("layers", "embed", "heads"), **gates,
                "conv": ("layers", None, "heads"),
                "A_log": ("layers", None), "dt_bias": ("layers", None),
                "norm": ("layers", "norm"),
                "wo": ("layers", "heads", "kv", "embed")}
    elif cfg.kv_lora_rank:
        query = {"wq_a": ("layers", "embed", None),
                 "q_a_norm": ("layers", "norm"),
                 "wq_b": ("layers", None, "heads", "kv")} \
            if cfg.q_lora_rank else {"wq": ("layers", "embed", "heads", "kv")}
        attn = {**query,
                "wkv_a": ("layers", "embed", None),
                "kv_a_norm": ("layers", "norm"),
                "wkv_b": ("layers", None, "heads", "kv"),
                "wo": ("layers", "heads", "kv", "embed")}
        if cfg.index_heads:
            attn.update({"index_wq": ("layers", None, "heads", "kv"),
                         "index_wk": ("layers", "embed", None),
                         "index_k_norm": ("layers", "norm"),
                         "index_k_bias": ("layers", "norm"),
                         "index_w": ("layers", "embed", "heads")})
    else:
        norms = {"q_norm": ("layers", "heads", "kv"),
                 "k_norm": ("layers", "heads", "kv")} if cfg.qk_norm else {}
        if cfg.qk_norm_per_head:
            norms = {"q_norm": ("layers", "kv"), "k_norm": ("layers", "kv")}
        attn = {"wq": ("layers", "embed", "heads", "kv"),
                "wkv": ("layers", "embed", None, "heads", "kv"),
                "wo": ("layers", "heads", "kv", "embed"),
                **norms}
    for name in ("ln1_post", "ln2_post") if cfg.post_norm else ():
        extra[name] = {"scale": ("layers", "norm")}
    for name in ("hc_attn", "hc_mlp") if cfg.hc_mult else ():
        extra[name] = {"proj": ("layers", None, None),
                       "alpha": ("layers", None), "bias": ("layers", None)}
    for name in ("ln1", "ln2") if cfg.pre_norm else ():
        extra[name] = {"scale": ("layers", "norm")}
    return {kind if kind != "full" else "attn": attn, "mlp": mlp, **extra}


def llama_param_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Logical-axis annotations matching ``llama_init`` (same rule table
    as GPT: heads/mlp -> tp, embed -> fsdp, layers -> pp; experts carry
    "expert" -> ep, the router stays replicated over them)."""
    dense = {"dense_layers": _group_axes(cfg, False)} \
        if cfg.first_dense_layers and not cfg.layer_pattern else {}
    layers = _group_axes(cfg, bool(cfg.num_experts))
    if _group_a_layer(cfg):
        layers = tuple(
            _group_axes(cfg, at >= cfg.first_dense_layers, kind)
            for at, kind in enumerate(_layer_kinds(cfg)))
    elif cfg.layer_pattern:
        layers = tuple(_group_axes(cfg, False, kind)
                       for kind in cfg.layer_pattern)
    return {
        "wte": (None, "embed"),
        **dense,
        "layers": layers,
        "ln_f": {"scale": ("norm",)},
        **({} if cfg.tie_embeddings else {"lm_head": ("embed", None)}),
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


def yarn_rope_tables(S: int, H: int, theta: float, factor: float,
                     original_len: float, beta_fast: float,
                     beta_slow: float, mscale: float = 1.0,
                     mscale_all_dim: float = 0.0) -> tuple:
    """(cos, sin) [S, H/2] f32 tables with YaRN's frequencies, as
    DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` makes them: the pairs
    that turn more than ``beta_fast`` times over ``original_len`` positions
    keep their frequency, those that turn fewer than ``beta_slow`` times
    have it divided by ``factor``, a linear ramp between; both tables times
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""
    exponent = np.arange(0, H, 2, dtype=np.float64) / H
    extra, inter = theta ** -exponent, theta ** -exponent / factor

    def correction_dim(turns):
        return H * np.log(original_len / (turns * 2 * np.pi)) \
            / (2 * np.log(theta))
    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), H - 1)
    if low == high:
        high += 0.001
    keep = 1 - np.clip((np.arange(H // 2) - low) / (high - low), 0, 1)
    freqs = np.outer(np.arange(S, dtype=np.float64),
                     inter * (1 - keep) + extra * keep)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return (jnp.asarray(np.cos(freqs) * m, jnp.float32),
            jnp.asarray(np.sin(freqs) * m, jnp.float32))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * np.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(cfg: LlamaConfig, S: int) -> tuple:
    """The model's (cos, sin) tables for ``S`` positions; (None, None) for
    a model that rotates nothing (``rope_theta`` 0)."""
    if not cfg.rope_theta:
        return None, None
    if not cfg.kv_lora_rank:
        return rope_tables(S, cfg.head_dim, cfg.rope_theta)
    return yarn_rope_tables(S, cfg.qk_rope_dim, cfg.rope_theta,
                            *cfg.rope_yarn)


def apply_rope_pairs(x, cos, sin):
    """Rotate the pairs (2i, 2i+1) of [..., H] (DeepSeek's interleaved
    convention) and leave them de-interleaved: the first halves, then the
    second.  A score is a dot product of two vectors rotated alike, so the
    order they are left in is the cache's own business."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


def _dense_causal_attention_gqa(q, k, v, rep: int, block: int = 0):
    """Head-major grouped-query dense attention: q [B, N, S, H] with
    N = G*rep query heads sharing k/v [B, G, S, H].  Scores/output keep
    the (group, rep) split so K/V never replicate in memory.  Causal, or
    with ``block`` causal over blocks of that many positions: a position
    sees its whole block, both ways, and every block before it."""
    import numpy as _np
    B, N, S, H = q.shape
    G = N // rep
    qg = q.reshape(B, G, rep, S, H)
    scores = jnp.einsum("bgrqh,bgkh->bgrqk", qg, k) / _np.sqrt(H)
    if block:
        at = jnp.arange(S) // block
        mask = at[None, :] <= at[:, None]
    else:
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
    together (OLMoE norms before it splits into heads), with
    ``cfg.qk_norm_per_head`` one over EACH HEAD's values with a scale [H]
    all heads share (Qwen3's ``q_norm`` / ``k_norm``); then the rotation at
    ``cos``/``sin``'s positions."""
    if cfg.qk_norm_per_head:
        q = _rms_norm(q, p["attn"]["q_norm"], cfg.rms_eps)
        k = _rms_norm(k, p["attn"]["k_norm"], cfg.rms_eps)
    if cfg.qk_norm:
        def norm(a, scale):          # scale [N, H], a's heads on axis 1
            scale = scale.reshape(scale.shape[0], *(1,) * (a.ndim - 3), -1)
            return _rms_norm(a, scale, cfg.rms_eps, axis=(1, -1))
        q = norm(q, p["attn"]["q_norm"])
        k = norm(k, p["attn"]["k_norm"])
    if cos is None:                  # a model that rotates nothing
        return q, k
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
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def _sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of dividing each row of [..., n, n] by its sum and
    then each column by its: towards a doubly stochastic matrix."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _hc_coeff(cfg: LlamaConfig, hp, x):
    """A hyper-connection's coefficients for the stream x [..., n, C], all
    float32: ``pre`` [..., n] in (0, 1), how much of each row the sublayer
    reads; ``post`` [..., n] in (0, 2), how much of its output each row
    receives; ``res`` [..., n, n], rows and columns summing to 1, how the
    rows are mixed into the next stream.  All three are an affine function
    of the RMS-normed stream (all n x C values together, no learned scale):
    ``hp["proj"]`` [n*C, 2n + n*n] holds the three projections side by
    side, ``hp["alpha"]`` [3] their scalars, ``hp["bias"]`` their biases."""
    n = cfg.hc_mult
    lo, hi = cfg.hc_clamp
    with jax.named_scope("hc_coeff"):
        flat = x.reshape(*x.shape[:-2], -1)
        u = _rms_norm(flat.astype(jnp.float32), 1.0, cfg.rms_eps)
        raw = jnp.einsum("...k,km->...m", u, hp["proj"],
                         precision=jax.lax.Precision.HIGHEST)
        raw = raw * jnp.repeat(hp["alpha"], np.array([n, n, n * n]),
                               total_repeat_length=2 * n + n * n) \
            + hp["bias"]
        pre = jax.nn.sigmoid(raw[..., :n])
        post = 2.0 * jax.nn.sigmoid(raw[..., n:2 * n])
        res = raw[..., 2 * n:].reshape(*raw.shape[:-1], n, n)
        res = _sinkhorn(jnp.exp(jnp.clip(res, lo, hi)),
                        cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return pre, post, res


def _hc_reduce(x):
    """The stream's rows [..., n, C] summed into one, after the last
    layer."""
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def _sublayer(cfg: LlamaConfig, p, which: int, x, fn):
    """One sublayer around the residual stream: ``which`` 0 is attention
    (``ln1``, ``ln1_post``, ``hc_attn``), 1 the feed-forward.  ``fn`` takes
    the normed input [..., D] and returns (the sublayer's output, anything
    else the caller wants back).  Plain: ``x + fn(norm(x))``; without
    ``cfg.pre_norm`` the input is the stream itself and the only norm is
    ``_add_sublayer``'s, on the output (OLMo 2's order).  With
    ``cfg.hc_mult`` the stream is [..., n, D]: the sublayer reads ``pre .
    x``, and the next stream is ``res @ x + post (outer) y``, with the
    coefficients of ``_hc_coeff``; the products are float32 on the stream's
    own type and rounded to it once."""
    ln, post, hc = (("ln1", "ln1_post", "hc_attn"),
                    ("ln2", "ln2_post", "hc_mlp"))[which]
    if not cfg.hc_mult:
        y, rest = fn(_rms_norm(x, p[ln]["scale"], cfg.rms_eps)
                     if cfg.pre_norm else x)
        return _add_sublayer(cfg, p, post, x, y), rest
    h_pre, h_post, h_res = _hc_coeff(cfg, p[hc], x)
    with jax.named_scope("hc_mix"):
        x32 = x.astype(jnp.float32)
        h = jnp.sum(h_pre[..., None] * x32, axis=-2).astype(x.dtype)
    y, rest = fn(_rms_norm(h, p[ln]["scale"], cfg.rms_eps))
    with jax.named_scope("hc_mix"):
        mixed = jnp.sum(h_res[..., None] * x32[..., None, :, :], axis=-2)
        out = mixed + h_post[..., None] \
            * y.astype(jnp.float32)[..., None, :]
    return out.astype(x.dtype), rest


def _stream_axes(cfg: LlamaConfig) -> tuple:
    """Logical axes of the residual stream [B, S, (n,) D]."""
    return ("batch", "seq", None, "embed") if cfg.hc_mult else \
        ("batch", "seq", "embed")


def _embed(cfg: LlamaConfig, params, tokens):
    """The tokens' rows of the table; hyper-connected, each repeated to the
    stream's ``hc_mult`` rows."""
    x = params["wte"].astype(cfg.dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.hc_mult:
        x = jnp.repeat(x[..., None, :], cfg.hc_mult, axis=-2)
    return x


def mla_softmax_scale(cfg: LlamaConfig) -> float:
    """What latent attention's scores are multiplied by: the q/k head
    (unrotated + rotated) to the -1/2 and, under YaRN, the square of
    ``yarn_mscale(factor, mscale_all_dim)``, as DeepSeek-V3 has it."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_yarn and cfg.rope_yarn[5]:
        scale *= yarn_mscale(cfg.rope_yarn[0], cfg.rope_yarn[5]) ** 2
    return float(scale)


def _mla_project(cfg: LlamaConfig, p, h, cos, sin):
    """Latent attention's projections of the normed hidden h [..., D] at
    the positions of ``cos``/``sin`` [..., qk_rope_dim/2] (None: nothing is
    rotated): the heads'
    unrotated queries [..., N, qk_nope_dim] and rotated ones [..., N,
    qk_rope_dim], and the position's cache row [..., kv_lora_rank +
    qk_rope_dim]: the normed compressed key-value and the rotated key that
    all heads share.  A model with an indexer gets a fourth result, what
    ``_index_project`` makes of ``h`` and the SAME normed query bottleneck
    that ``wq_b`` reads."""
    a, dt = p["attn"], cfg.dtype
    rank, dn = cfg.kv_lora_rank, cfg.qk_nope_dim
    if cfg.q_lora_rank:
        cq = _rms_norm(jnp.einsum("...d,dr->...r", h, a["wq_a"].astype(dt)),
                       a["q_a_norm"], cfg.rms_eps)
        q = jnp.einsum("...r,rnh->...nh", cq, a["wq_b"].astype(dt))
    else:                            # no bottleneck
        q = jnp.einsum("...d,dnh->...nh", h, a["wq"].astype(dt))
    ckr = jnp.einsum("...d,dr->...r", h, a["wkv_a"].astype(dt))
    c = _rms_norm(ckr[..., :rank], a["kv_a_norm"], cfg.rms_eps)
    if cos is None:                  # a model that rotates nothing: the
        #                              shared key and its part of q as they are
        return q[..., :dn], q[..., dn:], jnp.concatenate(
            [c, ckr[..., rank:]], axis=-1)
    r = apply_rope_pairs(ckr[..., rank:], cos, sin)
    q_rope = apply_rope_pairs(q[..., dn:], cos[..., None, :],
                              sin[..., None, :])
    projected = q[..., :dn], q_rope, jnp.concatenate([c, r], axis=-1)
    if cfg.index_heads:
        with jax.named_scope("dsa_index"):
            projected += (_index_project(cfg, p, h, cq, cos, sin),)
    return projected


def _index_project(cfg: LlamaConfig, p, h, cq, cos, sin):
    """The lightning indexer's projections of the normed hidden h [..., D]
    and the normed query bottleneck cq [..., q_lora_rank] (DeepSeek-V3.2's):
    (queries [..., Hi, di], a weight a head [..., Hi] float32, the
    position's key [..., di]).  The key is LayerNormed (scale and bias);
    the first ``qk_rope_dim`` columns of queries and key are rotated by the
    attention's own tables, the others are not; the weights carry
    ``Hi^-1/2 x di^-1/2``.  The release's Hadamard rotation of both sides
    and their FP8 quantisation are left out (an orthogonal map of both
    sides leaves every dot product as it was)."""
    a, dt, dr = p["attn"], cfg.dtype, cfg.qk_rope_dim

    def rotated(x, cos, sin):
        return jnp.concatenate([apply_rope_pairs(x[..., :dr], cos, sin),
                                x[..., dr:]], axis=-1)
    q = jnp.einsum("...r,rnh->...nh", cq, a["index_wq"].astype(dt))
    # the key's and the weights' products are kept in float32 as they are
    # accumulated (two narrow matrices): what LayerNorm norms is not
    # rounded first, and the weights never are
    k = jnp.einsum("...d,dh->...h", h, a["index_wk"].astype(dt),
                   preferred_element_type=jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                          + cfg.rms_eps)
    k = (k * a["index_k_norm"] + a["index_k_bias"]).astype(dt)
    w = jnp.einsum("...d,dn->...n", h, a["index_w"].astype(dt),
                   preferred_element_type=jnp.float32) \
        * (cfg.index_heads * cfg.index_head_dim) ** -0.5
    return (rotated(q, cos[..., None, :], sin[..., None, :]), w,
            rotated(k, cos, sin))


def _mla_chunk(cfg: LlamaConfig, p, q_nope, q_rope, pages, layer, row,
               start, length, keep=None):
    """Causal latent attention of a CHUNK of one sequence against its own
    pages: q_nope [S, N, dn], q_rope [S, N, dr] are the queries of positions
    ``start .. start + S - 1`` (``length`` of them real), ``row`` [maxp] the
    sequence's page table, whose pages already hold every position under
    ``start + length`` (the chunk's own, just written, among them).  The
    table's rows are gathered and their keys and values expanded per head
    ONCE (``wkv_b``, as ``_mla_expanded`` expands a whole sequence's: 1.1 GB
    at 128 heads and 17,408 positions), and
    ``ops/latent_prefill.py::latent_chunk_attention`` walks them, queries
    outermost: one kernel that keeps a block's scores, probabilities and
    running statistics in fast memory (interpreted where the backend has no
    compiler for it); the scores ``[N, S, S]`` never exist (8.6 GB at 128 heads
    and 4,096 positions) and nothing past ``start + length`` is visited.
    ``keep`` [S, maxp x page] bool is the selection, a mask a QUERY that all
    heads share and that already holds the causal bound; None: every causal
    position.  Returns [S, N, dv]."""
    from ray_tpu.ops.latent_prefill import latent_chunk_attention
    from ray_tpu.ops.paged_attention import paged_rows
    rank, dn, dt = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.dtype
    rows = paged_rows(pages, layer, row, 0, row.shape[0])        # [T, Wp]
    wkv_b = p["attn"]["wkv_b"].astype(dt)
    # two plain matrix products, a head a run of columns of a position's
    # row: what the kernel reads as it lies (the einsum to [T, N, h] comes
    # out position-minor on the TPU and is copied back, 1.1 GB a layer)
    k, v = (jnp.dot(rows[:, :rank], w.reshape(rank, -1))
            for w in (wkv_b[..., :dn], wkv_b[..., dn:]))
    return latent_chunk_attention(
        q_nope, q_rope, k, v, rows[:, rank:rank + cfg.qk_rope_dim], keep,
        start, length, sm_scale=mla_softmax_scale(cfg))


def _mla_expanded(cfg: LlamaConfig, p, q_nope, q_rope, latent):
    """Causal latent attention over whole sequences with keys and values
    expanded per head from the cache rows: q_nope [B, S, N, dn], q_rope
    [B, S, N, dr], latent [B, S, rank + dr] -> [B, S, N, dv]."""
    rank, dn, dt = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.dtype
    S = latent.shape[1]
    kv = jnp.einsum("bsc,cnh->bsnh", latent[..., :rank],
                    p["attn"]["wkv_b"].astype(dt))
    scores = (jnp.einsum("bqnh,bknh->bnqk", q_nope, kv[..., :dn])
              + jnp.einsum("bqnh,bkh->bnqk", q_rope, latent[..., rank:])) \
        * mla_softmax_scale(cfg)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bnqk,bknh->bqnh", probs, kv[..., dn:])


def _mla_absorbed(cfg: LlamaConfig, p, q_nope, q_rope, pages, layer,
                  lengths, page_table, selected=None):
    """One query a sequence against the paged latent cache AS IT LIES:
    ``wkv_b``'s key half goes into the query, all heads score against the
    one cached row of a position, the probabilities weigh the compressed
    rows themselves, and ``wkv_b``'s value half comes after.  The same
    mathematics as ``_mla_expanded``; no per-head key or value of a cached
    position is ever made.  q_nope [B, N, dn], q_rope [B, N, dr] -> [B, N,
    dv].  ``selected`` (an indexer's ``select_positions``: positions and
    which of them count) limits the read to those rows, gathered through the
    page table, and nothing else of the pool is read."""
    from ray_tpu.ops.paged_attention import (paged_latent_attention,
                                             paged_latent_attention_selected)
    dn, dt = cfg.qk_nope_dim, cfg.dtype
    wkv_b = p["attn"]["wkv_b"].astype(dt)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bnh,cnh->bnc", q_nope, wkv_b[..., :dn])
    q_lat = jnp.concatenate([q_lat, q_rope], axis=-1)
    if selected is not None:
        with jax.named_scope("dsa_read"):
            o_lat = paged_latent_attention_selected(
                q_lat, pages, layer, *selected, page_table,
                sm_scale=mla_softmax_scale(cfg), rank=cfg.kv_lora_rank)
    else:
        o_lat = paged_latent_attention(
            q_lat, pages, layer, lengths, page_table,
            sm_scale=mla_softmax_scale(cfg), rank=cfg.kv_lora_rank)
    with jax.named_scope("mla_absorb"):
        return jnp.einsum("bnc,cnh->bnh", o_lat, wkv_b[..., dn:])


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
        if cfg.hc_mult:
            x = _hc_reduce(x)
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
    return {**layers, "mlp": jnp.arange(
        cfg.num_layers - cfg.first_dense_layers)}, layers["mlp"]


def _scan_layers(cfg: LlamaConfig, params, body, carry, t=None,
                 served: bool = False):
    """``body(experts, carry, xs) -> (carry, ys)`` over the layers in their
    order: the leading dense layers (``params["dense_layers"]``, if the
    model has them; they are few, and unrolled: a scan's slice of a stack
    of one is a copy of it), then the rest, scanned.  ``xs`` is a layer's
    slice of its group or, for a ``served`` model, that slice and the
    layer's index into the pools in pass ``t`` (``_pool_layers``).  Returns
    the last carry and the scanned layers' ``ys`` (the expert layers'
    loads)."""
    if cfg.layer_pattern:
        return (_unrolled_layers if _group_a_layer(cfg)
                else _scan_periods)(cfg, params, body, carry, served)
    first = cfg.first_dense_layers
    layers, experts = _scanned_layers(cfg, params)
    pool_layers = _pool_layers(cfg, t) if served else None
    if first:
        dense = params["dense_layers"]
        if served:
            dense = (dense, pool_layers[:first])
        for i in range(first):
            carry, _ = body(None, carry, jax.tree.map(lambda a: a[i], dense))
    if served:
        layers = (layers, pool_layers[first:] if first else pool_layers)
    return jax.lax.scan(functools.partial(body, experts), carry, layers)


def _scan_periods(cfg: LlamaConfig, params, body, carry, served: bool):
    """``_scan_layers`` for a stack of two kinds of layer in a repeating
    ``cfg.layer_pattern``: the scan goes over PERIODS with the pattern
    unrolled inside; ``params["layers"]`` holds a group a position of the
    pattern, each stacked over the periods, so every layer's weights are the
    scan's own slice of a stack (a slice at any other index the compiler
    answers with a re-laid-out copy of the whole stack, every call).  A
    ``served`` layer gets its index among the layers of ITS KIND beside its
    slice: the full layers' pages and the linear layers' state rows are
    pools of their own."""
    pattern = cfg.layer_pattern
    counts = {kind: pattern.count(kind) for kind in set(pattern)}

    def period(carry, xs):
        groups, at = xs
        seen = dict.fromkeys(counts, 0)
        for kind, p in zip(pattern, groups):
            layer = at * counts[kind] + seen[kind]
            carry, _ = body(None, carry, (p, layer) if served else p)
            seen[kind] += 1
        return carry, None
    periods = cfg.num_layers // len(pattern)
    return jax.lax.scan(period, carry,
                        (params["layers"], jnp.arange(periods)))


def _unrolled_layers(cfg: LlamaConfig, params, body, carry, served: bool):
    """``_scan_layers`` for a stack of two kinds of layer whose feed-forwards
    are experts (all of them, or all behind leading dense layers, which are
    another shape): every
    layer is its own group in ``params["layers"]``, a stack of one, and the
    layers run in a Python loop (an index of 0 into a stack of one moves
    nothing).  An expert layer's experts are its own ``mlp``, handed to
    ``body`` whole beside the index 0 into them, as ``_scanned_layers`` does
    for a scanned stack.  A ``served`` layer gets its index among the layers
    of its kind.  Returns the last carry and the expert layers' ``ys``
    stacked."""
    seen, ys = dict.fromkeys(cfg.layer_pattern, 0), []
    for kind, group in zip(_layer_kinds(cfg), params["layers"]):
        experts = group["mlp"] if "router" in group["mlp"] else None
        p = jax.tree.map(lambda a: a[0], {
            name: leaves for name, leaves in group.items()
            if experts is None or name != "mlp"})
        if experts is not None:
            p["mlp"] = 0             # the layer's index into its own stack
        carry, y = body(experts, carry, (p, seen[kind]) if served else p)
        seen[kind] += 1
        if experts is not None:
            ys.append(y)
    return carry, None if None in ys else jnp.stack(ys)


def _ffn(cfg: LlamaConfig, p, h, live=None, lc=lambda a, ax: a,
         experts=None):
    """The block's feed-forward on the normed hidden ``h`` [..., D]:
    SwiGLU, dense or (``experts``: the stacked experts of all expert
    layers, ``_scanned_layers``) top-k experts without capacity; then
    ``p["mlp"]`` is the layer's index into ``experts``, and ``p["shared"]``
    the layer's shared expert if the model has one.  Returns (y [..., D],
    load): ``load`` [E] int32 counts per expert the assignments of the
    tokens that ``live`` [...] marks (all when None), and is None for a
    dense layer.  A row that ``live`` does not mark (padding, an idle
    slot) is routed to no expert: the experts' products skip it and the
    routed part of its ``y`` is zero, which no live row can tell."""
    dt = cfg.dtype
    if experts is not None:
        from ray_tpu.ops.moe import moe_dropless
        y, load = moe_dropless(
            h.reshape(-1, h.shape[-1]), experts, layer=p["mlp"],
            top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
            live=None if live is None else live.reshape(-1),
            scoring=cfg.router_scoring, routed_scaling=cfg.routed_scaling,
            shared=_cast_leaves(p["shared"], dt, "wgu", "wd")
            if cfg.shared_experts else None,
            first_expert=cfg.expert_share[0] * _held_experts(cfg),
            norm_eps=cfg.router_norm_eps, groups=cfg.expert_groups)
        return y.reshape(h.shape), load
    gu = jnp.einsum("...d,cdm->c...m", h, p["mlp"]["wgu"].astype(dt))
    a = lc(jax.nn.silu(gu[0]) * gu[1], ("batch", "seq", "mlp"))
    return jnp.einsum("...m,md->...d", a, p["mlp"]["wd"].astype(dt)), None


class AttentionState(NamedTuple):
    """What a layer's attention does with its keys and values:
    ``kv(p, layer, pools, q, k, v)`` for K/V pages, ``latent(p, layer, pools,
    q_nope, q_rope, latent)`` for latent pages, ``recurrent(p, layer, pools,
    qkv, g, beta)`` for a linear layer's state row (None: not written for
    them), ``conv(p, layer, pools, u)`` for a conv layer's tail (the
    convolution of ``u`` over time and, served, the hand-over of its last
    inputs).  Each writes, reads and returns ``(o, pools)``.  ``p`` is the
    layer's parameters (a latent kind expands with its ``wkv_b``, a
    recurrent one convolves with its ``conv``), ``layer`` its index into
    ``pools`` among the layers of its kind, and ``pools`` the pair the trunk
    carries from layer to layer and never looks inside: (K pages, V pages);
    a latent model's (latent pages, None); with linear layers the second is
    ``RecurrentPools``, beside K pages or beside latent pages.  A model may
    use two of the three (full layers of either kind of page, and linear
    ones).  The projections come in ``_attention``'s and
    ``_linear_attention``'s layouts; ``g`` is a value a head or, for a decay
    a key channel, ``[..., N, dk]``."""
    kv: Callable
    latent: Optional[Callable] = None
    recurrent: Optional[Callable] = None
    conv: Optional[Callable] = None


class RecurrentPools(NamedTuple):
    """What a model with linear or conv layers keeps where the others keep
    their V pool: that pool (the full layers'; None where those keep latent
    pages, which have no V pool) and, a row a decode SLOT and not
    pages, the linear layers' states ``[linear layers, slots, panels, dk,
    lanes]`` float32 (``ops/linear_attention.py``'s folded layout; None for a
    stack of conv layers, which keep no state matrix) and the
    last inputs of their convolutions ``[linear or conv layers, slots, (K -
    1) * channels]``.  A slot's rows are overwritten whole by the next
    prefill into it: nothing allocates or frees them."""
    v_pages: Optional[jax.Array]
    state: Optional[jax.Array]
    conv: jax.Array


def _pages(pools):
    """The (k_pages, v_pages) that a ``kv`` state reads and writes."""
    if pools is None or not isinstance(pools[1], RecurrentPools):
        return pools
    return pools[0], pools[1].v_pages


def _with_pages(pools, pages):
    """``pools`` with its (k_pages, v_pages) replaced by ``pages``."""
    if pools is None or not isinstance(pools[1], RecurrentPools):
        return pages
    return pages[0], pools[1]._replace(v_pages=pages[1])


def _linear_split(cfg: LlamaConfig, mixed):
    """The convolved channels [..., C] as the rule's float32 heads: q, k
    [..., N, dk], each of unit length (q times dk^-1/2 besides), and v [...,
    N, dv]."""
    from ray_tpu.ops.linear_attention import l2_normalise
    N, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    lead = mixed.shape[:-1]
    q = l2_normalise(mixed[..., :N * dk].reshape(*lead, N, dk)) * dk ** -0.5
    k = l2_normalise(mixed[..., N * dk:2 * N * dk].reshape(*lead, N, dk))
    v = mixed[..., 2 * N * dk:].reshape(*lead, N, dv).astype(jnp.float32)
    return q, k, v


def _linear_sequence(cfg: LlamaConfig, p, qkv, g, beta, length=None):
    """A linear layer over one whole sequence from an empty state, by the
    chunked scan: qkv [S, C] as projected, g, beta [S, N].  Returns (o [S,
    N, dv] float32, the state [N, dk, dv] and the convolution's last inputs
    as they stand after position ``length - 1``; None: after the last)."""
    from ray_tpu.ops.linear_attention import (causal_conv, conv_tail,
                                              gated_delta_chunked,
                                              kda_chunked)
    w = p["linear"]["conv"]
    with jax.named_scope("linear_conv"):
        mixed = causal_conv(qkv, w)
        tail = conv_tail(qkv, qkv.shape[0] if length is None else length,
                         w.shape[0])
    with jax.named_scope("linear_state"):
        rule = kda_chunked if cfg.linear_gate_rank else gated_delta_chunked
        o, state = rule(*_linear_split(cfg, mixed), g, beta, length)
    return o, state, tail


def _ssm_split(cfg: LlamaConfig, mixed):
    """The convolved channels [..., C] of an ssm layer as the rule's float32
    operands: x [..., N, dv] a head, and the ONE key B and ONE query C [...,
    dk] that all heads share."""
    N, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    mixed = mixed.astype(jnp.float32)
    x = mixed[..., :N * dv].reshape(*mixed.shape[:-1], N, dv)
    return x, mixed[..., N * dv:N * dv + dk], mixed[..., N * dv + dk:]


def _ssm_sequence(cfg: LlamaConfig, p, xbc, g, delta, length=None):
    """An ssm layer over one whole sequence from an empty state, by the
    chunked form: xbc [S, C] as projected, g = log a and delta [S, N].
    Returns (y [S, N, dv] float32 with the skip ``D x`` in it, the state [N,
    dk, dv] and the convolution's last inputs as they stand after position
    ``length - 1``; None: after the last).  A padded position writes nothing
    and decays nothing: ``ssm_chunked`` gives it a = 1 and an input of 0."""
    from ray_tpu.ops.linear_attention import (causal_conv, conv_tail,
                                              ssm_chunked)
    a = p["ssm"]
    with jax.named_scope("linear_conv"):
        mixed = causal_conv(xbc, a["conv"], bias=a["conv_bias"])
        tail = conv_tail(xbc, xbc.shape[0] if length is None else length,
                         a["conv"].shape[0])
    with jax.named_scope("linear_state"):
        x, key, query = _ssm_split(cfg, mixed)
        o, state = ssm_chunked(query, key, delta[..., None] * x, g, length)
        return o + a["D"][:, None] * x, state, tail


def _no_cache(cfg: LlamaConfig, attn_fn: Callable, lc) -> AttentionState:
    """The training trunk's: nothing is written, and whole sequences attend
    to themselves by ``attn_fn`` (the flash kernels, or dense)."""
    def kv(p, layer, pools, q, k, v):
        rep = cfg.num_heads // cfg.num_kv_heads
        if rep > 1 and getattr(attn_fn, "_gqa_native", False):
            # Grouped dense path: fold the share-group dim into the einsum
            # — K/V stay at kv_heads width (no jnp.repeat materializing
            # rep copies of the KV tensors in HBM).
            return _checkpoint_name(
                _dense_causal_attention_gqa(q, k, v, rep), "attn_out"), pools
        if rep > 1:   # flash kernel expects equal head counts
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        q = lc(q, ("batch", "heads", "seq", "kv"))
        k = lc(k, ("batch", "heads", "seq", "kv"))
        v = lc(v, ("batch", "heads", "seq", "kv"))
        return _checkpoint_name(attn_fn(q, k, v), "attn_out"), pools

    def recurrent(p, layer, pools, qkv, g, beta):
        sequence = _ssm_sequence if "ssm" in p else _linear_sequence
        return jax.vmap(lambda *row: sequence(cfg, p, *row)[0])(
            qkv, g, beta), pools

    def conv(p, layer, pools, u):
        from ray_tpu.ops.linear_attention import causal_conv
        return jax.vmap(lambda row: causal_conv(
            row, p["conv"]["taps"], silu=False))(u), pools

    # a latent model's is dense: the kernels want equal heads
    return AttentionState(kv, lambda p, layer, pools, *projected: (
        _mla_expanded(cfg, p, *projected), pools), recurrent, conv)


def _prefill_state(cfg: LlamaConfig, length, page_table,
                   flash: bool, slot=0, start=None) -> AttentionState:
    """A sequence's prefill: its rows go to its pages (the padded tail to
    scratch page 0) and it attends over what is in hand; a linear layer
    scans the sequence from an empty state and leaves in the rows of decode
    slot ``slot`` what stands after position ``length - 1``.  With ``start``
    (latent pages alone) the rows are a chunk of the prompt from that
    position on, and they attend over the sequence's PAGES, what earlier
    chunks left there and their own rows (``_mla_chunk``); an indexer's
    keys go to their own pool likewise and its selection is the mask."""
    def kv(p, layer, pools, q, k, v):
        from ray_tpu.ops.flash_attention import flash_attention
        from ray_tpu.ops.paged_attention import prefill_kv
        pools = prefill_kv(*pools, layer, k[0], v[0], length, page_table[0])
        if flash:    # the padded tail lies after every real position
            return flash_attention(q, k, v, True, None, None, None, None,
                                   "bnsh"), pools
        return _dense_causal_attention_gqa(
            q, k, v, cfg.num_heads // cfg.num_kv_heads,
            cfg.block_length), pools

    def latent(p, layer, pools, q_nope, q_rope, latent, index=None):
        from ray_tpu.ops.paged_attention import (index_scores,
                                                 paged_rows, prefill_latent,
                                                 select_mask)
        pool = prefill_latent(pools[0], layer, latent[0], length,
                              page_table[0], start)
        if start is None:
            return _mla_expanded(cfg, p, q_nope, q_rope, latent), \
                (pool, pools[1])
        row, keep, keys = page_table[0], None, pools[1]
        if index is not None:
            q, w, k = index
            S = latent.shape[1]
            with jax.named_scope("dsa_index"):
                keys = prefill_latent(keys, layer, k[0], length, row, start)
                scores = index_scores(q[0], w[0], paged_rows(
                    keys, layer, row, 0, row.shape[0]))
            with jax.named_scope("dsa_select"):
                seen = jnp.arange(scores.shape[1])[None] \
                    <= (start + jnp.arange(S))[:, None]
                keep = select_mask(scores, seen, cfg.index_topk)
        with jax.named_scope("dsa_read" if index is not None
                             else "latent_read"):
            o = _mla_chunk(cfg, p, q_nope[0], q_rope[0], pool, layer, row,
                           start, length, keep)
        return o[None], (pool, keys)

    def recurrent(p, layer, pools, qkv, g, beta):
        from ray_tpu.ops.linear_attention import fold_state
        sequence = _ssm_sequence if "ssm" in p else _linear_sequence
        o, state, tail = sequence(cfg, p, qkv[0], g[0], beta[0], length)
        rows = pools[1]
        with jax.named_scope("linear_state"):
            rows = rows._replace(
                state=jax.lax.dynamic_update_slice(
                    rows.state, fold_state(state)[None, None],
                    (layer, slot, 0, 0, 0)),
                conv=jax.lax.dynamic_update_slice(
                    rows.conv, tail.astype(rows.conv.dtype)[None, None],
                    (layer, slot, 0)))
        return o[None], (pools[0], rows)

    def conv(p, layer, pools, u):
        # the tail is the last inputs before position ``length``, zeros on
        # the left of a prompt shorter than it: whatever the rung pads
        from ray_tpu.ops.linear_attention import causal_conv, conv_tail
        w, rows = p["conv"]["taps"], pools[1]
        tail = conv_tail(u[0], length, w.shape[0])
        rows = rows._replace(conv=jax.lax.dynamic_update_slice(
            rows.conv, tail.astype(rows.conv.dtype)[None, None],
            (layer, slot, 0)))
        return causal_conv(u[0], w, silu=False)[None], (pools[0], rows)
    return AttentionState(kv, latent, recurrent, conv)


def _token_state(cfg: LlamaConfig, pos, page_table) -> AttentionState:
    """One row a sequence: appended at ``pos``, and the pages read as far
    as it; a latent pool as it lies (``_mla_absorbed``); a linear layer's
    state row stepped one position where it lies."""
    def kv(p, layer, pools, q, k, v):
        from ray_tpu.ops.paged_attention import append_kv, paged_attention
        pools = append_kv(*pools, layer, k, v, pos, page_table)
        return paged_attention(q, *pools, layer, pos + 1, page_table), pools

    def latent(p, layer, pools, q_nope, q_rope, latent, index=None):
        from ray_tpu.ops.paged_attention import (append_latent,
                                                 paged_index_scores,
                                                 select_positions)
        pool = append_latent(pools[0], layer, latent, pos, page_table)
        if index is None:
            return _mla_absorbed(cfg, p, q_nope, q_rope, pool, layer,
                                 pos + 1, page_table), (pool, pools[1])
        q, w, k = index
        with jax.named_scope("dsa_index"):
            keys = append_latent(pools[1], layer, k, pos, page_table)
            scores = paged_index_scores(q, w, keys, layer, page_table)
        with jax.named_scope("dsa_select"):
            selected = select_positions(scores, pos + 1, cfg.index_topk)
        return _mla_absorbed(cfg, p, q_nope, q_rope, pool, layer, pos + 1,
                             page_table, selected), (pool, keys)

    def recurrent(p, layer, pools, qkv, g, beta):
        # a row a slot is the slot's own: row b of the batch is slot b.  A
        # parked slot (pos 0) keeps what it holds.
        from ray_tpu.ops.linear_attention import causal_conv_step, step_pool
        rows, live = pools[1], pos > 0
        a = p["ssm" if "ssm" in p else "linear"]
        with jax.named_scope("linear_conv"):
            # (the bias BY NAME and only where the layer has one: the
            # numerics tools plant convolutions of the four-argument form)
            mixed, tail = causal_conv_step(
                qkv, a["conv"], rows.conv[layer],
                **({"bias": a["conv_bias"]} if "conv_bias" in a else {}))
            tail = jnp.where(live[:, None], tail, rows.conv[layer])
        with jax.named_scope("linear_state"):
            if "ssm" in p:       # beta is the step size: no correction
                x, key, query = _ssm_split(cfg, mixed)
                o, state = step_pool(query, key, beta[..., None] * x, g,
                                     None, rows.state, layer, live)
                o = o + a["D"][:, None] * x
            else:
                o, state = step_pool(*_linear_split(cfg, mixed), g, beta,
                                     rows.state, layer, live)
            rows = rows._replace(
                state=state, conv=jax.lax.dynamic_update_index_in_dim(
                    rows.conv, tail, layer, 0))
        return o, (pools[0], rows)

    def conv(p, layer, pools, u):
        # row b of the batch is slot b; a parked slot keeps its tail
        from ray_tpu.ops.linear_attention import causal_conv_step
        rows = pools[1]
        c, tail = causal_conv_step(u, p["conv"]["taps"], rows.conv[layer],
                                   silu=False)
        tail = jnp.where((pos > 0)[:, None], tail, rows.conv[layer])
        return c, (pools[0], rows._replace(
            conv=jax.lax.dynamic_update_index_in_dim(
                rows.conv, tail, layer, 0)))
    return AttentionState(kv, latent, recurrent, conv)


def _block_state(cfg: LlamaConfig, pos0, page_table) -> AttentionState:
    """A block's rows: written at ``pos0 .. pos0 + B - 1`` at every pass,
    and the pages read as far as the block's end, the block both ways."""
    def kv(p, layer, pools, q, k, v):
        from ray_tpu.ops.paged_attention import (append_block_kv,
                                                 paged_block_attention)
        pools = append_block_kv(*pools, layer, k, v, pos0, page_table)
        return paged_block_attention(
            q, *pools, layer, pos0 + cfg.block_length, page_table), pools
    return AttentionState(kv)


def _attention(cfg: LlamaConfig, p, h, cos, sin, state: AttentionState,
               layer, pools):
    """A layer's attention on the normed hidden ``h``: the projections,
    ``_qk``, the ``state``'s write and read, the output projection.  An
    ``h`` with a sequence axis, [B, S, D] (the training trunk, the prefill,
    and the block step, whose rows a slot are that axis), goes head-major, q
    [B, N, S, H] and k, v [B, NKV, S, H]: the flash kernels' native layout,
    picked in the projection's epilogue for free.  The token step's [B, D]
    gives [B, N, H].  Returns (the sublayer's output, the state's pools)."""
    dt, a, rows = cfg.dtype, p["attn"], h.ndim == 3
    if cfg.kv_lora_rank:
        o, pools = state.latent(p, layer, pools,
                                *_mla_project(cfg, p, h, cos, sin))
        return jnp.einsum("bsnh,nhd->bsd" if rows else "bnh,nhd->bd", o,
                          a["wo"].astype(dt)), pools
    q = jnp.einsum("bsd,dnh->bnsh" if rows else "bd,dnh->bnh", h,
                   a["wq"].astype(dt))
    kv = jnp.einsum("bsd,dcnh->bcnsh" if rows else "bd,dcnh->bcnh", h,
                    a["wkv"].astype(dt))
    k, v = kv[:, 0], kv[:, 1]
    q, k = _qk(cfg, p, q, k, cos, sin)
    if cfg.attention_multiplier:     # every read scales by head_dim^-1/2
        q = q * jnp.asarray(
            cfg.attention_multiplier * cfg.head_dim ** 0.5, q.dtype)
    o, pages = state.kv(p, layer, _pages(pools), q, k, v)
    return jnp.einsum("bnsh,nhd->bsd" if rows else "bnh,nhd->bd", o,
                      a["wo"].astype(dt)), _with_pages(pools, pages)


def _linear_attention(cfg: LlamaConfig, p, h, state: AttentionState, layer,
                      pools):
    """A linear layer's attention on ``h`` [..., D] (the gated delta rule;
    transformers' ``Qwen3NextGatedDeltaNet``): the projections (q | k | v
    together, the channels the convolution runs over; the output's gate z;
    the rule's two gates b | a), the ``state``'s convolution, rule and
    write-back, then every head's values RMS-normed with one learned scale,
    gated by ``silu(z)`` and projected out.  Returns (the sublayer's output,
    the state's pools)."""
    from ray_tpu.ops.linear_attention import decay_and_beta
    if state.recurrent is None:
        raise NotImplementedError(
            "models/llama.py: this program's attention state is not "
            "written for linear-attention layers (layer_pattern): the "
            "block step keeps K/V pages only")
    a, dt = p["linear"], cfg.dtype
    N, dv = cfg.linear_heads, cfg.linear_value_dim
    qkv = jnp.einsum("...d,dc->...c", h, a["wqkv"].astype(dt))
    if cfg.linear_gate_rank:
        return _kda_gates_and_output(cfg, p, h, qkv, state, layer, pools)
    z = jnp.einsum("...d,dc->...c", h, a["wz"].astype(dt))
    ba = jnp.einsum("...d,dc->...c", h, a["wba"].astype(dt))
    g, beta = decay_and_beta(ba[..., N:], ba[..., :N], a["A_log"],
                             a["dt_bias"], cfg.linear_neg_eigval)
    o, pools = state.recurrent(p, layer, pools, qkv, g, beta)
    y = _gated_norm(cfg, a["norm"], o, z.reshape(*z.shape[:-1], N, dv))
    return jnp.einsum("...nv,nvd->...d", y, a["wo"].astype(dt)), pools


def _kda_gates_and_output(cfg: LlamaConfig, p, h, qkv, state, layer, pools):
    """``_linear_attention`` from the projected channels ``qkv`` on for Kimi
    Delta Attention (``modeling_kimi.py``'s ``KimiDeltaAttention``): the
    decay a KEY CHANNEL, ``g = -exp(A_log) softplus((h Wf_a) Wf_b +
    dt_bias)`` through a bottleneck (the second product and everything after
    it float32), ``beta = sigmoid(h Wb)`` in (0, 1); the rule; the read-out
    normed and gated by ``sigmoid((h Wg_a) Wg_b)``."""
    from ray_tpu.ops.linear_attention import kda_gate
    a, dt = p["linear"], cfg.dtype
    N, dv = cfg.linear_heads, cfg.linear_value_dim
    with jax.named_scope("kda_gate"):
        f = jnp.einsum("...r,rc->...c",
                       jnp.einsum("...d,dr->...r", h, a["wf_a"].astype(dt)),
                       a["wf_b"].astype(dt),
                       preferred_element_type=jnp.float32)
        g = kda_gate(f, a["A_log"], a["dt_bias"])
        beta = jax.nn.sigmoid(jnp.einsum(
            "...d,dn->...n", h, a["wb"].astype(dt)).astype(jnp.float32))
    z = jnp.einsum("...r,rc->...c",
                   jnp.einsum("...d,dr->...r", h, a["wg_a"].astype(dt)),
                   a["wg_b"].astype(dt))
    o, pools = state.recurrent(p, layer, pools, qkv, g, beta)
    y = _gated_norm(cfg, a["norm"], o, z.reshape(*z.shape[:-1], N, dv),
                    jax.nn.sigmoid)
    return jnp.einsum("...nv,nvd->...d", y, a["wo"].astype(dt)), pools


def _gated_norm(cfg: LlamaConfig, scale, o, z, gate=jax.nn.silu):
    """A linear layer's read-out o [..., N, dv] (float32) RMS-normed over
    each head's values with the one learned ``scale`` [dv], times
    ``gate(z)`` (``silu``; Kimi Delta Attention's is a sigmoid), in the
    compute dtype."""
    with jax.named_scope("linear_gate_norm"):
        return (_rms_norm(o, scale, cfg.rms_eps)
                * gate(z.astype(jnp.float32))).astype(cfg.dtype)


def _ssm_mixer(cfg: LlamaConfig, p, h, state: AttentionState, layer, pools):
    """An ssm layer's mixer on ``h`` [..., D] (Mamba-2's, State Space
    Duality, arXiv:2405.21060; transformers' ``GraniteMoeHybridMambaLayer``
    with one group): ``[z | xBC | dt] = h W_in``; the ``state``'s convolution
    of ``xBC`` with its bias and SiLU, ``[x | B | C]``, the rule ``S_n <- a_n
    S_n + B (delta_n x_n)^T``, ``y_n = S_n^T C + D_n x_n`` with ``delta =
    softplus(dt + dt_bias)`` and ``a = exp(-exp(A_log) delta)`` a head, and
    the write-back; then ``y * silu(z)`` RMS-normed over ALL ``N dv``
    channels at once (the gate FIRST, one norm: not ``_gated_norm``'s norm a
    head, then gate) and projected out.  The state's ``recurrent`` takes the
    step size where the delta rule's takes beta.  Returns (the sublayer's
    output, the state's pools)."""
    if state.recurrent is None:
        raise NotImplementedError(
            "models/llama.py: this program's attention state is not "
            "written for ssm layers (layer_pattern): the block step keeps "
            "K/V pages only")
    a, dt = p["ssm"], cfg.dtype
    inner = cfg.linear_heads * cfg.linear_value_dim
    C = _mixer_channels(cfg, "ssm")
    with jax.named_scope("ssm_proj"):
        zxbcdt = jnp.einsum("...d,dc->...c", h, a["win"].astype(dt))
    z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:inner + C]
    delta = jax.nn.softplus(
        zxbcdt[..., inner + C:].astype(jnp.float32) + a["dt_bias"])
    o, pools = state.recurrent(p, layer, pools, xbc,
                               -jnp.exp(a["A_log"]) * delta, delta)
    with jax.named_scope("linear_gate_norm"):
        y = o.reshape(*o.shape[:-2], inner) \
            * jax.nn.silu(z.astype(jnp.float32))
        y = _rms_norm(y, a["norm"], cfg.rms_eps).astype(dt)
    with jax.named_scope("ssm_proj"):
        return jnp.einsum("...c,cd->...d", y, a["wout"].astype(dt)), pools


def _conv_operator(cfg: LlamaConfig, p, h, state: AttentionState, layer,
                   pools):
    """A conv layer's operator on ``h`` [..., D] (LFM2's gated short
    convolution; transformers' ``Lfm2ShortConv``): ``[B | C | z] = h W_in``
    cut in that order into three of D, ``u = B * z``, the ``state``'s causal
    depthwise convolution of ``u`` over ``linear_conv`` positions (no bias,
    NO activation; served, the tail's hand-over), ``y = C * conv(u)``, ``y
    W_out``.  Returns (the sublayer's output, the state's pools)."""
    if state.conv is None:
        raise NotImplementedError(
            "models/llama.py: this program's attention state is not "
            "written for conv layers (layer_pattern): the block step keeps "
            "K/V pages only")
    a, dt, D = p["conv"], cfg.dtype, cfg.embed_dim
    with jax.named_scope("conv_in"):
        bcz = jnp.einsum("...d,dc->...c", h, a["win"].astype(dt))
        u = bcz[..., :D] * bcz[..., 2 * D:]
    with jax.named_scope("conv_mix"):
        c, pools = state.conv(p, layer, pools, u)
        y = bcz[..., D:2 * D] * c
    with jax.named_scope("conv_out"):
        return jnp.einsum("...c,cd->...d", y, a["wout"].astype(dt)), pools


def _layer(cfg: LlamaConfig, p, x, cos, sin, state: AttentionState, layer,
           pools, live=None, lc=lambda a, ax: a, experts=None):
    """One layer, the same for every program: attention against ``state``
    and the feed-forward, each a ``_sublayer`` of the residual stream ``x``.
    Returns (x, the state's pools, the experts' load)."""
    stream = _stream_axes(cfg)
    if "linear" in p:                # the layer's kind, by its leaves
        def mixer(h):
            return _linear_attention(cfg, p, h, state, layer, pools)
    elif "conv" in p:
        def mixer(h):
            return _conv_operator(cfg, p, h, state, layer, pools)
    elif "ssm" in p:
        def mixer(h):
            return _ssm_mixer(cfg, p, h, state, layer, pools)
    else:
        def mixer(h):
            return _attention(cfg, p, h, cos, sin, state, layer, pools)
    x, pools = _sublayer(cfg, p, 0, x, mixer)
    x = lc(x, stream)
    x, load = _sublayer(cfg, p, 1, x, lambda h: _ffn(
        cfg, p, h, live, lc, experts))
    return lc(x, stream), pools, load


def _block(cfg: LlamaConfig, rules: Optional[LogicalAxisRules],
           attn_fn: Callable, cos, sin, experts, x, p):
    """The training trunk's layer: ``_layer`` with nothing cached."""
    lc = (lambda a, ax: with_logical_constraint(a, rules, ax)) if rules \
        else (lambda a, ax: a)
    return _layer(cfg, p, x, cos, sin, _no_cache(cfg, attn_fn, lc), None,
                  None, lc=lc, experts=experts)[0]


def llama_hidden(params: Dict[str, Any], tokens: jax.Array,
                 cfg: LlamaConfig,
                 rules: Optional[LogicalAxisRules] = None,
                 mesh=None) -> jax.Array:
    """tokens [B, S] int32 -> final hidden [B, S, D] after rms_norm (compute
    dtype) — the trunk without the LM head (see gpt_hidden)."""
    if cfg.block_length:
        raise NotImplementedError(
            "models/llama.py serves its block-diffusion model "
            "(block_length) through llama_prefill / llama_block_step; the "
            "training trunk's attention is causal and the mask-predict "
            "objective is not written")
    if cfg.index_heads:
        raise NotImplementedError(
            "models/llama.py serves its indexer model (index_heads) through "
            "llama_prefill / llama_decode_step, whose attention reads the "
            "selected positions from the pages; the training trunk's "
            "whole-sequence attention is not written for a selection")
    S = tokens.shape[1]
    if not cfg.kv_lora_rank and \
            resolve_attention(cfg.attention, S) == "flash":
        attn_fn = _flash_attention_bnsh(rules, mesh)
    else:
        def attn_fn(q, k, v):
            return _dense_causal_attention_bnsh(q, k, v)
        attn_fn._gqa_native = True

    cos, sin = _rope_tables(cfg, S)
    x = _embed(cfg, params, tokens)
    if rules is not None:
        x = with_logical_constraint(x, rules, _stream_axes(cfg))

    block = functools.partial(_block, cfg, rules, attn_fn, cos, sin)
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

    (x,), _ = _passes(cfg, params, lambda carry, t: _scan_layers(
        cfg, params, lambda experts, c, lp: ((block(experts, c[0], lp),),
                                              None), carry), (x,))
    return x


def _head(cfg: LlamaConfig, params, x, rows: str = "bs"):
    """Logits of the final hidden ``x`` [*rows, D] in the compute dtype: by
    the head's own matrix, or with ``cfg.tie_embeddings`` by the table."""
    if cfg.tie_embeddings:
        logits = jnp.einsum(f"{rows}d,vd->{rows}v", x,
                            params["wte"].astype(cfg.dtype))
    else:
        logits = jnp.einsum(f"{rows}d,dv->{rows}v", x,
                            params["lm_head"].astype(cfg.dtype))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


def llama_forward(params: Dict[str, Any], tokens: jax.Array,
                  cfg: LlamaConfig,
                  rules: Optional[LogicalAxisRules] = None,
                  mesh=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V] (compute dtype; the fused
    loss upcasts inside its reductions, same contract as gpt_forward)."""
    return _head(cfg, params, llama_hidden(params, tokens, cfg, rules, mesh))


# ---------------------------------------------------------------------------
# Paged KV-cache decode (serving path) — LLaMA variant of gpt.py's
# init_paged_cache/gpt_prefill/gpt_decode_step: the training trunk's layer
# against a state that keeps pages.  GQA keeps the pools at kv_heads width,
# and rope is applied at each token's absolute position before the K is
# scattered (the pools hold POST-rope keys) — with cfg.dtype=float32 paged
# greedy decode reproduces llama_forward's token-by-token argmax, which the
# CPU equivalence tests assert.  A latent (MLA) model has ONE pool, of latent
# pages, and ``None`` where the others have their V pool: the steps take and
# return the pair either way.


def llama_init_paged_cache(cfg: LlamaConfig, num_pages: int,
                           page_size: int, dtype: Any = None,
                           slots: int = 0):
    """Zeroed page pools of all layers, of the kind the model's attention
    caches (token-major: see ops.paged_attention): K and V pools ``[L, P,
    page, NKV*H]`` each or, for latent attention, one pool of latent pages
    ``[L, P, page, Wp]`` and None, ``Wp`` = ``kv_lora_rank + qk_rope_dim``
    rounded up to whole 128-lane tiles (576 -> 640: a page of one layer is
    then one run of whole tiles, which the read copies where it lies;
    ops.paged_attention says why), on every backend; the columns past the
    values are zero and stay zero.  ``L`` is a
    layer for every pass of a looped model: ``ut_steps * num_layers``.
    Page 0 is the scratch sink for padded/inactive writes — allocators
    must never hand it out.  With a ``layer_pattern`` the pages are the
    full layers' alone, K/V or latent, and where the V pool would be come
    ``RecurrentPools``: that pool (None beside latent pages), and the linear
    or ssm layers' state and convolution rows for ``slots`` decode slots,
    zeroed
    (an empty state); conv layers keep a convolution tail a slot (the last
    ``linear_conv - 1`` positions of D channels) and no state: None.  A
    latent model with an indexer (``index_heads``) has TWO pools a position:
    the latent pages and, where the V pool would be, the indexer's keys
    ``[L, P, page, index_head_dim]``, addressed by the same page table."""
    dt = dtype or cfg.dtype
    L = cfg.ut_steps * cfg.num_layers - _slot_layers(cfg)
    if cfg.block_length and page_size % cfg.block_length:
        raise ValueError(f"page_size={page_size} must be a multiple of "
                         f"block_length={cfg.block_length}: a block's "
                         "positions lie in one page")
    if cfg.kv_lora_rank:
        from ray_tpu.ops.paged_attention import latent_width
        # an indexer keeps a second row a position, its key, in a pool of
        # its own where the others have their V pool
        pages = jnp.zeros((L, num_pages, page_size, latent_width(
            cfg.kv_lora_rank, cfg.qk_rope_dim)), dt), \
            jnp.zeros((L, num_pages, page_size, cfg.index_head_dim), dt) \
            if cfg.index_heads else None
    else:
        shape = (L, num_pages, page_size, cfg.num_kv_heads * cfg.head_dim)
        pages = jnp.zeros(shape, dt), jnp.zeros(shape, dt)
    if not cfg.layer_pattern:
        return pages
    from ray_tpu.ops.linear_attention import state_shape
    if slots < 1:
        raise ValueError("a model with linear layers keeps a state row a "
                         "decode slot: say how many slots")
    rows = (_slot_layers(cfg), slots)
    if "conv" in cfg.layer_pattern:
        return pages[0], RecurrentPools(pages[1], None, jnp.zeros(
            (*rows, (cfg.linear_conv - 1) * cfg.embed_dim), dt))
    N, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    kind = "ssm" if "ssm" in cfg.layer_pattern else "linear"
    return pages[0], RecurrentPools(
        pages[1],
        jnp.zeros((*rows, *state_shape(N, dk, dv)), jnp.float32),
        jnp.zeros((*rows, (cfg.linear_conv - 1)
                   * _mixer_channels(cfg, kind)), dt))


def llama_serving_params(params: Dict[str, Any],
                         cfg: LlamaConfig) -> Dict[str, Any]:
    """``params`` with every leaf in the dtype ``llama_prefill`` and
    ``llama_decode_step`` read it in, for a caller that keeps the tree
    between calls: the leaves those two cast with ``.astype(cfg.dtype)``
    (embedding table, head, the attention projections, a dense
    feed-forward, a shared expert) are cast here, once, and the casts in
    the steps then cost nothing (``astype`` to an array's own dtype returns
    the array).  Every other leaf is handed back as the caller's own array:
    the norms' scales, a hyper-connection's leaves, the router and the
    stacked experts are read as they are stored (``_rms_norm``, ``_hc_coeff``;
    ``moe_dropless`` casts the few rows of activations to the experts'
    dtype, never the experts: float32 experts stay float32, bfloat16 ones
    bfloat16).  Casting twice is casting once, so the steps return the same
    bits for this tree as for ``params``."""
    dt = cfg.dtype
    matrices = ("wq", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "index_wq",
                "index_wk", "index_w") \
        if cfg.kv_lora_rank else ("wq", "wkv", "wo")

    def cast(tree, *names):          # those of ``names`` that it has
        return _cast_leaves(tree, dt, *(n for n in names if n in tree))

    def group(layers, experts):
        # a linear layer's mixer: its gates' A_log and dt_bias stay f32
        mixer = {"linear": cast(
            layers["linear"], "wqkv", "wz", "wba", "wg_a", "wg_b", "wf_a",
            "wf_b", "wb", "conv", "wo")} \
            if "linear" in layers else \
            {"conv": cast(layers["conv"], "win", "taps", "wout")} \
            if "conv" in layers else \
            {"ssm": cast(layers["ssm"], "win", "conv", "wout")} \
            if "ssm" in layers else \
            {"attn": cast(layers["attn"], *matrices)}
        out = {**layers, **mixer,
               "mlp": layers["mlp"] if experts else
               _cast_leaves(layers["mlp"], dt, "wgu", "wd")}
        if "shared" in layers:
            out["shared"] = _cast_leaves(layers["shared"], dt, "wgu", "wd")
        return out
    dense = {"dense_layers": group(params["dense_layers"], False)} \
        if "dense_layers" in params else {}
    return {**cast(params, "wte", "lm_head"), **dense,
            "layers": tuple(group(g, "router" in g["mlp"])
                            for g in params["layers"])
            if cfg.layer_pattern else group(params["layers"],
                                            bool(cfg.num_experts))}


def _paged_results(logits, k_pages, v_pages, load):
    """(logits, pools) and, from an expert model, the experts' load."""
    if load is None:
        return logits, k_pages, v_pages
    return logits, k_pages, v_pages, load


def llama_prefill_attention(cfg: LlamaConfig, S: int,
                            start: bool = False) -> str:
    """The attention ``llama_prefill`` runs over a padded sequence of S
    positions, "flash", "dense" or "latent_chunk"; ``llama_prefill`` itself
    asks, so what the engine reports is what the program decided.  A call
    with a ``start`` (``start`` says whether it has one; an indexer model's
    always has) is a CHUNK's, over the sequence's pages: "latent_chunk", the
    kernel of ``ops/latent_prefill.py``.  Every other call: what
    ``resolve_attention`` says of ``cfg.attention`` and S, as for
    ``llama_hidden``.  Two kinds of model are dense at every S without a
    ``start``: a block model, whose mask is causal over blocks (the kernel's
    sub-tile walk does not express it), and a latent one, whose q/k heads
    are wider than its v heads; ``flash`` pinned on either raises."""
    unwritten = "block_length" if cfg.block_length else \
        "kv_lora_rank" if cfg.kv_lora_rank else None
    if unwritten and cfg.attention == "flash":
        raise NotImplementedError(
            f"llama_prefill: the flash kernel is not written for a model "
            f"with {unwritten}; leave attention 'auto' or 'dense'")
    if (start or cfg.index_heads) and llama_prefill_chunks(cfg):
        return "latent_chunk"
    if unwritten or resolve_attention(cfg.attention, S) != "flash":
        return "dense"
    return "flash"


def llama_prefill_chunks(cfg: LlamaConfig) -> bool:
    """Whether ``llama_prefill`` takes a ``start``: a model whose whole past
    is addressable by position in latent pages (with or without an indexer's
    keys beside them).  Not one with a recurrent state or a convolution's
    tail, whose scans take no initial row; not K/V pages, for which the read
    of earlier positions is not written; not a block model."""
    return bool(cfg.kv_lora_rank) and not cfg.layer_pattern \
        and not cfg.block_length and cfg.ut_steps == 1


def llama_paged_read(cfg: LlamaConfig, k_pages) -> str:
    """What a step's programs read the pages with, "kernel" or "gather":
    the token step's K/V pages, and a latent model's one pool
    (``paged_latent_attention``), as ``paged_read_kind`` says of the step's
    queries and the pool; a block model's read (``paged_block_attention``)
    is a gather, and so is an indexer model's (its keys through the table,
    then the selected latent rows)."""
    from ray_tpu.ops.paged_attention import paged_read_kind
    if cfg.block_length or cfg.index_heads:  # (the selected rows: a gather)
        return "gather"
    # a latent model's queries are as wide as its pool's padded rows
    head = k_pages.shape[3] if cfg.kv_lora_rank else cfg.head_dim
    return paged_read_kind(jax.ShapeDtypeStruct(
        (1, cfg.num_heads, head), cfg.dtype), k_pages)


def llama_linear_state(cfg: LlamaConfig, v_pages) -> str:
    """What a token step's programs step the linear layers' states with,
    "kernel" or "rule" (``ops/linear_attention.py::state_step_kind`` of the
    rows' pool ``v_pages.state``)."""
    from ray_tpu.ops.linear_attention import state_step_kind
    return state_step_kind(v_pages.state, cfg.linear_heads,
                           cfg.linear_value_dim,
                           "ssm" in cfg.layer_pattern)


def _served_trunk(cfg: LlamaConfig, params, x, cos, sin,
                  state: AttentionState, live, *pools):
    """Every pass over every layer of a served program, ``pools`` carried
    through both loops and written in place: ((x after the final norm,
    *pools), the expert layers' load of the rows ``live`` marks)."""
    def body(experts, carry, inp):
        (x, *kept), (p, layer) = carry, inp
        x, kept, load = _layer(cfg, p, x, cos, sin, state, layer, kept,
                               live, experts=experts)
        return (x, *kept), load

    return _passes(cfg, params, lambda carry, t: _scan_layers(
        cfg, params, body, carry, t, served=True), (x, *pools))


def llama_prefill(params: Dict[str, Any], cfg: LlamaConfig,
                  tokens: jax.Array, length: jax.Array,
                  k_pages: jax.Array, v_pages: Optional[jax.Array],
                  page_table: jax.Array, slot: jax.Array = 0,
                  start: Optional[jax.Array] = None):
    """Prefill ONE padded sequence (see gpt_prefill): the trunk over the
    whole padded length, its causal attention by the flash forward kernel
    or dense as ``llama_prefill_attention`` says (the kernel reads grouped
    k and v where they lie and writes no scores to memory),
    per-layer post-rope K/V scattered into the sequence's pages, f32
    next-token logits at position length-1.  ``tokens`` [1, S] with S a
    multiple of the page size; ``page_table`` [1, maxp];
    ``k_pages``/``v_pages`` [L, P, page, NKV*H], carried through the layer
    scan and written in place; a latent model's ``k_pages`` is its pool of
    latent pages and its ``v_pages`` None.  With ``cfg.block_length`` the
    mask is causal over blocks (``length`` is then a whole number of blocks:
    the prompt's trailing part of a block joins the first generated block)
    and the logits are empty, ``[1, 0]``.  An expert model returns a
    fourth result, ``load`` [expert layers, E] int32: per layer and expert,
    the assignments of the prompt's real positions.  A model with linear
    layers (``v_pages`` is then ``RecurrentPools``) leaves their state after
    position ``length - 1`` in the rows of decode slot ``slot``, whatever the
    rung; every other model takes no notice of ``slot``.

    With ``start`` (a multiple of the page size; ``llama_prefill_chunks``
    says which models take one) ``tokens`` is a CHUNK of the prompt,
    positions ``start .. start + S - 1`` of which ``length`` are real: its
    rows go to the pages of those positions and its queries attend over the
    sequence's pages, everything under ``start`` that earlier calls left
    there and the chunk itself; the logits are those of the chunk's last
    real position.  A prompt of any length is then a row of such calls, each
    no wider than the widest compiled, and a call with ``start`` 0 that
    holds the whole prompt gives what the call without ``start`` gives, to
    the rounding of a blocked softmax.  A model with an indexer always runs
    this way (``start`` 0 where none is given): its selection is a read of
    the pages."""
    S = tokens.shape[1]
    if start is not None and not llama_prefill_chunks(cfg):
        raise NotImplementedError(
            "llama_prefill: a prefill that starts at a position other than "
            "0 reads what lies before it from latent pages; K/V pages are "
            "not read so yet, and a recurrent state or a convolution's tail "
            "cannot be (their scans take no initial row)")
    # the one decision, which the engine reports by the same call
    attention = llama_prefill_attention(cfg, S, start is not None)
    flash = attention == "flash"
    if attention == "latent_chunk" and start is None:    # an indexer's
        start = jnp.int32(0)
    if start is None:
        cos, sin = _rope_tables(cfg, S)
    else:                            # the chunk's rows of the whole table
        cos, sin = (jax.lax.dynamic_slice_in_dim(t, start, S)
                    for t in _rope_tables(cfg, cfg.max_seq_len))
    x = _embed(cfg, params, tokens)
    live = (jnp.arange(S) < length)[None]                # the real positions
    (x, k_pages, v_pages), load = _served_trunk(
        cfg, params, x, cos, sin,
        _prefill_state(cfg, length, page_table, flash, slot, start), live,
        k_pages, v_pages)
    if cfg.block_length:
        # no logits: a block model's first token comes from its first
        # block (llama_block_step), not from the prompt's last position
        return _paged_results(jnp.zeros((1, 0), jnp.float32), k_pages,
                              v_pages, load)
    last = x[0, length - 1]                              # [D]
    logits = _head(cfg, params, last, "").astype(jnp.float32)
    return _paged_results(logits[None], k_pages, v_pages, load)


def llama_decode_step(params: Dict[str, Any], cfg: LlamaConfig,
                      token: jax.Array, pos: jax.Array,
                      k_pages: jax.Array, v_pages: Optional[jax.Array],
                      page_table: jax.Array):
    """One decode step for a BATCH of sequences (see gpt_decode_step).
    ``token``/``pos`` [B]; rope rotates q and the new K at each
    sequence's absolute position; the paged attention's GQA grouping
    keeps K/V at kv_heads width; a latent model appends a position's
    latent row and reads its one pool as it lies (``_mla_absorbed``).
    Inactive slots (pos 0, all-zero page-table row) harmlessly churn
    scratch page 0.  An expert model returns a fourth result, ``load``
    [expert layers, E] int32: per layer and expert, the assignments of the
    live slots (``pos > 0``: a sequence that decodes has a prompt behind
    it).  A linear layer steps the state row of every live slot where it
    lies: row ``b`` of the batch is decode slot ``b``."""
    cos_t, sin_t = _rope_tables(cfg, cfg.max_seq_len)
    if cos_t is None:                # nothing is rotated
        cos = sin = None
    elif cfg.kv_lora_rank:           # one rotated key for all heads
        cos, sin = cos_t[pos], sin_t[pos]                # [B, dr/2]
    else:
        cos, sin = cos_t[pos][:, None], sin_t[pos][:, None]  # [B, 1, H/2]
    x = _embed(cfg, params, token)
    (x, k_pages, v_pages), load = _served_trunk(
        cfg, params, x, cos, sin, _token_state(cfg, pos, page_table),
        pos > 0, k_pages, v_pages)
    logits = _head(cfg, params, x, "b").astype(jnp.float32)
    return _paged_results(logits, k_pages, v_pages, load)


def llama_block_step(params: Dict[str, Any], cfg: LlamaConfig,
                     state, end: jax.Array, k_pages: jax.Array,
                     v_pages: jax.Array, page_table: jax.Array):
    """One pass over a block of ``B = cfg.block_length`` positions a
    sequence, for a BATCH of sequences: the decode step of a model that
    generates by diffusion over blocks.  ``state`` is ``(tokens [S, B]
    int32, masked [S, B] bool, pos0 [S] int32, passes [S] int32)``: the
    block as it stands (``cfg.mask_token`` where ``masked``), the position
    of its first row, and the denoise passes it has had (``block_unmask``
    reads the last two).  ``end`` [S] is the position a sequence's last block
    ends at; a slot with ``pos0 >= end`` (an empty one: 0, 0) is parked on
    scratch page 0.  Row ``i`` is rotated at ``pos0 + i``; the block's K/V
    go to the sequence's OWN positions ``pos0 .. pos0 + B - 1`` at EVERY
    pass, a later pass overwriting an earlier one's, and all B query rows
    see every position under ``pos0 + B``: the block both ways with no mask
    inside it, and everything committed before it.  So a pass on a block
    with masks left is a denoise pass, whose K/V nobody keeps, and the
    pass on a block without any is the commit pass: what it leaves in the
    pages is the K/V of the finished tokens, and the next block starts
    above them.  Returns ``(logits [S, B, V] float32, k_pages, v_pages)``
    and, from an expert model, ``load``; ``logits[s, i]`` predicts the token
    AT ``pos0 + i`` (mask-predict, no shift)."""
    tokens, _, pos0, _ = state
    live = pos0 < end
    pos0 = jnp.where(live, pos0, 0)
    at = pos0[:, None] + jnp.arange(cfg.block_length)    # [S, B]
    cos_t, sin_t = _rope_tables(cfg, cfg.max_seq_len)
    cos, sin = (None, None) if cos_t is None else \
        (cos_t[at][:, None], sin_t[at][:, None])         # [S, 1, B, H/2]
    x = _embed(cfg, params, tokens)                      # [S, B, D]
    (x, k_pages, v_pages), load = _served_trunk(
        cfg, params, x, cos, sin, _block_state(cfg, pos0, page_table),
        jnp.broadcast_to(live[:, None], tokens.shape), k_pages, v_pages)
    with jax.named_scope("lm_head"):
        logits = _head(cfg, params, x, "sb").astype(jnp.float32)
    return _paged_results(logits, k_pages, v_pages, load)


def block_unmask(cfg: LlamaConfig, logits: jax.Array, state,
                 end: jax.Array) -> Dict[str, Any]:
    """What a pass's ``logits`` [S, B, V] do to the blocks of ``state``
    (``llama_block_step``'s), greedy, on the device: the next state and what
    the host is told of the pass.

    A block with masks left was denoised: ``x0 = argmax(logits)``, its
    confidence the softmax's value there (float32), and of the masked
    positions the ``n_t = B // T + (t < B % T)`` most confident are unmasked
    to their ``x0`` (``T = cfg.denoise_steps``, ``t`` the block's passes so
    far; ties to the lower position; all that are left if fewer), or, with
    a ``cfg.confidence_threshold`` (SDAR's ``low_confidence_dynamic``),
    every masked position above it where those are at least ``n_t``.  Which
    positions are masked is the state's own boolean, never a comparison
    with ``cfg.mask_token``: an argmax or a prompt may hit that id.  A block
    without masks was committed by this pass: its tokens are ``emitted``,
    ``pos0`` moves a block on and a fresh block starts, all masks.  A parked
    slot (``pos0 >= end``) keeps its state.  Returns ``{"state": the next
    state, "live" [S], "committed" [S] bool, "emitted" [S, B] (the block as
    it stood), "passes" [S] (the denoise passes a committed block took),
    "by_threshold" / "by_count" [S] (positions this pass unmasked by either
    rule)}``."""
    B, T = cfg.block_length, cfg.denoise_steps
    tokens, masked, pos0, passes = state
    with jax.named_scope("block_unmask"):
        live = pos0 < end
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # [S, B]
        peak = jnp.max(logits, axis=-1, keepdims=True)
        conf = 1.0 / jnp.sum(jnp.exp(logits - peak), axis=-1)
        conf = jnp.where(masked, conf, -jnp.inf)
        n_t = B // T + (passes < B % T)                          # [S]
        order = jnp.argsort(-conf, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        counted = masked & (rank < n_t[:, None])
        high = masked & (conf > cfg.confidence_threshold) \
            if cfg.confidence_threshold else jnp.zeros_like(masked)
        dynamic = (jnp.sum(high, axis=-1) >= n_t) \
            if cfg.confidence_threshold else jnp.zeros_like(live)
        unmask = jnp.where(dynamic[:, None], high, counted)
        commit = live & ~jnp.any(masked, axis=-1)
        denoise = (live & ~commit)[:, None]
        step = commit[:, None]
        nxt = (jnp.where(step, cfg.mask_token,
                         jnp.where(denoise & unmask, x0, tokens)),
               jnp.where(step, True, masked & ~(denoise & unmask)),
               pos0 + B * commit,
               jnp.where(commit, 0, passes + (live & ~commit)))
        done = jnp.sum(denoise & unmask, axis=-1, dtype=jnp.int32)
        return {"state": nxt, "live": live, "committed": commit,
                "emitted": tokens, "passes": passes,
                "by_threshold": jnp.where(dynamic, done, 0),
                "by_count": jnp.where(dynamic, 0, done)}


def served(config: Optional[LlamaConfig] = None, seq: int = 0):
    """The serving engine's record of this model (``models/serving.py``):
    a block model's step is a block's pass, and what it feeds the next is
    ``block_unmask``'s state, the [slots, B, V] logits no result."""
    from ray_tpu.models.serving import ServedModel, greedy
    cfg = config or LlamaConfig.tiny(seq=seq)
    return ServedModel(
        config=cfg, init=llama_init, stored=llama_serving_params,
        new_pools=functools.partial(llama_init_paged_cache, cfg),
        slot_rows=(lambda k_pages, v_pages: tuple(
            a for a in (v_pages.state, v_pages.conv) if a is not None))
        if cfg.layer_pattern else None,
        conv_tails=(lambda k_pages, v_pages: v_pages.conv)
        if cfg.layer_pattern else None,
        page_kind="latent" if cfg.kv_lora_rank else "kv",
        chunked=llama_prefill_chunks(cfg),
        index_pool=(lambda k_pages, v_pages: v_pages)
        if cfg.index_heads else None,
        select_topk=cfg.index_topk,
        expert_stack=(lambda params: _expert_stack(cfg, params))
        if cfg.num_experts else None,
        prefill=llama_prefill,
        step=llama_block_step if cfg.block_length else llama_decode_step,
        prefill_attention=llama_prefill_attention,
        paged_read=llama_paged_read,
        linear_state=llama_linear_state
        if {"linear", "ssm"} & set(cfg.layer_pattern) else None,
        block=cfg.block_length,
        feed=(lambda cfg, logits, state, end: (
            None, block_unmask(cfg, logits, state, end)))
        if cfg.block_length else greedy)


def _expert_stack(cfg: LlamaConfig, params) -> Dict[str, Any]:
    """One stack of routed experts as the tree stores it ({"wgu", "wd",
    "router", ...}): all expert layers', or, where every layer is a group of
    its own, the first expert layer's."""
    if not cfg.layer_pattern:
        return params["layers"]["mlp"]
    return params["layers"][cfg.first_dense_layers]["mlp"]


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
    if cfg.layer_pattern:
        raise NotImplementedError(
            "models/llama.py serves its model with linear-attention, conv "
            "or ssm layers (layer_pattern) and runs llama_forward, but does "
            "not train it: the chunked scan's backward pass is not written "
            "(nor the state-space rule's), and neither is a pattern stack's "
            "remat and sharding")
    if cfg.ut_steps > 1:
        raise NotImplementedError(
            "models/llama.py serves its looped model (ut_steps > 1) but "
            "does not train it: the expectation over exit steps with its "
            "entropy term, and the exit gate it trains, are not written")
    if cfg.hc_mult:
        raise NotImplementedError(
            "models/llama.py serves its hyper-connected model (hc_mult) "
            "but does not train it: the widened stream's remat and "
            "sharding are not written")
    if cfg.num_experts:
        raise NotImplementedError(
            "models/llama.py serves its expert model but does not train "
            "it: the router's load-balancing loss is not written")
    if cfg.logits_scaling != 1.0:
        raise NotImplementedError(
            "models/llama.py's fused loss reads the head's products as they "
            "are: logits_scaling is not written for it")
    toks = batch["tokens"]
    targets = toks[:, 1:]
    x = llama_hidden(params, toks[:, :-1], cfg, rules, mesh)
    ll = ce_head_loglike_sum(
        x, params["wte" if cfg.tie_embeddings else "lm_head"].astype(
            cfg.dtype), targets, cfg.ce_block,
        "vd" if cfg.tie_embeddings else "dv")
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
