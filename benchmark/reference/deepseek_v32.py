"""DeepSeek-V3.2-Exp's forward pass in plain ``jax.numpy``: float32, highest
matmul precision, no kernels, no cache, no absorption, no chunks, no
batching of sequences into steps.  The selection is a mask.

Follows the published ``config.json`` (``model_type`` ``deepseek_v32``) and
the release's description of its parts: DeepSeek-V3's latent attention with
YaRN positions, the lightning indexer of DeepSeek Sparse Attention, and
DeepSeek-V3's ``noaux_tc`` routing limited to groups.  A layer, pre-norm
RMSNorm, residual stream ``x`` [S, D]:

1. Latent attention: ``qr = RMSNorm(h W_qa)``; ``q = qr W_qb``, heads of
   ``[nope | rope]``; ``[c | k_r] = h W_kva``; ``c = RMSNorm(c)``; ``k_r``
   and each head's ``q[rope]`` rotated at the position, pairs (2i, 2i+1), by
   the YaRN table; ``[k_h | v_h] = c W_kvb``; score of head h = ``(q_h[nope]
   . k_h + q_h[rope] . k_r) x (nope + rope)^-1/2 x m^2`` with ``m = 0.1
   mscale_all_dim ln(factor) + 1``.
2. The indexer: ``q_I = qr W^I_q``, ``index_n_heads`` heads of
   ``index_head_dim`` whose FIRST ``qk_rope_head_dim`` columns are rotated
   by the same table; ``k_I = LayerNorm(h W^I_k)`` (scale and bias), one key
   a position, rotated the same way; ``w = (h W^I_w) x heads^-1/2 x
   head_dim^-1/2``; ``I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])`` for
   ``s <= t``; ``S_t`` = the positions of the ``min(index_topk, t + 1)``
   largest ``I[t, .]`` (a tie to the lower position).
3. The read: the softmax of 1 runs over ``s in S_t`` only; ``o_h = sum p
   v_h``; output ``concat(o_h) W_o``.
4. Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU.  The
   others: ``s = sigmoid(h W_r)`` over the PUBLISHED number of routed
   experts; ``s + bias`` in ``n_group`` groups, a group's mark the sum of its
   two largest, the ``topk_group`` best groups kept, the
   ``num_experts_per_tok`` largest of the kept groups chosen; gates ``g =
   s[chosen] / (sum s[chosen] + 1e-20) x routed_scaling_factor``; ``sum g_e
   E_e(h) + E_shared(h)`` over the experts HELD (``expert_share``: one
   chip's share of the layer; what the others would add is left out, as the
   program leaves it out).
5. The final RMSNorm and the untied head.

Reads the program's parameter tree (``dense_layers`` and ``layers`` stacked
on a leading dimension, the indexer's leaves ``index_*`` beside the
attention's) in whatever dtype it is stored and upcasts IN PIECES: a head of
the attention and of the indexer at a time, the dense feed-forward a block of
its width at a time, the routed experts one at a time, inside ``lax.scan``s,
so that at the published size no float32 copy of more than a few hundred MB
is alive (an expert layer whole would be 3.8 GB beside an engine that holds
11.4 of the chip's 16) and 6k positions fit.  Shares no code with
``ray_tpu/models`` or ``ray_tpu/ops``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.xing import mscale, yarn_angles

DENSE_BLOCK = 2048                   # of a dense feed-forward's width


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    x = x - x.mean(-1, keepdims=True)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale + bias


def _rotate(x, angle):
    """x [S, dim]: pair (2i, 2i+1) rotated by the position's angle [S,
    dim/2], in place."""
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _rotate_first(x, angle):
    """The first ``2 x angle.shape[-1]`` columns rotated, the others not."""
    width = 2 * angle.shape[-1]
    return jnp.concatenate([_rotate(x[..., :width], angle), x[..., width:]],
                           axis=-1)


def selection(h, qr, attn, angle, config):
    """The indexer's choice: h [S, D], qr [S, q_lora_rank] -> keep [S, S]
    bool, row t the positions ``S_t``."""
    S = h.shape[0]
    heads, width = config["index_n_heads"], config["index_head_dim"]
    k = _rotate_first(_layer_norm(
        h @ attn["index_wk"].astype(jnp.float32),
        attn["index_k_norm"].astype(jnp.float32),
        attn["index_k_bias"].astype(jnp.float32), config["rms_norm_eps"]),
        angle)
    w = h @ attn["index_w"].astype(jnp.float32) * (heads * width) ** -0.5

    def head(total, part):
        wq, w_j = part                              # [rq, width], [S]
        q = _rotate_first(qr @ wq.astype(jnp.float32), angle)
        return total + w_j[:, None] * jax.nn.relu(q @ k.T), None
    scores, _ = jax.lax.scan(
        head, jnp.zeros((S, S), jnp.float32),
        (jnp.moveaxis(attn["index_wq"], 1, 0), w.T))
    causal = jnp.tril(jnp.ones((S, S), bool))
    keep = min(config["index_topk"], S)
    _, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), keep)
    chosen = jnp.zeros((S, S), bool).at[jnp.arange(S)[:, None], at].set(True)
    return chosen & causal


def attention(h, attn, config):
    """h [S, D] -> (the sublayer's output [S, D], keep [S, S])."""
    eps, scaling = config["rms_norm_eps"], config["rope_scaling"]
    rank, nope, rope = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                        config["qk_rope_head_dim"])
    S = h.shape[0]
    angle = jnp.asarray(yarn_angles(S, rope, float(config["rope_theta"]),
                                    scaling), jnp.float32)
    if mscale(scaling["factor"], scaling["mscale"]) != \
            mscale(scaling["factor"], scaling["mscale_all_dim"]):
        raise ValueError("written for mscale = mscale_all_dim: the tables "
                         "are not rescaled")
    scale = (nope + rope) ** -0.5
    if scaling["mscale_all_dim"]:
        scale *= mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    qr = _rms_norm(h @ attn["wq_a"].astype(jnp.float32),
                   attn["q_a_norm"].astype(jnp.float32), eps)
    ckr = h @ attn["wkv_a"].astype(jnp.float32)
    c = _rms_norm(ckr[:, :rank], attn["kv_a_norm"].astype(jnp.float32), eps)
    k_r = _rotate(ckr[:, rank:], angle)
    keep = selection(h, qr, attn, angle, config)

    def head(total, part):
        wq, wkv, wo = _f32(part)        # [rq, nope+rope], [rank, nope+v], ..
        q = qr @ wq
        kv = c @ wkv
        scores = (q[:, :nope] @ kv[:, :nope].T
                  + _rotate(q[:, nope:], angle) @ k_r.T) * scale
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return total + (probs @ kv[:, nope:]) @ wo, None
    out, _ = jax.lax.scan(
        head, jnp.zeros_like(h),
        (jnp.moveaxis(attn["wq_b"], 1, 0), jnp.moveaxis(attn["wkv_b"], 1, 0),
         attn["wo"]))
    return out, keep


def swiglu(h, wgu, wd):
    gate, up = jnp.einsum("sd,cdm->csm", h, wgu)
    return (jax.nn.silu(gate) * up) @ wd


def dense(h, mlp):
    """A dense SwiGLU, a block of its width at a time."""
    width = mlp["wd"].shape[0]
    block = DENSE_BLOCK if width % DENSE_BLOCK == 0 else width

    def part(total, ws):
        wgu, wd = _f32(ws)
        return total + swiglu(h, wgu, wd), None
    total, _ = jax.lax.scan(part, jnp.zeros_like(h), (
        jnp.moveaxis(mlp["wgu"].reshape(2, h.shape[1], -1, block), 2, 0),
        mlp["wd"].reshape(-1, block, h.shape[1])))
    return total


def gate_matrix(h, router, bias, config):
    """h [S, D] -> [S, R]: each token's gates at its chosen experts of the R
    the router scores, zero elsewhere; the choice inside ``topk_group`` of
    ``n_group`` groups."""
    scores = jax.nn.sigmoid(h @ router)
    S, R = scores.shape
    groups, best = config["n_group"], config["topk_group"]
    choice = scores + bias
    grouped = choice.reshape(S, groups, R // groups)
    mark = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)          # [S, groups]
    kept = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None], jax.lax.top_k(mark, best)[1]].set(True)
    choice = jnp.where(jnp.repeat(kept, R // groups, axis=1), choice,
                       -jnp.inf)
    _, chosen = jax.lax.top_k(choice, config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * config["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(S)[:, None],
                                     chosen].set(picked)


def routed(h, gates, wgu, wd):
    """The held experts on every token of h [S, D], weighed by gates [S, E
    held]; one expert at a time, upcast there."""
    def expert(total, part):
        wgu_e, wd_e, gate = part
        return total + gate[:, None] * swiglu(h, *_f32((wgu_e, wd_e))), None
    total, _ = jax.lax.scan(expert, jnp.zeros_like(h), (wgu, wd, gates.T))
    return total


def held_share(config: dict):
    """(first held expert, experts held) of the router's published width."""
    share, _ = config["expert_share"]
    held = config["n_routed_experts"]
    return share * held, held


def forward(params, tokens, config, rows=None, with_selection=False):
    """tokens [S] -> logits [S, V] float32, or with ``rows`` = (first, count)
    those of positions ``first .. first + count - 1`` alone (the head over 6k
    positions is 400 MB nobody compares; ``first`` may be traced);
    ``with_selection`` adds every layer's keep mask [layers, S, S] bool."""
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        first, held = held_share(config)

        def layer(x, lp):
            norm = lambda name: lp[name]["scale"].astype(jnp.float32)  # noqa
            y, keep = attention(_rms_norm(x, norm("ln1"), eps), lp["attn"],
                                config)
            x = x + y
            h, mlp = _rms_norm(x, norm("ln2"), eps), lp["mlp"]
            if "router" not in mlp:                # a leading dense layer
                return x + dense(h, mlp), keep
            gates = gate_matrix(h, mlp["router"].astype(jnp.float32),
                                mlp["router_bias"].astype(jnp.float32),
                                config)[:, first:first + held]
            return x + routed(h, gates, mlp["wgu"], mlp["wd"]) \
                + dense(h, lp["shared"]), keep

        x = params["wte"][tokens].astype(jnp.float32)
        kept = []
        for group in ("dense_layers", "layers"):
            if group in params:
                x, keep = jax.lax.scan(layer, x, params[group])
                kept.append(keep)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, *rows)
        x = _rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
        logits = x @ params["lm_head"].astype(jnp.float32)
        return (logits, jnp.concatenate(kept)) if with_selection else logits
