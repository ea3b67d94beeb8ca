"""Xing4.0-29B-A4B's forward pass in plain ``jax.numpy``: float32, highest
matmul precision, no kernels, no cache, no absorption, no batching of
sequences into steps.

Follows the published ``config.json`` (``model_type`` ``xing4_0``) and, for
what its keys name, the published descriptions of the mechanisms: DeepSeek-V2
and -V3's latent attention with YaRN positions (``modeling_deepseek.py``),
DeepSeek-V3's ``noaux_tc`` sigmoid routing with a shared expert, and
manifold-constrained hyper-connections (arXiv:2512.24880) on the residual
path.  ``x`` is the residual state of a position, ``[n, C]`` with ``n`` =
``hc_mult``; ``x_0`` is the embedding row repeated ``n`` times.

1. Around EVERY sublayer ``F`` (attention, then the feed-forward), with its
   own parameters: ``u = RMSNorm(vec(x))`` over all ``n C`` values, no
   learned scale; ``pre~ = a_pre (u P_pre) + b_pre`` [n], ``post~ = a_post
   (u P_post) + b_post`` [n], ``res~ = a_res mat(u P_res) + b_res`` [n, n];
   ``H_pre = sigmoid(pre~)``, ``H_post = 2 sigmoid(post~)``, ``H_res =
   Sinkhorn(exp(clip(res~, clamp_min, clamp_max)))``: ``hc_sinkhorn_iters``
   rounds of dividing each row by (its sum + ``hc_eps``) and then each
   column by (its sum + ``hc_eps``); ``h = H_pre x`` [C]; ``y =
   F(RMSNorm_learned(h))``; ``x' = H_res x + H_post^T y``.
2. Latent attention: ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb``, heads of
   ``[nope | rope]``; ``[c | r] = h W_kva``; ``c = RMSNorm(c)``; ``r`` and
   each head's ``q[rope]`` rotated at the position, pairs (2i, 2i+1), by the
   YaRN table; ``[k_h | v_h] = c W_kvb``; score of head h = ``(q_h[nope] .
   k_h + q_h[rope] . r) x (nope + rope)^-1/2 x m^2`` with ``m = 0.1
   mscale_all_dim ln(factor) + 1``; causal softmax; ``o_h = sum p v_h``;
   output ``concat(o_h) W_o``.
3. Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU.  Every
   other layer: ``s = sigmoid(h W_r)`` over the routed experts; the
   ``num_experts_per_tok`` experts with the largest ``s + bias``; gates ``g =
   s[chosen] / (sum s[chosen] + 1e-20) x routed_scaling_factor`` (the sum
   only with ``norm_topk_prob``); ``sum g_e E_e(h) + E_shared(h)``, SwiGLU
   experts, no token dropped.
4. After the last layer the ``n`` rows are summed, then the final RMSNorm
   and the untied head.

Assumed (the configuration's file lists each with its reason): the embedding
repeated to ``n`` rows and the rows summed at the end; ``hc_eps`` as the
Sinkhorn denominators' guard and ``rms_norm_eps`` in the ``n C``-wide norm;
the clamp before ``exp``; the learned RMSNorm on ``h`` inside ``F``.

Reads the program's parameter tree (``dense_layers`` and ``layers`` stacked
on a leading dimension; a hyper-connection's three projections side by side
in ``proj`` [n C, 2n + n n] as pre | post | res, ``alpha`` and ``bias``
alike) in whatever dtype it is stored, and upcasts a layer at a time
inside a ``lax.scan`` over each stack, the routed experts a block at a time
inside another, so that at the published size one layer's slice and one
block's float32 copy are alive and never the 19 GB.  Shares no code with
``ray_tpu/models`` or ``ray_tpu/ops``.
"""

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 8


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def yarn_angles(seq, dim, theta, scaling):
    """Angles [seq, dim/2] of the rotary pairs, float64: DeepSeek-V3's YaRN
    (``scaling`` is the config's ``rope_scaling``)."""
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq = theta ** -exponent
    factor, orig = scaling["factor"], \
        scaling["original_max_position_embeddings"]

    def correction(turns):   # the pair that turns so often over `orig`
        return dim * np.log(orig / (turns * 2 * np.pi)) \
            / (2 * np.log(theta))
    low = max(np.floor(correction(scaling["beta_fast"])), 0)
    high = min(np.ceil(correction(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    freq = freq / factor * ramp + freq * (1 - ramp)
    return np.arange(seq, dtype=np.float64)[:, None] * freq


def mscale(factor, m):
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def _rotate(x, angle, m):
    """x [B, S, ..., dim]: rotate pair (2i, 2i+1) by the position's angle;
    cos and sin times ``m``."""
    angle = angle.reshape(angle.shape[0], *(1,) * (x.ndim - 3),
                          angle.shape[1])
    cos = jnp.asarray(np.cos(angle) * m, jnp.float32)
    sin = jnp.asarray(np.sin(angle) * m, jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def sinkhorn(m, iters, eps):
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hyper_coefficients(x, hp, config):
    """x [B, S, n, C] -> H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n,
    n]."""
    n = x.shape[-2]
    flat = x.reshape(*x.shape[:-2], -1)
    u = _rms_norm(flat, 1.0, config["rms_norm_eps"])
    p_pre, p_post, p_res = jnp.split(hp["proj"], [n, 2 * n], axis=-1)
    b_pre, b_post, b_res = jnp.split(hp["bias"], [n, 2 * n])
    a_pre, a_post, a_res = hp["alpha"]
    pre = jax.nn.sigmoid(a_pre * (u @ p_pre) + b_pre)
    post = 2.0 * jax.nn.sigmoid(a_post * (u @ p_post) + b_post)
    res = (a_res * (u @ p_res) + b_res).reshape(*u.shape[:-1], n, n)
    res = jnp.exp(jnp.clip(res, config["mhc_h_res_clamp_min"],
                           config["mhc_h_res_clamp_max"]))
    return pre, post, sinkhorn(res, config["hc_sinkhorn_iters"],
                               config["hc_eps"])


def hyper_connected(x, hp, norm_scale, config, sublayer):
    pre, post, res = hyper_coefficients(x, hp, config)
    h = jnp.einsum("bsn,bsnc->bsc", pre, x)
    y = sublayer(_rms_norm(h, norm_scale, config["rms_norm_eps"]))
    return jnp.einsum("bsij,bsjc->bsic", res, x) \
        + post[..., None] * y[..., None, :]


def attention(h, attn, config):
    eps, scaling = config["rms_norm_eps"], config["rope_scaling"]
    rank, nope, rope = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                        config["qk_rope_head_dim"])
    seq = h.shape[1]
    angle = yarn_angles(seq, rope, float(config["rope_theta"]), scaling)
    scale = (nope + rope) ** -0.5
    m = mscale(scaling["factor"], scaling["mscale"]) \
        / mscale(scaling["factor"], scaling["mscale_all_dim"])
    if scaling["mscale_all_dim"]:
        scale *= mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    c_q = _rms_norm(h @ attn["wq_a"], attn["q_a_norm"], eps)
    q = jnp.einsum("bsr,rnh->bsnh", c_q, attn["wq_b"])
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], angle, m)
    ckr = h @ attn["wkv_a"]
    c = _rms_norm(ckr[..., :rank], attn["kv_a_norm"], eps)
    r = _rotate(ckr[..., rank:], angle, m)             # one for all heads
    kv = jnp.einsum("bsc,cnh->bsnh", c, attn["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bqnh,bknh->bnqk", q_nope, k_nope)
              + jnp.einsum("bqnh,bkh->bnqk", q_rope, r)) * scale
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bnqk,bknh->bqnh", probs, v)
    return jnp.einsum("bqnh,nhd->bqd", o, attn["wo"])


def swiglu(h, wgu, wd):
    gate, up = jnp.einsum("...d,cdm->c...m", h, wgu)
    return (jax.nn.silu(gate) * up) @ wd


def gate_matrix(h, router, bias, config):
    """h [T, D] -> [T, E]: each token's gates at its chosen experts, zero
    elsewhere."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * config["routed_scaling_factor"]
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def routed(h, gates, wgu, wd):
    """Every expert on every token of h [T, D], weighed by gates [T, E]; a
    block of experts at a time, upcast there."""
    blocks = wgu.shape[0] // EXPERT_BLOCK

    def block(total, part):
        wgu_b, wd_b, gates_b = part
        each = jax.vmap(lambda g, d: swiglu(h, g, d))(_f32(wgu_b),
                                                       _f32(wd_b))
        return total + jnp.einsum("etd,te->td", each, gates_b), None
    split = lambda a: a.reshape(blocks, EXPERT_BLOCK, *a.shape[1:])  # noqa
    total, _ = jax.lax.scan(
        block, jnp.zeros_like(h),
        (split(wgu), split(wd), split(gates.T).transpose(0, 2, 1)))
    return total


def forward(params, tokens, config, with_gates=False):
    """tokens [B, S] -> logits [B, S, V], float32; ``with_gates`` adds the
    expert layers' gate matrices [layers, B, S, E]."""
    with jax.default_matmul_precision("highest"):
        n = config["hc_mult"]
        batch, seq = tokens.shape

        def layer(x, lp):
            """One layer on the state x [B, S, n, C]; ``lp`` its slice of
            the stack, upcast here (its routed experts a block at a time,
            in ``routed``)."""
            mlp = lp["mlp"]
            lp = _f32({k: v for k, v in lp.items() if k != "mlp"})
            x = hyper_connected(
                x, lp["hc_attn"], lp["ln1"]["scale"], config,
                lambda h: attention(h, lp["attn"], config))
            gates = None

            def feed_forward(h):
                nonlocal gates
                if "router" not in mlp:            # a leading dense layer
                    return swiglu(h, *_f32((mlp["wgu"], mlp["wd"])))
                flat = h.reshape(-1, h.shape[-1])
                gates = gate_matrix(flat, _f32(mlp["router"]),
                                    _f32(mlp["router_bias"]), config)
                y = routed(flat, gates, mlp["wgu"], mlp["wd"]) \
                    + swiglu(flat, lp["shared"]["wgu"], lp["shared"]["wd"])
                return y.reshape(h.shape)
            x = hyper_connected(x, lp["hc_mlp"], lp["ln2"]["scale"], config,
                                feed_forward)
            return x, None if gates is None else gates.reshape(batch, seq,
                                                               -1)

        x = params["wte"][tokens].astype(jnp.float32)
        x = jnp.repeat(x[:, :, None, :], n, axis=2)            # [B, S, n, C]
        # a scan a group, so that one layer of a stack is alive at a time
        for group in ("dense_layers", "layers"):
            if group in params:
                x, chosen = jax.lax.scan(layer, x, params[group])
        x = _rms_norm(x.sum(axis=2), params["ln_f"]["scale"].astype(
            jnp.float32), config["rms_norm_eps"])
        logits = x @ params["lm_head"].astype(jnp.float32)
        return (logits, chosen) if with_gates else logits
