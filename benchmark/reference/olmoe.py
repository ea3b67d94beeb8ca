"""OLMoE-1B-7B's forward pass in plain ``jax.numpy``: float32, highest
matmul precision, no kernels, cache, scan or sort.

Follows Muennighoff et al. 2024 and ``modeling_olmoe.py`` of the Hugging
Face implementation that the published ``config.json`` configures:

1. ``h = RMSNorm(x)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``, no bias;
2. ``q = RMSNorm_q(q)``, ``k = RMSNorm_k(k)``: a learned scale over the
   WHOLE projection, before it is split into heads (``config.json`` has no
   key for it; ``clip_qkv`` is null and unused);
3. heads of ``head_dim``, q and k rotated (the half-split ``rotate_half``
   convention), causal attention, ``x += o Wo``;
4. ``h = RMSNorm(x)``; router logits ``h Wr`` and their softmax over all
   experts; the ``k`` largest probabilities are the gates, as they are
   unless ``norm_topk_prob``; ``x += sum_e gate_e Wd_e(silu(Wg_e h) * Wu_e
   h)`` over the chosen experts.  No token is dropped, no shared expert;
5. after the last layer ``RMSNorm``, then the untied head.

Every expert is applied to every token, a block of tokens at a time, and
combined with a ``[tokens, experts]`` matrix that holds the gate at the
chosen experts and zero elsewhere.  Reads the program's parameter tree
(layers stacked on a leading dimension, q/k scales as ``[heads,
head_dim]``) and shares no code with ``ray_tpu/models`` or ``ray_tpu/ops``.
"""

import jax
import jax.numpy as jnp
import numpy as np

TOKEN_BLOCK = 128


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """x [B, S, N, H]: rotate pair (i, i + H/2) by position * theta^(-2i/H)."""
    seq, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = (np.arange(seq, dtype=np.float64)[:, None] * freq)[None, :, None]
    cos = jnp.asarray(np.cos(angle), jnp.float32)
    sin = jnp.asarray(np.sin(angle), jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def gate_matrix(h, router, top_k, norm_topk_prob):
    """h [T, D] -> [T, E]: the softmax probability at each token's ``top_k``
    most probable experts, zero elsewhere."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    gates = jnp.where(probs >= kth, probs, 0.0)
    if norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates


def experts(h, gates, wgu, wd):
    """Every expert on every token of h [T, D], weighed by gates [T, E]."""
    out = []
    for at in range(0, h.shape[0], TOKEN_BLOCK):
        block = h[at:at + TOKEN_BLOCK]
        gate, up = jnp.einsum("td,ecdm->ctem", block, wgu)
        each = jnp.einsum("tem,emd->ted", jax.nn.silu(gate) * up, wd)
        out.append(jnp.einsum("ted,te->td", each, gates[at:at + TOKEN_BLOCK]))
    return jnp.concatenate(out)


def forward(params, tokens, rope_theta, rms_eps, top_k, norm_topk_prob,
            with_gates=False):
    """tokens [B, S] -> logits [B, S, V], float32; ``with_gates`` adds the
    gate matrices [L, B, S, E] (non-zero at the chosen experts)."""
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        layers = p32["layers"]
        batch, seq = tokens.shape
        heads, head_dim = layers["attn"]["wq"].shape[-2:]
        x = p32["wte"][tokens]
        width = x.shape[-1]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        chosen = []
        for i in range(layers["ln1"]["scale"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], layers)
            attn = lp["attn"]
            h = _rms_norm(x, lp["ln1"]["scale"], rms_eps)
            q = h @ attn["wq"].reshape(width, -1)
            k, v = jnp.moveaxis(
                jnp.einsum("bsd,dcnh->bscnh", h, attn["wkv"]), 2, 0)
            kv_heads = k.shape[2]
            q = _rms_norm(q, attn["q_norm"].reshape(-1), rms_eps)
            k = _rms_norm(k.reshape(batch, seq, -1),
                          attn["k_norm"].reshape(-1), rms_eps)
            q = _rotate(q.reshape(batch, seq, heads, head_dim), rope_theta)
            k = _rotate(k.reshape(batch, seq, kv_heads, head_dim),
                        rope_theta)
            # query head n reads key-value head n // (heads / kv_heads)
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
            scores = jnp.einsum("bqnh,bknh->bnqk", q, k) / np.sqrt(head_dim)
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bnqk,bknh->bqnh", probs, v)
            x = x + jnp.einsum("bqnh,nhd->bqd", o, attn["wo"])
            h = _rms_norm(x, lp["ln2"]["scale"], rms_eps).reshape(-1, width)
            gates = gate_matrix(h, lp["mlp"]["router"], top_k,
                                norm_topk_prob)
            chosen.append(gates.reshape(batch, seq, -1))
            x = x + experts(h, gates, lp["mlp"]["wgu"],
                            lp["mlp"]["wd"]).reshape(x.shape)
        x = _rms_norm(x, p32["ln_f"]["scale"], rms_eps)
        logits = x @ p32["lm_head"]
        return (logits, jnp.stack(chosen)) if with_gates else logits
