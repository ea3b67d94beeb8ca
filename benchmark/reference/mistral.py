"""Mistral-7B's forward pass in plain ``jax.numpy``: float32, highest
matmul precision, no kernels, cache or scan.

Follows Jiang et al. 2023 and the published ``config.json`` of v0.3:
pre-RMSNorm blocks, rotary positions on q and k (the half-split
``rotate_half`` convention of the published implementation), grouped-query
causal attention without a sliding window, SwiGLU feed-forward, untied
head.  Reads the program's parameter tree (layers stacked on a leading
dimension), and shares no code with ``ray_tpu/models``.
"""

import jax
import jax.numpy as jnp
import numpy as np


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


def forward(params, tokens, rope_theta, rms_eps):
    """tokens [B, S] -> logits [B, S, V], float32."""
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        layers = p32["layers"]
        seq = tokens.shape[1]
        heads, head_dim = layers["attn"]["wq"].shape[-2:]
        kv_heads = layers["attn"]["wkv"].shape[-2]
        x = p32["wte"][tokens]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        for i in range(layers["ln1"]["scale"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], layers)
            h = _rms_norm(x, lp["ln1"]["scale"], rms_eps)
            q = jnp.einsum("bsd,dnh->bsnh", h, lp["attn"]["wq"])
            k, v = jnp.moveaxis(
                jnp.einsum("bsd,dcnh->bscnh", h, lp["attn"]["wkv"]), 2, 0)
            q, k = _rotate(q, rope_theta), _rotate(k, rope_theta)
            # query head n reads key-value head n // (heads / kv_heads)
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
            scores = jnp.einsum("bqnh,bknh->bnqk", q, k) / np.sqrt(head_dim)
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bnqk,bknh->bqnh", probs, v)
            x = x + jnp.einsum("bqnh,nhd->bqd", o, lp["attn"]["wo"])
            h = _rms_norm(x, lp["ln2"]["scale"], rms_eps)
            gate, up = jnp.einsum("bsd,cdm->cbsm", h, lp["mlp"]["wgu"])
            x = x + (jax.nn.silu(gate) * up) @ lp["mlp"]["wd"]
        x = _rms_norm(x, p32["ln_f"]["scale"], rms_eps)
        return x @ p32["lm_head"]
