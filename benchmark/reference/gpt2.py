"""GPT-2's forward pass and loss in plain ``jax.numpy``: float32, highest
matmul precision, no kernels, cache, scan or rematerialisation.

Follows Radford et al. 2019 as published in the ``gpt2`` configs:
pre-LayerNorm blocks (eps 1e-5), learned positions, causal softmax
attention, tanh-approximated GELU (``gelu_new``), head tied to the
embedding.  Reads the program's parameter tree (layers stacked on a
leading dimension), and shares no code with ``ray_tpu/models``.
Departure kept from the program: its q, k, v projection has no bias.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, p, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def forward(params, tokens):
    """tokens [B, S] -> logits [B, S, V], float32."""
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        layers = p32["layers"]
        seq = tokens.shape[1]
        head_dim = layers["attn"]["wqkv"].shape[-1]
        x = p32["wte"][tokens] + p32["wpe"][:seq]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        for i in range(layers["ln1"]["scale"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], layers)
            h = _layer_norm(x, lp["ln1"])
            q, k, v = jnp.moveaxis(
                jnp.einsum("bsd,dcnh->bscnh", h, lp["attn"]["wqkv"]), 2, 0)
            scores = jnp.einsum("bqnh,bknh->bnqk", q, k) / np.sqrt(head_dim)
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bnqk,bknh->bqnh", probs, v)
            x = x + jnp.einsum("bqnh,nhd->bqd", o, lp["attn"]["wo"]) \
                + lp["attn"]["bo"]
            h = _layer_norm(x, lp["ln2"])
            h = _gelu_new(h @ lp["mlp"]["wi"] + lp["mlp"]["bi"])
            x = x + h @ lp["mlp"]["wo"] + lp["mlp"]["bo"]
        x = _layer_norm(x, p32["ln_f"])
        return x @ p32["wte"].T


def loss(params, tokens):
    """Mean next-token cross-entropy over tokens [B, S+1]."""
    logits = forward(params, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()
