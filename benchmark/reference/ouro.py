"""Ouro-2.6B's forward pass in plain ``jax.numpy``: float32, highest matmul
precision, no kernels, no cache.

Follows the published ``config.json`` (``total_ut_steps``,
``early_exit_threshold`` and every width) and, for what it has no key for,
``modeling_ouro.py`` of the published implementation:

1. ``x = E[token]``; then ``total_ut_steps`` passes over the SAME layers;
2. a layer: ``a = RMSNorm(x)``; ``q, k, v = a Wq, a Wk, a Wv``, no bias;
   heads of ``head_dim``, q and k rotated at the token's position (the
   half-split ``rotate_half`` convention); causal attention over the keys and
   values that THIS pass made (the published cache index is ``current_ut *
   num_hidden_layers + layer_idx``: a cache for every pass and layer, which
   without a cache is plain causal attention inside the pass);
   ``x += RMSNorm(o Wo)`` (``input_layernorm_2``: the sublayer's output is
   normed before the add); ``m = RMSNorm(x)``; ``x += RMSNorm(Wd(silu(Wg m)
   * Wu m))`` (``post_attention_layernorm_2``);
3. after EVERY pass the model's final ``RMSNorm``; the next pass starts
   from its result, and ``h[t]`` is that result;
4. the exit gate: ``lam[t] = sigmoid(h[t] . w + b)``, ``p[t] = lam[t] *
   prod_{s<t}(1 - lam[s])`` for ``t < T - 1`` and the remainder for the last;
   a position leaves at the first pass whose cumulative ``p`` reaches
   ``early_exit_threshold``, else at the last; ``logits = h[t*] W_head``.

Departure: the program's tree has no leaves for the gate (at the published
threshold of 1 the cumulative probability reaches 1 only with the last
pass's remainder, so the gate changes nothing), so ``forward`` takes them as
an argument and, given none, reads the last pass.

Reads the program's parameter tree (layers stacked on a leading dimension,
in whatever dtype they are stored) and upcasts a layer at a time, inside a
``lax.scan`` over the stack, so that at the published size one float32
layer is alive and not all 48.  Shares no code with ``ray_tpu/models``.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


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


def exit_step(hidden, gate, threshold):
    """hidden [T, B, S, D] -> the pass [B, S] each position leaves at."""
    w, b = gate
    lam = jax.nn.sigmoid(hidden @ w + b)                       # [T, B, S]
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]])    # sums to 1
    reached = jnp.cumsum(p, axis=0) >= threshold
    last = hidden.shape[0] - 1
    return jnp.where(reached.any(0), jnp.argmax(reached, axis=0), last)


def forward(params, tokens, rope_theta, rms_eps, ut_steps, gate=None,
            threshold=1.0):
    """tokens [B, S] -> logits [B, S, V], float32.  With ``gate`` (``w``
    [D], ``b`` scalar) also the pass [B, S] whose hidden state each
    position's logits were read from."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1]
        heads, head_dim = params["layers"]["attn"]["wq"].shape[-2:]
        causal = jnp.tril(jnp.ones((seq, seq), bool))

        def layer(x, lp):
            lp = _f32(lp)                     # this layer alone, upcast
            a = _rms_norm(x, lp["ln1"]["scale"], rms_eps)
            q = jnp.einsum("bsd,dnh->bsnh", a, lp["attn"]["wq"])
            k, v = jnp.moveaxis(
                jnp.einsum("bsd,dcnh->bscnh", a, lp["attn"]["wkv"]), 2, 0)
            q, k = _rotate(q, rope_theta), _rotate(k, rope_theta)
            # query head n reads key-value head n // (heads / kv_heads)
            k = jnp.repeat(k, heads // k.shape[2], axis=2)
            v = jnp.repeat(v, heads // v.shape[2], axis=2)
            scores = jnp.einsum("bqnh,bknh->bnqk", q, k) / np.sqrt(head_dim)
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bnqk,bknh->bqnh", probs, v)
            x = x + _rms_norm(jnp.einsum("bqnh,nhd->bqd", o,
                                         lp["attn"]["wo"]),
                              lp["ln1_post"]["scale"], rms_eps)
            m = _rms_norm(x, lp["ln2"]["scale"], rms_eps)
            gate_, up = jnp.einsum("bsd,cdm->cbsm", m, lp["mlp"]["wgu"])
            f = (jax.nn.silu(gate_) * up) @ lp["mlp"]["wd"]
            return x + _rms_norm(f, lp["ln2_post"]["scale"], rms_eps), None

        x = params["wte"][tokens].astype(jnp.float32)
        final = params["ln_f"]["scale"].astype(jnp.float32)
        hidden = []
        for _ in range(ut_steps):             # the same layers every pass
            x, _ = jax.lax.scan(layer, x, params["layers"])
            x = _rms_norm(x, final, rms_eps)
            hidden.append(x)
        head = params["lm_head"].astype(jnp.float32)
        if gate is None:
            return hidden[-1] @ head
        hidden = jnp.stack(hidden)
        at = exit_step(hidden, _f32(gate), threshold)
        left = jnp.take_along_axis(hidden, at[None, ..., None], axis=0)[0]
        return left @ head, at
