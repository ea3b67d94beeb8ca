"""LFM2's mixture-of-experts model (``model_type: lfm2_moe``;
LFM2-24B-A2B) forward in plain ``jax.numpy``: float32, highest matmul
precision, no kernels, no cache, no batching.

``config.json`` gives the sizes and every layer's kind (``layer_types``:
``conv`` | ``full_attention``); the equations are ``transformers``'
``models/lfm2/modeling_lfm2.py`` (the operators, the norms, the stack) and,
for the experts' router, the release's notes and later ``transformers``'
``modeling_lfm2_moe.py``.  What ``config.json`` does not say is listed in the
configuration file's ``assumed``.  RMSNorm is ``x / sqrt(mean(x^2) + eps) *
w`` with ``eps = norm_eps``.

The stack: ``h = E[ids]``; a layer ``h = h + op(RMSNorm_operator(h))``, then
``h = h + ffn(RMSNorm_ffn(h))``; after the last layer ``RMSNorm(h)`` (the
release's ``embedding_norm``, applied at the END) and the logits ``h E^T``:
the head is the table (``tie_word_embeddings``).

``op`` of a ``conv`` layer (``Lfm2ShortConv``), on the normed ``x``:

    [B | C | z] = x W_in        cut in that order into three of D
    u = B * z
    c_t = sum_{j=0..K-1} w[j] * u_{t-(K-1)+j}     K = conv_L_cache = 3; zeros
                                                  before the sequence; no
                                                  bias; NO activation
    y = C * c;   y W_out

written here as an explicit sum over ``K`` shifted copies of ``u``.

``op`` of a ``full_attention`` layer (``Lfm2Attention``): ``q = x Wq``, ``k =
x Wk``, ``v = x Wv``, no biases; q and k RMS-normed EACH HEAD by itself with
one learned scale [head] each; both rotated over all the head's columns
(``rope_theta``, the half-split ``rotate_half`` form); causal softmax at
``head^-1/2``, ``num_attention_heads / num_key_value_heads`` query heads a
K/V head; ``Wo``.  Computed a query head at a time.

``ffn``: the first ``num_dense_layers`` layers a SwiGLU ``W2(silu(W1 x) * W3
x)``.  Every other layer: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok``
experts with the largest ``s + expert_bias``; gates ``s[chosen] / (sum
s[chosen] + 1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``;
``sum g_e E_e(x)``, SwiGLU experts, no shared expert, no token dropped.

Reads the program's parameter tree (``layers`` a tuple with a group a layer,
each a stack of one; the operator's leaves under ``conv`` (``win``, ``taps``
[K, D] with ``taps[K - 1]`` at the position itself, ``wout``) or ``attn``
(``wq``, ``wkv``, ``wo``, ``q_norm``, ``k_norm``); the program's own
precision, upcast a layer at a time, the routed experts a block of eight at a
time so that the float32 copies fit beside the program at the published
widths) and shares no code with ``ray_tpu/models`` or ``ray_tpu/ops``.
Departures from a literal reading: k and v are read from one stacked matrix
``wkv``; W1 and W3 from one ``wgu``; every expert runs on every token and is
weighed by a gate that is 0 where it was not chosen (the same sum); the
router's product is float32 at the highest precision like every other.
"""

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 8
ROUTER_EPS = 1e-6


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def swiglu(h, wgu, wd):
    gate, up = jnp.einsum("...d,cdm->c...m", h, wgu)
    return (jax.nn.silu(gate) * up) @ wd


def short_conv(h, p):
    """h [B, S, D] -> the operator's output [B, S, D]."""
    width, seq = h.shape[-1], h.shape[1]
    bcz = h @ p["win"]
    b, c, z = (bcz[..., i * width:(i + 1) * width] for i in range(3))
    u = b * z
    taps = p["taps"].shape[0]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = sum(padded[:, j:j + seq] * p["taps"][j] for j in range(taps))
    return (c * mixed) @ p["wout"]


def _rotate(x, theta):
    """x [B, S, H] rotated at positions 0..S-1, the half-split form."""
    seq, head = x.shape[1], x.shape[2]
    inv = 1.0 / theta ** (np.arange(0, head, 2, dtype=np.float32) / head)
    angle = np.outer(np.arange(seq, dtype=np.float32), inv)
    cos, sin = jnp.asarray(np.cos(angle)), jnp.asarray(np.sin(angle))
    x1, x2 = x[..., :head // 2], x[..., head // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, config):
    eps, theta = config["norm_eps"], config["rope_parameters"]["rope_theta"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    seq, head = h.shape[1], p["wq"].shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    out = jnp.zeros_like(h)
    for n in range(heads):
        g = n // (heads // kv_heads)
        q = _rotate(_rms_norm(h @ p["wq"][:, n], p["q_norm"], eps), theta)
        k = _rotate(_rms_norm(h @ p["wkv"][:, 0, g], p["k_norm"], eps),
                    theta)
        v = h @ p["wkv"][:, 1, g]
        scores = jnp.einsum("bqh,bkh->bqk", q, k) * head ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = out + jnp.einsum("bqk,bkh->bqh", probs, v) @ p["wo"][n]
    return out


def gate_matrix(h, router, bias, config):
    """h [T, D] -> [T, E]: each token's gates at its chosen experts, zero
    elsewhere."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + ROUTER_EPS)
    picked = picked * config["routed_scaling_factor"]
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def routed(h, gates, wgu, wd):
    """Every expert on every token of h [T, D], weighed by its gates [T, E];
    a block of experts at a time, upcast there."""
    block_size = min(EXPERT_BLOCK, wgu.shape[0])
    blocks = wgu.shape[0] // block_size

    def block(total, part):
        wgu_b, wd_b, gates_b = part
        each = jax.vmap(lambda g, d: swiglu(h, g, d))(_f32(wgu_b),
                                                       _f32(wd_b))
        return total + jnp.einsum("etd,te->td", each, gates_b), None
    split = lambda a: a.reshape(blocks, block_size, *a.shape[1:])  # noqa
    total, _ = jax.lax.scan(
        block, jnp.zeros_like(h),
        (split(wgu), split(wd), split(gates.T).transpose(0, 2, 1)))
    return total


def feed_forward(h, mlp, config):
    if "router" not in mlp:                        # a leading dense layer
        return swiglu(h, *_f32((mlp["wgu"], mlp["wd"])))
    flat = h.reshape(-1, h.shape[-1])
    gates = gate_matrix(flat, _f32(mlp["router"]), _f32(mlp["router_bias"]),
                        config)
    return routed(flat, gates, mlp["wgu"], mlp["wd"]).reshape(h.shape)


def forward(params, tokens, config):
    """tokens [B, S] -> logits [B, S, V], float32."""
    eps = config["norm_eps"]
    with jax.default_matmul_precision("highest"):
        table = params["wte"].astype(jnp.float32)
        x = table[tokens]
        for kind, group in zip(config["layer_types"], params["layers"]):
            group = jax.tree.map(lambda a: a[0], group)
            mlp = group["mlp"]
            lp = _f32({k: v for k, v in group.items() if k != "mlp"})
            h = _rms_norm(x, lp["ln1"]["scale"], eps)
            x = x + (short_conv(h, lp["conv"]) if kind == "conv"
                     else attention(h, lp["attn"], config))
            x = x + feed_forward(_rms_norm(x, lp["ln2"]["scale"], eps), mlp,
                                 config)
        x = _rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
        return x @ table.T
