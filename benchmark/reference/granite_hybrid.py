"""Granite 4.0-H's forward pass in plain ``jax.numpy``: float32, highest
matmul precision, the state-space layers in their RECURRENT form, a position
at a time; no kernels, cache, chunks or batching of the recurrence.

``config.json`` (``model_type: granitemoehybrid``) gives the sizes and which
layer is of which kind (``layer_types``: ``mamba`` | ``attention``); the
equations are transformers' ``GraniteMoeHybridMambaLayer`` (Bamba's; Mamba-2,
State Space Duality, arXiv:2405.21060), ``GraniteMoeHybridAttention`` and
GraniteMoe's router.  What the config does not say is listed in the
configuration file's ``assumed``.  With D the hidden size, H heads of P
values (``mamba_n_heads``, ``mamba_d_head``), N = ``mamba_d_state``, K =
``mamba_d_conv``, r = ``residual_multiplier``:

1. ``x = embedding_multiplier * E[token]``.  A layer, pre-norm (RMSNorm, eps
   ``rms_norm_eps``, a learned scale), no post-norm:

       x = x + r * Mixer(RMSNorm(x));   h = RMSNorm(x)
       x = x + r * (Experts(h) + Shared(h))

   Logits ``= RMSNorm(x) E^T / logits_scaling`` (the table is the head).
2. ``Mixer`` of a ``mamba`` layer, on the normed ``h``: ``[z | xBC | dt] = h
   W_in`` (widths H P | H P + 2 N | H, no bias); every channel of ``xBC``
   convolved over time with its own K weights, causally (``y_t = sum_j w_j
   x_{t-K+1+j} + b``, zeros before the sequence), then SiLU; ``[x | B | C] =
   xBC`` (widths H P | N | N: ONE B and ONE C for all heads,
   ``mamba_n_groups`` 1); ``delta = softplus(dt + dt_bias)`` a head (not
   clamped); ``a = exp(-exp(A_log) delta)`` a head; per head n with ``x_n``
   in R^P, from ``S_n = 0`` [P, N]:

       S_n <- a_n S_n + (delta_n x_n) B^T;   y_n = S_n C + D_n x_n

   ``y = RMSNorm_{H P}(y * silu(z)) * w``: the gate FIRST, then ONE norm over
   all H P channels; ``y W_out`` (no bias).
3. ``Mixer`` of an ``attention`` layer: q, k, v = ``h W`` (heads of D /
   ``num_attention_heads``; ``num_key_value_heads`` of k and v, each shared by
   a group of q heads), no bias, NOTHING rotated (``position_embedding_type``
   "nope"), causal softmax of ``attention_multiplier * q . k``, ``W_o``.
4. ``Experts``: ``l = h W_r`` (one logit an expert of the router's PUBLISHED
   width, no bias), the ``num_experts_per_tok`` largest kept, gates = softmax
   over those alone; expert e gives ``(silu(h W1_e) * (h W3_e)) W2_e``;
   ``Shared(h)`` is the same SwiGLU at ``shared_intermediate_size`` for every
   token, ungated.  THE SHARE: the tree holds experts ``first .. first + E -
   1`` of the router's (``first`` = ``expert_share[0]`` x E); the sum runs
   over those of a token's chosen experts that the tree holds and the others
   add nothing, here as in the program; the shared expert is whole.

Reads the program's parameter tree (``layers`` a tuple with a group a layer,
each a stack of one; the program's own precision, upcast a layer at a time,
the routed experts and the head a block at a time) and shares no code with
``ray_tpu/models`` or ``ray_tpu/ops``.  Departures from a literal reading:
the recurrence is a ``lax.scan`` over positions; ``z``, ``xBC`` and ``dt``
are read from one matrix, which changes no arithmetic; the head's product is
made a block of the vocabulary at a time.

``states_after`` = (n1, n2, ...) also hands out what the state-space layers
hold after each of those many positions: the states ``[mamba layers, len(n),
B, H, P, N]`` and the last ``K - 1`` inputs of the convolution ``[mamba
layers, len(n), B, K - 1, H P + 2 N]`` (zeros where the sequence had not
begun), which is what a serving program keeps a slot.
"""

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 6
VOCAB_BLOCKS = 8


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def swiglu(h, wgu, wd):
    gate, up = jnp.einsum("...d,cdm->c...m", h, wgu)
    return (jax.nn.silu(gate) * up) @ wd


def state_space(x, key, query, a, delta, after=()):
    """Step 2's recurrence for every position in turn: x [B, S, H, P], key,
    query [B, S, N], a, delta [B, S, H] -> (y without the skip [B, S, H, P],
    the states after each of ``after`` positions [len(after), B, H, P,
    N])."""
    batch, _, heads, width = x.shape
    at = jnp.asarray(after, jnp.int32).reshape(-1)
    shape = (batch, heads, width, key.shape[-1])

    def position(carry, row):
        S, kept = carry
        t, x_t, k_t, q_t, a_t, d_t = row
        S = a_t[..., None, None] * S \
            + (d_t[..., None] * x_t)[..., None] * k_t[:, None, None, :]
        kept = jnp.where((at == t + 1)[:, None, None, None, None], S[None],
                         kept)
        return (S, kept), jnp.einsum("bhpn,bn->bhp", S, q_t)

    rows = jax.tree.map(lambda v: jnp.moveaxis(v, 1, 0),
                        (x, key, query, a, delta))
    (_, kept), y = jax.lax.scan(
        position, (jnp.zeros(shape, jnp.float32),
                   jnp.zeros((at.shape[0], *shape), jnp.float32)),
        (jnp.arange(x.shape[1]), *rows))
    return jnp.moveaxis(y, 0, 1), kept


def mamba(h, p, config, after=()):
    """-> (the mixer's output [B, S, D], the states and the convolution's
    last inputs after each of ``after`` positions)."""
    heads, width, state = (config["mamba_n_heads"], config["mamba_d_head"],
                           config["mamba_d_state"])
    inner = heads * width
    batch, seq, _ = h.shape
    zxbcdt = h @ p["win"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-heads],
                  zxbcdt[..., -heads:])
    taps = p["conv"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    tails = jnp.stack([jax.lax.dynamic_slice_in_dim(padded, n, taps - 1, 1)
                       for n in after]) if after else None
    xbc = jax.nn.silu(sum(padded[:, j:j + seq] * p["conv"][j]
                          for j in range(taps)) + p["conv_bias"])
    x = xbc[..., :inner].reshape(batch, seq, heads, width)
    key, query = xbc[..., inner:inner + state], xbc[..., inner + state:]
    delta = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(-jnp.exp(p["A_log"]) * delta)
    y, states = state_space(x, key, query, a, delta, after)
    y = y + p["D"][:, None] * x
    y = y.reshape(batch, seq, inner) * jax.nn.silu(z)
    y = _rms_norm(y, p["norm"], config["rms_norm_eps"])
    return y @ p["wout"], (states, tails)


def attention(h, p, config):
    seq = h.shape[1]
    q = jnp.einsum("bsd,dnh->bsnh", h, p["wq"])
    k, v = jnp.einsum("bsd,dcnh->cbsnh", h, p["wkv"])
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k) \
        * config["attention_multiplier"]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bqnh,nhd->bqd",
                      jnp.einsum("bnqk,bknh->bqnh", probs, v), p["wo"])


def gate_matrix(h, router, config):
    """h [T, D] -> [T, R]: each token's gates at its chosen experts of ALL
    the router's (the softmax of the kept logits), zero elsewhere."""
    logits = h @ router
    kept, chosen = jax.lax.top_k(logits, config["num_experts_per_tok"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, chosen].set(
        jax.nn.softmax(kept, axis=-1))


def routed(h, gates, wgu, wd):
    """The experts of the stack on every token of h [T, D], weighed by
    THEIR gates [T, E]; a block of experts at a time, upcast there."""
    block_size = next(n for n in range(min(EXPERT_BLOCK, wgu.shape[0]), 0,
                                       -1) if wgu.shape[0] % n == 0)
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


def feed_forward(h, lp, mlp, config):
    flat = h.reshape(-1, h.shape[-1])
    gates = gate_matrix(flat, _f32(mlp["router"]), config)
    held = mlp["wgu"].shape[0]
    first = config["expert_share"][0] * held
    y = routed(flat, gates[:, first:first + held], mlp["wgu"], mlp["wd"]) \
        + swiglu(flat, lp["shared"]["wgu"], lp["shared"]["wd"])
    return y.reshape(h.shape)


def head(x, table, scaling):
    """``x E^T / scaling`` a block of the vocabulary at a time, each block
    upcast where it is used."""
    blocks = next(n for n in range(VOCAB_BLOCKS, 0, -1)
                  if table.shape[0] % n == 0)
    parts = jax.lax.map(
        lambda rows: jnp.einsum("bsd,vd->bsv", x, rows.astype(jnp.float32)),
        table.reshape(blocks, -1, table.shape[1]))
    return jnp.moveaxis(parts, 0, 2).reshape(*x.shape[:2], -1) / scaling


def forward(params, tokens, config, states_after=None):
    """tokens [B, S] -> logits [B, S, V], float32; with ``states_after`` also
    ``{"state", "tail"}`` as the module's docstring has them."""
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    after = () if states_after is None else tuple(states_after)
    with jax.default_matmul_precision("highest"):
        x = config["embedding_multiplier"] \
            * params["wte"][tokens].astype(jnp.float32)
        states, tails = [], []
        for kind, group in zip(config["layer_types"], params["layers"]):
            group = jax.tree.map(lambda a: a[0], group)
            mlp = group["mlp"]
            lp = _f32({k: v for k, v in group.items() if k != "mlp"})
            h = _rms_norm(x, lp["ln1"]["scale"], eps)
            if kind == "mamba":
                y, (state, tail) = mamba(h, lp["ssm"], config, after)
                states.append(state)
                tails.append(tail)
            else:
                y = attention(h, lp["attn"], config)
            x = x + r * y
            x = x + r * feed_forward(
                _rms_norm(x, lp["ln2"]["scale"], eps), lp, mlp, config)
        x = _rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
        logits = head(x, params["wte"], config["logits_scaling"])
        if not after:
            return logits
        return logits, {"state": jnp.stack(states), "tail": jnp.stack(tails)}
