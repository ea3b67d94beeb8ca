"""Kimi Linear's forward pass in plain ``jax.numpy``: float32, highest
matmul precision, no kernels, cache, chunks, absorption or batching of the
recurrence.

``config.json`` (``model_type: kimi_linear``) gives the sizes and which layers
are of which kind (``linear_attn_config.kda_layers`` /
``full_attn_layers``, 1-based: three KDA layers then one latent layer,
repeated); the equations are the release's ``modeling_kimi.py`` and the
paper's (Kimi Linear, arXiv:2510.26692).  What the config does not say is
listed in the configuration file's ``assumed``.

A layer, on the residual stream ``x`` (RMSNorm, eps ``rms_norm_eps``, on each
sublayer's INPUT):

    x = x + mixer(RMSNorm(x));   x = x + ffn(RMSNorm(x))

``mixer`` of a KDA layer, on the normed ``h``, a position ``t`` at a time:

1. ``q~, k~, v~ = h Wq, h Wk, h Wv`` (one matrix ``wqkv``), no biases; every
   channel convolved over time with its own ``K`` = 4 weights, causally
   (``y_t = sum_j w_j x_{t-K+1+j}``, zeros before the sequence), no bias, then
   SiLU; split into heads ``q, k, v`` [N, 128];
2. ``q = q / sqrt(|q|^2 + 1e-6) / sqrt(dk)``, ``k = k / sqrt(|k|^2 + 1e-6)``;
3. the decay a KEY CHANNEL: ``g = -exp(A_log[head]) softplus((h Wf_a) Wf_b +
   dt_bias)`` [N, dk], ``alpha = exp(g)``; ``beta = sigmoid(h Wb)`` [N];
4. per head, from ``S = 0`` [dk, dv]:  ``S = Diag(alpha) S``;
   ``S = S + beta k (v - S^T k)^T``;  ``o = S^T q``;
5. ``y = (RMSNorm_dv(o) * w) * sigmoid((h Wg_a) Wg_b)`` per head, one scale
   ``w`` [dv] for all heads; ``y Wo``.

``mixer`` of a latent (MLA) layer: ``q = h Wq`` directly (``q_lora_rank``
null), heads of ``[nope | rope]``; ``[c | k_s] = h W_kva``; ``c =
RMSNorm(c)`` with a learned scale; NOTHING is rotated (``mla_use_nope``);
``[k_h | v_h] = c W_kvb``; score of head h = ``(q_h[nope] . k_h + q_h[rope] .
k_s) (nope + rope)^-1/2``; causal softmax; ``o_h = sum p v_h``; ``concat(o_h)
W_o``.

``ffn``: the first ``first_k_dense_replace`` layers a SwiGLU.  Every other
layer: ``s = sigmoid(h W_r)`` over ALL the router's experts; the
``num_experts_per_token`` experts with the largest ``s + bias``; gates ``g =
s[chosen] / (sum s[chosen] + 1e-20) x routed_scaling_factor``; ``sum g_e
E_e(h) + E_shared(h)``, SwiGLU experts, no token dropped.  THE SHARE: the
tree holds experts ``first .. first + E - 1`` of the router's (``first`` =
``expert_share[0]`` x E); the sum runs over those of a token's chosen experts
that the tree holds and the others add nothing, here as in the program; the
shared expert is whole.

After the last layer ``RMSNorm``, then the untied head, over the vocabulary
the tree holds.

Reads the program's parameter tree (``layers`` a tuple with a group a layer,
each a stack of one; the program's own precision, upcast a layer at a time,
the routed experts a block at a time) and shares no code with
``ray_tpu/models`` or ``ray_tpu/ops``.  Departures from a literal reading:
the recurrence of step 4 is a ``lax.scan`` over positions; the three
projections and convolutions of step 1 are read from one matrix and one set
of taps side by side, which changes no arithmetic.
"""

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 8


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def swiglu(h, wgu, wd):
    gate, up = jnp.einsum("...d,cdm->c...m", h, wgu)
    return (jax.nn.silu(gate) * up) @ wd


def delta_rule(q, k, v, alpha, beta):
    """Step 4 for every position in turn: q, k [B, S, N, dk], v [B, S, N,
    dv], alpha [B, S, N, dk], beta [B, S, N] -> (o [B, S, N, dv], the states
    after the last position [B, N, dk, dv])."""
    batch, _, heads, dk = q.shape

    def position(S, row):
        q_t, k_t, v_t, a_t, b_t = row
        S = a_t[..., None] * S
        u = jnp.einsum("bnij,bni->bnj", S, k_t)
        S = S + jnp.einsum("bni,bnj->bnij", k_t, b_t[..., None] * (v_t - u))
        return S, jnp.einsum("bnij,bni->bnj", S, q_t)

    rows = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0),
                        (q, k, v, alpha, beta))
    S, o = jax.lax.scan(
        position, jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32),
        rows)
    return jnp.moveaxis(o, 0, 1), S


def kda(h, p, config):
    """-> (the mixer's output [B, S, D], the states after the last
    position)."""
    linear = config["linear_attn_config"]
    heads, dh = linear["num_heads"], linear["head_dim"]
    batch, seq, _ = h.shape
    qkv = h @ p["wqkv"]
    taps = p["conv"].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + seq] * p["conv"][j]
                          for j in range(taps)))
    q, k, v = (part.reshape(batch, seq, heads, dh)
               for part in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(dh)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = jax.nn.softplus((h @ p["wf_a"]) @ p["wf_b"] + p["dt_bias"])
    alpha = jnp.exp(-jnp.exp(p["A_log"])[:, None]
                    * f.reshape(batch, seq, heads, dh))
    beta = jax.nn.sigmoid(h @ p["wb"])
    o, states = delta_rule(q, k, v, alpha, beta)
    gate = jax.nn.sigmoid((h @ p["wg_a"]) @ p["wg_b"])
    y = _rms_norm(o, p["norm"], config["rms_norm_eps"]) \
        * gate.reshape(batch, seq, heads, dh)
    return jnp.einsum("bsnv,nvd->bsd", y, p["wo"]), states


def latent_attention(h, p, config):
    rank, nope, rope = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                        config["qk_rope_head_dim"])
    seq = h.shape[1]
    q = jnp.einsum("bsd,dnh->bsnh", h, p["wq"])
    ck = h @ p["wkv_a"]
    c = _rms_norm(ck[..., :rank], p["kv_a_norm"], config["rms_norm_eps"])
    k_shared = ck[..., rank:]                          # one for all heads
    kv = jnp.einsum("bsc,cnh->bsnh", c, p["wkv_b"])
    scores = (jnp.einsum("bqnh,bknh->bnqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqnh,bkh->bnqk", q[..., nope:], k_shared)) \
        * (nope + rope) ** -0.5
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bnqk,bknh->bqnh", probs, kv[..., nope:])
    return jnp.einsum("bqnh,nhd->bqd", o, p["wo"])


def gate_matrix(h, router, bias, config):
    """h [T, D] -> [T, R]: each token's gates at its chosen experts of ALL
    the router's, zero elsewhere."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores + bias, config["num_experts_per_token"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["moe_renormalize"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * config["routed_scaling_factor"]
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def routed(h, gates, wgu, wd):
    """The experts of the stack on every token of h [T, D], weighed by
    THEIR gates [T, E]; a block of experts at a time, upcast there."""
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


def feed_forward(h, lp, mlp, config):
    if "router" not in mlp:                        # a leading dense layer
        return swiglu(h, *_f32((mlp["wgu"], mlp["wd"])))
    flat = h.reshape(-1, h.shape[-1])
    gates = gate_matrix(flat, _f32(mlp["router"]), _f32(mlp["router_bias"]),
                        config)
    held = mlp["wgu"].shape[0]
    first = config["expert_share"][0] * held
    y = routed(flat, gates[:, first:first + held], mlp["wgu"], mlp["wd"]) \
        + swiglu(flat, lp["shared"]["wgu"], lp["shared"]["wd"])
    return y.reshape(h.shape)


def forward(params, tokens, config, with_states=False):
    """tokens [B, S] -> logits [B, S, V], float32; ``with_states`` adds the
    KDA layers' states after the last position [KDA layers, B, N, dk, dv]."""
    eps = config["rms_norm_eps"]
    kda_layers = set(config["linear_attn_config"]["kda_layers"])
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        states = []
        for number, group in enumerate(params["layers"], start=1):
            group = jax.tree.map(lambda a: a[0], group)
            mlp = group["mlp"]
            lp = _f32({k: v for k, v in group.items() if k != "mlp"})
            h = _rms_norm(x, lp["ln1"]["scale"], eps)
            if number in kda_layers:
                y, state = kda(h, lp["linear"], config)
                states.append(state)
            else:
                y = latent_attention(h, lp["attn"], config)
            x = x + y
            x = x + feed_forward(_rms_norm(x, lp["ln2"]["scale"], eps), lp,
                                 mlp, config)
        x = _rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
        logits = x @ params["lm_head"].astype(jnp.float32)
        return (logits, jnp.stack(states)) if with_states else logits
