"""Olmo-Hybrid's forward pass in plain ``jax.numpy``: float32, highest
matmul precision, no kernels, cache, chunks or batching of the recurrence.

``config.json`` (``model_type: olmo_hybrid``) gives the sizes and the order of
the layers (``layer_types``: three ``linear_attention`` then one
``full_attention``, repeated) and names the linear layer's keys as
transformers' Qwen3-Next does; the equations are that layer's
(``Qwen3NextGatedDeltaNet`` with ``torch_recurrent_gated_delta_rule``; Gated
DeltaNet, arXiv:2412.06464) with ``linear_allow_neg_eigval``
(arXiv:2411.12537), and OLMo 2 / OLMo 3's block around them.  What the
config does not say is listed in the configuration file's ``assumed``.

A layer, on the residual stream ``x`` (OLMo 2's order: NO norm on a
sublayer's input, one RMSNorm on its OUTPUT):

    x = x + RMSNorm(mixer(x));   x = x + RMSNorm(SwiGLU(x))

``mixer`` of a ``linear_attention`` layer, a position ``t`` at a time:

1. ``q~, k~, v~ = x Wq, x Wk, x Wv`` (one matrix ``wqkv``), ``z = x Wz``,
   ``b, a = x Wb, x Wa`` (one matrix ``wba``), no biases;
2. every channel of ``(q~, k~, v~)`` convolved over time with its own
   ``K`` = 4 weights, causally (``y_t = sum_j w_j x_{t-K+1+j}``, zeros before
   the sequence), no bias, then SiLU; split into heads ``q, k`` [N, dk] and
   ``v`` [N, dv];
3. ``q = q / sqrt(|q|^2 + 1e-6) / sqrt(dk)``, ``k = k / sqrt(|k|^2 + 1e-6)``;
4. ``beta = 2 sigmoid(b)`` (the 2 is ``linear_allow_neg_eigval``),
   ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``, one each a head;
5. per head, from ``S = 0`` [dk, dv]:  ``S = alpha S``;
   ``S = S + beta k (v - S^T k)^T``;  ``o = S^T q``;
6. ``y = (RMSNorm_dv(o) * w) * silu(z)`` per head, one scale ``w`` [dv] for
   all heads; ``y Wo``.

``mixer`` of a ``full_attention`` layer: ``q, k, v = x Wq, x Wk, x Wv``; q
and k RMS-normed over the WHOLE projection with a learned scale (OLMo 2's
``q_norm`` / ``k_norm``), NO rotation (``rope_parameters.rope_theta`` is
null), causal softmax attention over heads of ``hidden / heads``, ``o Wo``.

After the last layer ``RMSNorm``, then the untied head.  Reads the program's
parameter tree (``layers`` a tuple with one group a position of the
pattern's period, each stacked over the periods: layer ``l`` is row ``l //
period`` of group ``l % period``; the program's own precision, upcast a layer
at a time) and shares no code with
``ray_tpu/models`` or ``ray_tpu/ops``.  Departure from a literal reading:
the recurrence of step 5 is a ``lax.scan`` over positions.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _swiglu(x, mlp):
    gate, up = jnp.einsum("bsd,cdm->cbsm", x, mlp["wgu"])
    return (jax.nn.silu(gate) * up) @ mlp["wd"]


def delta_rule(q, k, v, alpha, beta):
    """Step 5 for every position in turn: q, k [B, S, N, dk], v [B, S, N,
    dv], alpha, beta [B, S, N] -> o [B, S, N, dv]."""
    batch, _, heads, dk = q.shape

    def position(S, row):
        q_t, k_t, v_t, a_t, b_t = row
        S = a_t[..., None, None] * S
        u = jnp.einsum("bnij,bni->bnj", S, k_t)
        S = S + jnp.einsum("bni,bnj->bnij", k_t, b_t[..., None] * (v_t - u))
        return S, jnp.einsum("bnij,bni->bnj", S, q_t)

    rows = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0),
                        (q, k, v, alpha, beta))
    _, o = jax.lax.scan(
        position, jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32),
        rows)
    return jnp.moveaxis(o, 0, 1)


def linear_attention(x, p, config):
    heads, dk, dv = (config["linear_num_key_heads"],
                     config["linear_key_head_dim"],
                     config["linear_value_head_dim"])
    batch, seq, _ = x.shape
    qkv, z, ba = x @ p["wqkv"], x @ p["wz"], x @ p["wba"]
    taps = p["conv"].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + seq] * p["conv"][j]
                          for j in range(taps)))
    q = qkv[..., :heads * dk].reshape(batch, seq, heads, dk)
    k = qkv[..., heads * dk:2 * heads * dk].reshape(batch, seq, heads, dk)
    v = qkv[..., 2 * heads * dk:].reshape(batch, seq, heads, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(ba[..., :heads])
    if config["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        ba[..., heads:] + p["dt_bias"]))
    o = delta_rule(q, k, v, alpha, beta)
    y = _rms_norm(o, p["norm"], config["rms_norm_eps"]) * jax.nn.silu(
        z.reshape(batch, seq, heads, dv))
    return jnp.einsum("bsnv,nvd->bsd", y, p["wo"])


def full_attention(x, p, config):
    batch, seq, width = x.shape
    heads, head_dim = p["wq"].shape[-2:]
    eps = config["rms_norm_eps"]
    q = x @ p["wq"].reshape(width, -1)
    k, v = jnp.moveaxis(jnp.einsum("bsd,dcnh->bscnh", x, p["wkv"]), 2, 0)
    kv_heads = k.shape[2]
    q = _rms_norm(q, p["q_norm"].reshape(-1), eps)
    k = _rms_norm(k.reshape(batch, seq, -1), p["k_norm"].reshape(-1), eps)
    q = q.reshape(batch, seq, heads, head_dim)
    k = jnp.repeat(k.reshape(batch, seq, kv_heads, head_dim),
                   heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k) / np.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bqnh,nhd->bqd",
                      jnp.einsum("bnqk,bknh->bqnh", probs, v), p["wo"])


def forward(params, tokens, config):
    """tokens [B, S] -> logits [B, S, V], float32."""
    eps = config["rms_norm_eps"]

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        groups = params["layers"]        # one a position of the period
        for at, kind in enumerate(config["layer_types"]):
            lp = f32(jax.tree.map(lambda a: a[at // len(groups)],
                                  groups[at % len(groups)]))
            y = linear_attention(x, lp["linear"], config) \
                if kind == "linear_attention" \
                else full_attention(x, lp["attn"], config)
            x = x + _rms_norm(y, lp["ln1_post"]["scale"], eps)
            x = x + _rms_norm(_swiglu(x, lp["mlp"]), lp["ln2_post"]["scale"],
                              eps)
        x = _rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
        return x @ params["lm_head"].astype(jnp.float32)
