"""SDAR-30B-A3B-Chat's forward pass and its generation by diffusion over
blocks in plain ``jax.numpy``: float32, highest matmul precision, no cache,
no kernels, no batching, no scan or sort.

Follows the family's published ``modeling_sdar_moe.py`` and ``generate.py``
(JetLM/SDAR), which the published ``config.json`` configures; what the
config has no key for is listed under ``assumed`` in the configuration.
Positions ``i``, blocks of ``B``, ``b(i) = i // B``:

1. ``a = RMSNorm(h)``; ``q = a Wq`` as heads of ``head_dim``, ``k = a Wk``,
   ``v = a Wv``, no bias;
2. ``q = RMSNorm(q; gq)``, ``k = RMSNorm(k; gk)``: EACH HEAD over its own
   ``head_dim`` values, one learned scale ``[head_dim]`` for all heads
   (Qwen3's ``q_norm`` / ``k_norm``); then q and k rotated (the half-split
   ``rotate_half`` convention);
3. position ``i`` attends to ``j`` iff ``b(j) <= b(i)``: its own block both
   ways and every block before it; softmax in float32; ``heads / kv_heads``
   query heads read one key-value head; ``h += o Wo``;
4. ``m = RMSNorm(h)``; ``softmax(m Wr)`` over all experts, the ``k`` largest
   are the gates, divided by their sum (``norm_topk_prob``); ``h += sum_e
   gate_e Wd_e(silu(Wg_e m) * Wu_e m)``.  No token is dropped, no shared
   expert;
5. after the last layer ``RMSNorm``, then the untied head; ``logits[i]``
   predicts the token AT position ``i`` (mask-predict, no shift).

``generate`` is the published loop, greedy: the prompt's whole blocks stand,
its trailing part of a block joins the first generated block, masks
elsewhere.  A denoise pass is ``forward`` over everything so far and the
block as it stands; of the masked positions the ``n_t`` most confident are
unmasked, or every one over the threshold where those are at least ``n_t``
(``low_confidence_dynamic``); when a pass finds no mask left the block is
final.  Which positions are masked is kept beside the tokens, never read off
the mask token's id (a stated departure: the published loop compares ids).
The reference keeps no cache, so its commit pass computes nothing and is not
run: the next block's passes see the finished tokens because they see the
whole sequence.

Every expert is applied to every token, ``EXPERT_BLOCK`` experts at a time,
and a layer is upcast when it is reached, so that the published size fits
beside the engine it is compared with.  Reads the program's parameter tree
(layers stacked on a leading dimension) and shares no code with
``ray_tpu/models`` or ``ray_tpu/ops``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 16


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """x [S, N, H]: rotate pair (i, i + H/2) by position * theta^(-2i/H)."""
    seq, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = (np.arange(seq, dtype=np.float64)[:, None] * freq)[:, None]
    cos = jnp.asarray(np.cos(angle), jnp.float32)
    sin = jnp.asarray(np.sin(angle), jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def gate_matrix(m, router, top_k):
    """m [S, D] -> [S, E]: the renormalised softmax probability at each
    token's ``top_k`` most probable experts (ties to the lower expert), zero
    elsewhere."""
    probs = jax.nn.softmax(m @ router, axis=-1)
    experts = probs.shape[-1]
    # rank of each expert among the token's: how many beat it
    beats = (probs[:, None, :] > probs[:, :, None]) | (
        (probs[:, None, :] == probs[:, :, None])
        & (jnp.arange(experts)[None, None, :]
           < jnp.arange(experts)[None, :, None]))
    gates = jnp.where(beats.sum(-1) < top_k, probs, 0.0)
    return gates / gates.sum(-1, keepdims=True)


def experts(m, gates, wgu, wd, layer):
    """Every expert of ``layer`` on every token of m [S, D], weighed by
    gates [S, E]; ``wgu`` [L, E, 2, D, M] and ``wd`` [L, E, M, D] are all
    layers' experts in the type they are stored in, and a few experts of
    the one layer are upcast at a time."""
    out = jnp.zeros_like(m)
    for at in range(0, wgu.shape[1], EXPERT_BLOCK):
        part = slice(at, at + EXPERT_BLOCK)
        # A block's upcast waits for the block before it.  Nothing else
        # orders the upcasts, and the TPU compiler then makes them all
        # ahead of their use: 14.9 GB at the published size where the
        # replica holds 9.3 (PERF.md, PR 40).
        out, wgu, wd = jax.lax.optimization_barrier((out, wgu, wd))
        gate, up = jnp.einsum("sd,ecdm->csem", m,
                              wgu[layer, part].astype(jnp.float32))
        each = jnp.einsum("sem,emd->sed", jax.nn.silu(gate) * up,
                          wd[layer, part].astype(jnp.float32))
        out = out + jnp.einsum("sed,se->sd", each, gates[:, part])
    return out


def forward(params, tokens, block_length, rope_theta, rms_eps, top_k,
            with_gates=False):
    """tokens [S] -> logits [S, V], float32, under the block-causal mask;
    ``with_gates`` adds the gate matrices [L, S, E] (non-zero at the chosen
    experts)."""
    with jax.default_matmul_precision("highest"):
        f32 = functools.partial(jax.tree.map,
                                lambda a: a.astype(jnp.float32))
        layers = params["layers"]
        seq = tokens.shape[0]
        heads, head_dim = layers["attn"]["wq"].shape[-2:]
        x = params["wte"][tokens].astype(jnp.float32)
        block = jnp.arange(seq) // block_length
        allowed = block[None, :] <= block[:, None]          # [query, key]
        chosen = []
        for i in range(layers["ln1"]["scale"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], {
                k: v for k, v in layers.items() if k != "mlp"})
            attn = f32(lp["attn"])
            a = _rms_norm(x, lp["ln1"]["scale"], rms_eps)
            q = jnp.einsum("sd,dnh->snh", a, attn["wq"])
            k, v = jnp.moveaxis(
                jnp.einsum("sd,dcnh->scnh", a, attn["wkv"]), 1, 0)
            q = _rotate(_rms_norm(q, attn["q_norm"], rms_eps), rope_theta)
            k = _rotate(_rms_norm(k, attn["k_norm"], rms_eps), rope_theta)
            # query head n reads key-value head n // (heads / kv_heads)
            k = jnp.repeat(k, heads // k.shape[1], axis=1)
            v = jnp.repeat(v, heads // v.shape[1], axis=1)
            scores = jnp.einsum("qnh,knh->nqk", q, k) / np.sqrt(head_dim)
            probs = jax.nn.softmax(
                jnp.where(allowed, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("nqk,knh->qnh", probs, v)
            x = x + jnp.einsum("qnh,nhd->qd", o, attn["wo"])
            m = _rms_norm(x, lp["ln2"]["scale"], rms_eps)
            mlp = layers["mlp"]
            gates = gate_matrix(m, mlp["router"][i].astype(jnp.float32),
                                top_k)
            chosen.append(gates)
            x = x + experts(m, gates, mlp["wgu"], mlp["wd"], i)
        x = _rms_norm(x, params["ln_f"]["scale"], rms_eps)
        logits = x @ params["lm_head"].astype(jnp.float32)
        return (logits, jnp.stack(chosen)) if with_gates else logits


def transfer_counts(block_length, denoise_steps):
    """``n_t`` for t = 0 .. T-1: ``B // T``, and one more for the first
    ``B % T`` passes."""
    return [block_length // denoise_steps + (t < block_length % denoise_steps)
            for t in range(denoise_steps)]


def unmask(logits, masked, count, threshold):
    """One denoise pass's choice: logits [B, V] float32, masked [B] bool ->
    (x0 [B], which masked positions are unmasked now [B] bool).  The
    ``count`` most confident (ties to the lower position; all if fewer are
    left) or, with a ``threshold``, every one above it where those are at
    least ``count``."""
    logits = np.asarray(logits, np.float32)
    x0 = logits.argmax(-1)
    shifted = np.exp(logits - logits.max(-1, keepdims=True))
    conf = np.where(masked, (1.0 / shifted.sum(-1)).astype(np.float32),
                    -np.inf)
    high = masked & (conf > threshold) if threshold else np.zeros_like(masked)
    if threshold and high.sum() >= count:
        return x0, high
    order = np.argsort(-conf, kind="stable")[:count]
    chosen = np.zeros_like(masked)
    chosen[order] = True
    return x0, chosen & masked


def generate(params, prompt, n, block_length, denoise_steps, threshold,
             mask_token, with_passes=False, **model):
    """``n`` tokens after ``prompt`` (a list of ids), greedy, by calling
    ``forward`` on the whole sequence every pass; ``model`` is ``forward``'s
    ``rope_theta``, ``rms_eps`` and ``top_k``.  ``with_passes`` adds the
    denoise passes each block took."""
    B = block_length
    counts = transfer_counts(B, denoise_steps)
    total = -(-(len(prompt) + n) // B) * B
    tokens = np.full((total,), mask_token, np.int32)
    tokens[:len(prompt)] = prompt
    masked = np.arange(total) >= len(prompt)
    run = jax.jit(functools.partial(forward, block_length=B, **model))
    passes = []
    for start in range(len(prompt) // B * B, total, B):
        block = slice(start, start + B)
        taken = 0
        while masked[block].any():
            # what lies past the block is invisible to it, so one shape
            # serves every pass
            logits = np.asarray(run(params, jnp.asarray(tokens)))[block]
            x0, now = unmask(logits, masked[block], counts[taken], threshold)
            tokens[block] = np.where(now, x0, tokens[block])
            masked[block] &= ~now
            taken += 1
        passes.append(taken)
    out = tokens[len(prompt):len(prompt) + n].tolist()
    return (out, passes) if with_passes else out
