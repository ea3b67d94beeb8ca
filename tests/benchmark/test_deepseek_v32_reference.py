"""DeepSeek-V3.2-Exp's plain reference (the selection a mask from a top-k of
the whole score matrix, attention and the indexer a head at a time, the
group-limited gates a scatter, the held experts one at a time) against the
family's seeded tree and against the replica's own check at a tiny size on
the CPU: what the codes promise of the drawn weights, and the engine's two
programs (chunks over the pages, then token steps that gather the selected
rows) agreeing with the reference's full forward the way the replica checks
it on the chip.  ``tests/test_llama_deepseek_v32.py`` compares the same two
formulations on weights drawn at random."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_deepseek_v32 as tiny
from benchmark.reference import deepseek_v32 as reference

CONFIG = tiny.TINY_DSV32


@pytest.fixture(scope="module")
def program():
    return tiny.program()


def test_the_references_parts_by_hand():
    """Eight positions, four kept: the rows of ``selection`` hold
    ``min(4, t + 1)`` causal positions, the largest scores'; ``gate_matrix``
    gives 4 gates a token inside 2 of 4 groups that sum to 2.5."""
    key = jax.random.PRNGKey(0)
    S, D, rq, hi, di = 8, 32, 12, 2, 8
    ks = jax.random.split(key, 8)
    attn = {"index_wq": jax.random.normal(ks[0], (rq, hi, di)),
            "index_wk": jax.random.normal(ks[1], (D, di)),
            "index_k_norm": jnp.ones((di,)), "index_k_bias": jnp.zeros((di,)),
            "index_w": jax.random.normal(ks[2], (D, hi))}
    h, qr = jax.random.normal(ks[3], (S, D)), jax.random.normal(ks[4],
                                                                (S, rq))
    angle = jnp.zeros((S, 2))
    config = {"index_n_heads": hi, "index_head_dim": di, "index_topk": 4,
              "rms_norm_eps": 1e-6}
    keep = np.asarray(reference.selection(h, qr, attn, angle, config))
    assert keep.sum(-1).tolist() == [1, 2, 3, 4, 4, 4, 4, 4]
    assert not np.triu(keep, 1).any()
    # by hand: the scores, then every row's four largest causal ones
    k = np.asarray(reference._layer_norm(h @ attn["index_wk"], 1.0, 0.0,
                                         1e-6))
    q = np.einsum("sr,rhd->shd", qr, attn["index_wq"])
    w = np.asarray(h @ attn["index_w"]) * (hi * di) ** -0.5
    scores = np.einsum("sh,shk->sk", w, np.maximum(
        np.einsum("shd,kd->shk", q, k), 0))
    for t in range(4, S):
        assert set(np.flatnonzero(keep[t])) == set(
            np.argsort(-scores[t, :t + 1], kind="stable")[:4])
    router = jax.random.normal(ks[5], (D, 16))
    bias = 0.2 * jax.random.normal(ks[6], (16,))
    gates = np.asarray(reference.gate_matrix(h, router, bias, {
        "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5}))
    assert ((gates > 0).sum(-1) == 4).all()
    assert np.allclose(gates.sum(-1), 2.5, atol=1e-5)
    groups = (gates.reshape(S, 4, 4) > 0).any(-1).sum(-1)
    assert (groups <= 2).all()


def test_the_codes_do_what_the_file_says(program):
    """The routing's and the indexer's codes on the seeded tree: no sublayer
    writes the code's places, a router row reads one place, the indexer's
    key and weights read the code's places alone and its queries one value
    of the bottleneck."""
    family, model, params = program
    R = model.num_experts
    for group in ("dense_layers", "layers"):
        layers = params[group]
        assert float(jnp.abs(layers["attn"]["wo"][..., :R]).max()) == 0
        assert float(jnp.abs(layers["mlp"]["wd"][..., :R]).max()) == 0
        attn = layers["attn"]
        assert float(jnp.abs(attn["index_wk"][:, R:]).max()) == 0
        assert float(jnp.abs(attn["index_w"][:, R:]).max()) == 0
        assert float(attn["index_w"][:, :R].min()) > 0
        assert float(jnp.abs(attn["index_wq"][:, 1:]).max()) == 0
        hot = np.flatnonzero(np.asarray(attn["index_wq"][0, 0, 0]))
        assert hot.tolist() == [0, model.qk_rope_dim]
        assert float(attn["wq_a"][:, :R, 0].min()) > 0
        assert float(jnp.abs(attn["wq_a"][:, R:, 0]).max()) == 0
        assert float(jnp.abs(attn["index_k_bias"]).max()) > 0
    router = np.asarray(params["layers"]["mlp"]["router"])
    assert ((router != 0).sum(axis=1) == 1).all() and router.max() == 64.0
    bias = np.asarray(params["layers"]["mlp"]["router_bias"])
    assert np.abs(bias).max() <= 0.2 * (1 + 0.5 / (R - 1)) + 1e-6
    # no two pairs of a layer's biases have one sum
    pairs = (bias[0][:, None] + bias[0][None])[np.triu_indices(R, 1)]
    assert len(np.unique(pairs)) == len(pairs)
    code = np.asarray(params["wte"][:, :R])
    assert ((code != 0).sum(-1) == model.experts_per_token + 1).all()


def test_the_checks_two_prompts():
    """One past a chunk's edge and past the selection's size, one under half
    of it (``tests/benchmark/test_tools_deepseek_v32.py`` drives them in
    float32, the rehearsal in bfloat16 through the replica)."""
    from benchmark import replica_longctx
    assert replica_longctx.prompt_lengths(CONFIG["engine"], 8) == (45, 7)
    published = {"page_size": 16, "max_prompt_len": 16384,
                 "prefill_chunk": 4096}
    assert replica_longctx.prompt_lengths(published, 2048) == (6181, 1027)
    assert replica_longctx.prompt_lengths(
        {"max_prompt_len": 64}, 8) == (61, 7)
