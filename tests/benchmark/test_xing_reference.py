"""Xing4.0's plain reference against ``ray_tpu/models/llama.py`` at a tiny
size (64 wide, 4 heads of 24 / 12, ranks 48 / 32, a four-row hyper-connected
stream, one dense layer and two expert layers of 8 experts top-2 with a
shared one, YaRN on): the full forward, and prefill then decode through the
latent pages by the engine's own two programs, the way the replica checks it
on the chip.

In float32 the two agree to rounding.  In the configuration's bfloat16 the
logits are held to the tiny configuration's ``numerics.logits_rtol``
(relative Frobenius error), and each planted fault of
``benchmark/tools/numerics_xing.py`` has to fall outside the float32
tolerance.  The published size's own readings, taken on the chip by that
tool, stand in ``benchmark/configs/xing4.0-29b-a4b-6l.json``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_xing
from benchmark import spec
from benchmark.reference import xing
from benchmark.tools import numerics_xing

TINY = tiny_xing.TINY_XING
RTOL = TINY["numerics"]["logits_rtol"]
SEQ = 48
F32_TOLERANCE = 1e-4


@pytest.fixture(scope="module")
def family():
    return spec.load_part("families", "xing")


@pytest.fixture(scope="module")
def params(family):
    """Seeded weights as the family makes them (the stored tree), with the
    norm scales moved off one so that each of them matters."""
    p = family.init(jax.random.PRNGKey(1), family.program_config(TINY, SEQ))
    for n, group in enumerate(("dense_layers", "layers")):
        layers = p[group]
        for m, name in enumerate(("ln1", "ln2")):
            layers[name] = {"scale": 1 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 + 4 * n + m),
                layers[name]["scale"].shape)}
        for m, name in enumerate(("q_a_norm", "kv_a_norm")):
            layers["attn"][name] = 1 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(12 + 4 * n + m),
                layers["attn"][name].shape)
    p["ln_f"] = {"scale": 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(20), p["ln_f"]["scale"].shape)}
    return p


@pytest.fixture(scope="module")
def params32(params):
    """The same values in float32, for the program in float32: a program
    multiplies its experts in the type they are STORED in (the activations
    cast to it), so bfloat16 experts round whatever the compute type is."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def rel_err(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def served(family, params, what="", **overrides):
    """Largest logits error of the two sequences, prefill then eight decode
    positions, with the fault ``what`` planted in the program, and the
    share of expert sets equal to the reference's."""
    model = family.program_config(TINY, SEQ, **overrides)
    seqs, got = numerics_xing.served_with(
        family, TINY, TINY["engine"], model, params,
        numerics_xing.FAULTS.get(what, {}), jax.random.PRNGKey(7), 8)
    errs, same = numerics_xing.compare(got, numerics_xing.reference_logits(
        family, TINY, params, seqs))
    return max(errs), same


def test_the_family_makes_the_stored_tree_and_the_program(family, params):
    cfg = family.program_config(TINY, SEQ)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (32, 48, 16, 8, 12)
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.dense_mlp_dim,
            cfg.mlp_dim, cfg.num_experts, cfg.experts_per_token,
            cfg.shared_experts) == (3, 1, 96, 32, 8, 2, 1)
    assert (cfg.router_scoring, cfg.router_bias, cfg.routed_scaling,
            cfg.norm_topk_prob) == ("sigmoid", True, 2.0, True)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_clamp) \
        == (4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.rope_yarn == (64.0, 16.0, 32.0, 1.0, 1.0, 1.0)
    experts, dense = params["layers"], params["dense_layers"]
    assert experts["mlp"]["wgu"].shape == (2, 8, 2, 64, 32)
    assert experts["mlp"]["wgu"].dtype == jnp.bfloat16      # stored in bf16
    assert experts["shared"]["wd"].dtype == jnp.bfloat16
    assert dense["mlp"]["wgu"].shape == (1, 2, 64, 96)
    for leaf in (experts["mlp"]["router"], experts["mlp"]["router_bias"],
                 *experts["hc_mlp"].values(), experts["attn"]["kv_a_norm"]):
        assert leaf.dtype == jnp.float32
    assert experts["hc_attn"]["proj"].shape == (2, 4 * 64, 4 + 4 + 16)
    assert experts["attn"]["wkv_b"].shape == (2, 32, 4, 16 + 12)
    assert family.kv_bytes_per_token(TINY) == 3 * (32 + 8) * 2
    assert family.moe_shape(TINY) == {"layers": 2, "experts": 8,
                                      "hidden": 64, "width": 32}
    published = spec.load_json("configs", "xing4.0-29b-a4b-6l.json")
    assert family.kv_bytes_per_token(published) == 6912
    assert family.moe_shape(published) == {"layers": 5, "experts": 64,
                                           "hidden": 3584, "width": 1024}


def test_forward_agrees_with_the_reference_in_float32(family, params32):
    from ray_tpu.models.llama import llama_forward
    params = params32
    cfg = family.program_config(TINY, SEQ, dtype=jnp.float32,
                                attention="dense")
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 256)
    want = family.reference_forward(params, tokens, TINY)
    assert want.dtype == jnp.float32 and want.shape == (2, 33, 256)
    assert rel_err(llama_forward(params, tokens, cfg), want) < 1e-5


def test_served_float32_agrees_with_the_references_full_forward(family,
                                                                params32):
    err, same = served(family, params32, dtype=jnp.float32)
    assert err < 1e-5 and same == 1.0


def test_served_bfloat16_is_inside_the_configurations_tolerance(family,
                                                                params):
    """And routes as float32 does: the routing code's margins are wider
    than any rounding, so the reading is rounding and not swapped experts."""
    err, same = served(family, params)
    assert 1e-4 < err < RTOL and same == 1.0


def test_the_routing_code_is_what_the_family_says(family, params):
    """A token's embedding names experts_per_token + 1 experts in the
    stream's first E places, nothing else writes or reads there but the
    router, and the experts that run are the named ones with the largest
    bias, all at one gate."""
    cfg = family.program_config(TINY, SEQ)
    E, k = cfg.num_experts, cfg.experts_per_token
    code = np.asarray(params["wte"][:, :E].astype(jnp.float32))
    assert set(np.unique(code)) == {0.0, np.float32(jnp.bfloat16(
        0.02 * (cfg.embed_dim / E) ** 0.5))}
    assert ((code > 0).sum(-1) == k + family.CODE_SPARE).all()
    assert len({tuple(row) for row in code > 0}) > E       # many codes
    for group in (params["dense_layers"], params["layers"]):
        writers = [group["attn"]["wo"], group["mlp"]["wd"]] + (
            [group["shared"]["wd"]] if "shared" in group else [])
        for w in writers:
            assert not np.asarray(w[..., :E].astype(jnp.float32)).any()
            assert np.asarray(w[..., E:].astype(jnp.float32)).any()
    mlp = params["layers"]["mlp"]
    router, bias = np.asarray(mlp["router"]), np.asarray(mlp["router_bias"])
    assert not router[:, E:].any()
    assert (np.sort(router[:, :E], axis=1)[:, -1] == family.CODE_WEIGHT
            ).all() and ((router[:, :E] != 0).sum(axis=(1, 2)) == E).all()
    assert all(len(set(row)) == E for row in bias.round(7).tolist())
    assert np.abs(bias).max() == np.float32(family.BIAS_SPAN)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (1, 40),
                                           0, 256), np.int32)
    _, gates = family.reference_forward(params, tokens, TINY,
                                        with_gates=True)
    gates = np.asarray(gates)[:, 0]                          # [L, S, E]
    assert ((gates > 0).sum(-1) == k).all()
    np.testing.assert_allclose(gates[gates > 0], 2.0 / k, rtol=1e-6)
    for layer in range(gates.shape[0]):
        reads = router[layer, :E].argmax(axis=0)     # expert -> its place
        named = code[tokens[0]][:, reads] > 0                # [S, E]
        want = np.argsort(np.where(named, bias[layer], -np.inf),
                          axis=-1)[:, -k:]
        got = np.argsort(gates[layer], axis=-1)[:, -k:]
        assert (np.sort(want) == np.sort(got)).all()


@pytest.mark.parametrize("fault", list(numerics_xing.FAULTS))
def test_a_planted_fault_is_outside_the_tolerance(family, params32, fault):
    """In float32 every fault stands clear of rounding; the two that round
    weights stand clear of it by construction."""
    err, _ = served(family, params32, fault, dtype=jnp.float32)
    assert err > 2 * F32_TOLERANCE


def test_absorbed_decode_is_expanded_decode(family, params):
    """One query a sequence against latent pages: the absorbed path
    (``wkv_b`` in the query and after the attention, the pool read as it
    lies) against keys and values expanded per head from the same pages."""
    from ray_tpu.models import llama
    from ray_tpu.ops.paged_attention import append_latent
    cfg = family.program_config(TINY, SEQ, dtype=jnp.float32)
    p = jax.tree.map(lambda a: a[1].astype(jnp.float32),
                     {"attn": params["layers"]["attn"]})
    B, page, maxp, W = 3, 8, 6, 40
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    pages, _ = llama.llama_init_paged_cache(cfg, B * maxp + 1, page)
    assert pages.shape == (3, B * maxp + 1, page * W)
    table = jnp.arange(1, B * maxp + 1).reshape(B, maxp)
    lengths = jnp.array([5, 17, 40])
    rows = jax.random.normal(key[0], (48, B, W))
    for pos in range(48):                   # fill layer 1, a position a time
        pages = append_latent(pages, 1, rows[pos], jnp.full((B,), pos),
                              table)
    q_nope = jax.random.normal(key[1], (B, 4, 16))
    q_rope = jax.random.normal(key[2], (B, 4, 8))
    got = llama._mla_absorbed(cfg, p, q_nope, q_rope, pages, 1, lengths,
                              table)
    # expanded: per-head keys and values of every cached position
    lat = jnp.moveaxis(rows, 0, 1)                               # [B, S, W]
    kv = jnp.einsum("bsc,cnh->bsnh", lat[..., :32], p["attn"]["wkv_b"])
    scores = (jnp.einsum("bnh,bsnh->bns", q_nope, kv[..., :16])
              + jnp.einsum("bnh,bsh->bns", q_rope, lat[..., 32:])) \
        * llama.mla_softmax_scale(cfg)
    valid = jnp.arange(48)[None] < lengths[:, None]
    probs = jax.nn.softmax(jnp.where(valid[:, None], scores, -jnp.inf), -1)
    want = jnp.einsum("bns,bsnh->bnh", probs, kv[..., 16:])
    assert got.shape == (B, 4, 12)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_h_res_is_doubly_stochastic_after_twenty_rounds(family, params):
    from ray_tpu.models import llama
    cfg = family.program_config(TINY, SEQ, dtype=jnp.float32)
    hp = jax.tree.map(lambda a: a[0], params["layers"]["hc_attn"])
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 7, 4, 64))
    pre, post, res = llama._hc_coeff(cfg, hp, x)
    assert res.shape == (5, 7, 4, 4) and float(res.min()) > 0
    assert float(jnp.abs(res.sum(-1) - 1).max()) < 1e-3
    assert float(jnp.abs(res.sum(-2) - 1).max()) < 1e-3
    assert float(pre.min()) > 0 and float(pre.max()) < 1
    assert float(post.min()) > 0 and float(post.max()) < 2
    # and it depends on the state: not one matrix for all positions
    assert float(jnp.abs(res - res[0, 0]).max()) > 0.01
    ref = xing.hyper_coefficients(x, hp, TINY)
    for got, want in zip((pre, post, res), ref):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    one = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
    rough = llama._hc_coeff(one, hp, x)[2]
    assert float(jnp.abs(rough.sum(-1) - 1).max()) > 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_row_with_identity_coefficients_is_the_plain_block(dtype):
    """``hc_mult`` 1 with coefficients that are exactly 1 (``H_pre`` =
    sigmoid(20), ``H_post`` = 2 sigmoid(0), ``H_res`` = e^30 / e^30 with
    no guard in the Sinkhorn denominators: with ``hc_eps`` every one of the
    forty divisions would take a millionth off the one entry) is the plain
    pre-norm residual block: bit for bit in float32; in bfloat16 to
    rounding, because the compiler is free to keep excess precision between
    two operations (``xla_allow_excess_precision``) and does so differently
    in two differently shaped programs."""
    from ray_tpu.models.llama import LlamaConfig, llama_forward, llama_init
    plain = LlamaConfig(vocab_size=97, max_seq_len=32, num_layers=2,
                        num_heads=4, num_kv_heads=2, embed_dim=64,
                        mlp_dim=96, dtype=dtype, attention="dense",
                        remat=False)
    hyper = dataclasses.replace(plain, hc_mult=1, hc_eps=0.0)
    params = llama_init(jax.random.PRNGKey(5), hyper)
    for name in ("hc_attn", "hc_mlp"):
        hc = params["layers"][name]
        hc["alpha"] = jnp.zeros_like(hc["alpha"])
        hc["bias"] = jnp.broadcast_to(jnp.array([20.0, 0.0, 30.0]),
                                      hc["bias"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 19), 0, 97)
    want = llama_forward(params, tokens, plain)
    got = llama_forward(params, tokens, hyper)
    assert got.dtype == dtype
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_array_equal(got, want)
    else:
        assert 0 < np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-2


def test_yarn_tables_are_deepseeks(family):
    """The program's tables against the reference's angles, and against
    the three regimes by hand: a pair that turns often keeps its frequency,
    one that turns rarely has it divided by ``factor``."""
    from ray_tpu.models.llama import rope_tables, yarn_rope_tables
    scaling = spec.load_json("configs",
                             "xing4.0-29b-a4b-6l.json")["rope_scaling"]
    cos, sin = yarn_rope_tables(4096, 64, 1e4, 64.0, 4096.0, 32.0, 1.0, 1.0,
                                1.0)
    angle = xing.yarn_angles(4096, 64, 1e4, scaling)
    np.testing.assert_allclose(cos, np.cos(angle), atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(angle), atol=1e-6)
    plain_cos, _ = rope_tables(4096, 64, 1e4)
    np.testing.assert_allclose(cos[:, 0], plain_cos[:, 0], atol=1e-6)
    slow = 1e4 ** -(62 / 64) / 64            # the last pair, interpolated
    np.testing.assert_allclose(angle[:, -1], np.arange(4096) * slow,
                               rtol=1e-12)
    assert not np.allclose(cos, plain_cos, atol=1e-3)


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1), ("n_group", 8), ("topk_group", 4),
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("moe_layer_freq", 2),
    ("first_k_dense_replace", 0), ("first_k_dense_replace", 3),
    ("num_key_value_heads", 2),
    ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("rope_scaling", None)])
def test_family_refuses_what_the_program_cannot_run(family, key, value):
    with pytest.raises(ValueError):
        family.program_config({**TINY, key: value}, SEQ)


def test_latent_attention_without_yarn_is_refused(family):
    """No configuration runs it, so no reference stands behind it."""
    from ray_tpu.models.llama import llama_init
    cfg = dataclasses.replace(family.program_config(TINY, SEQ),
                              rope_yarn=None)
    with pytest.raises(ValueError, match="rope_yarn"):
        llama_init(jax.random.PRNGKey(0), cfg)


def test_the_seeded_bias_and_coefficients_do_something(family, params):
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (40,), 0,
                                           256), np.int32)
    said = numerics_xing.drawn(family, TINY, params, tokens)
    assert said["expert_sets_the_bias_changes"] > 0.02
    assert 0.3 < said["h_res_mean_diagonal"] < 0.98
    assert said["h_res_sums_off_one"] < 1e-3
