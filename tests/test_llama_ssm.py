"""The state-space rule without a correction (Mamba-2's) and the stack that
runs it, in ``ops/linear_attention.py`` and ``models/llama.py`` (ISSUE 61):
the rule's three forms agree (recurrent = chunked at lengths that are and are
not whole chunks = one position on the folded pool, by the rule and by the
kernel's shared kind); prefill then token steps through the pools give
``llama_forward``'s logits, whatever the rung pads and whatever the slot held;
the seeded decay neither forgets at once nor never (the two shares of an
expert layer adding up to the uncut reference's layer is
``tests/benchmark/test_granite_hybrid_reference.py``'s); the ten largest
logits softmaxed are the softmax's ten largest renormalised; each multiplier
does what its name says and adds no operation where it is left alone;
``_check``'s new allowances and what it still refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import linear_attention as la

PAGE, SEQ, SLOTS = 4, 48, 3
MAXP = SEQ // PAGE
CFG = LlamaConfig(
    vocab_size=97, max_seq_len=SEQ, num_layers=3, num_heads=4,
    num_kv_heads=2, embed_dim=64, mlp_dim=16, dtype=jnp.float32,
    attention="dense", remat=False, rope_theta=0.0, rms_eps=1e-5,
    num_experts=8, expert_share=(1, 2), experts_per_token=3,
    norm_topk_prob=True, shared_experts=2, tie_embeddings=True,
    layer_pattern=("ssm", "full", "ssm"), linear_heads=8, linear_key_dim=16,
    linear_value_dim=16, embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=16.0)
TABLE = 1 + np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (30,), 0, 97))


@pytest.fixture(scope="module")
def model():
    params = jax.jit(lambda key: llama.llama_init(key, CFG))(
        jax.random.PRNGKey(1))
    return params, (
        jax.jit(lambda t: llama.llama_forward(params, t, CFG)),
        jax.jit(lambda *a: llama.llama_prefill(params, CFG, *a)[:3]),
        jax.jit(lambda *a: llama.llama_decode_step(params, CFG, *a)[:3]))


def pools(cfg=CFG):
    return llama.llama_init_paged_cache(cfg, SLOTS * MAXP + 1, PAGE,
                                        slots=SLOTS)


def prefill_at(prefill, length, rung, slot, kp, vp):
    padded = np.zeros((1, rung), np.int32)
    padded[0, :length] = TOKENS[:length]
    return prefill(padded, np.int32(length), kp, vp, TABLE[slot:slot + 1],
                   np.int32(slot))


def rule_operands(S=150, N=8, dk=16, dv=64, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (S, dk)), jax.random.normal(k[1], (S, dk)),
            jax.random.normal(k[2], (S, N, dv)),
            -jax.random.uniform(k[3], (S, N), minval=1e-3, maxval=0.5))


# ------------------------------------------------------------ the rule

@pytest.mark.parametrize("chunk", [50, 64])
def test_the_chunked_form_is_the_recurrent_one(chunk):
    """At lengths that are (3 x 50) and are not (150 of 64s) whole chunks,
    outputs and the state that is left."""
    q, k, v, g = rule_operands()
    o, state = la.ssm_recurrent(q, k, v, g)
    o2, state2 = la.ssm_chunked(q, k, v, g, chunk=chunk)
    np.testing.assert_allclose(o2, o, atol=2e-4)
    np.testing.assert_allclose(state2, state, atol=2e-5)


def test_positions_past_the_length_change_neither_state_nor_outputs():
    q, k, v, g = rule_operands()
    o, state = la.ssm_chunked(q, k, v, g, length=jnp.int32(100), chunk=64)
    o2, state2 = la.ssm_recurrent(q[:100], k[:100], v[:100], g[:100])
    np.testing.assert_allclose(o[:100], o2, atol=2e-4)
    np.testing.assert_allclose(state, state2, atol=2e-5)


@pytest.mark.parametrize("dv,plan", [(64, (128, 0, 2)), (16, (128, 0, 8)),
                                     (192, (128, 1, 2)), (128, (128, 1, 0))])
def test_heads_narrower_than_a_panel_lie_side_by_side(dv, plan):
    """Mamba-2's 64 values a head fold two to a 128-lane panel, with no
    whole panel a head; the delta rule's shapes fold as they did."""
    assert la._panel_plan(8, dv) == plan
    S = jax.random.normal(jax.random.PRNGKey(0), (8, 16, dv))
    folded = la.fold_state(S)
    assert folded.shape == la.state_shape(8, 16, dv)
    assert folded.size == S.size                  # nothing padded
    np.testing.assert_array_equal(la.unfold_state(folded, 8, dv), S)


@pytest.mark.parametrize("kernel", [False, True])
def test_one_position_on_the_pool_is_the_recurrent_rule(monkeypatch, kernel):
    """By the jnp step on the layer's slab and by the kernel's shared kind
    (interpreted here): a live slot's rows step, a parked slot's rows and the
    other layer's stay to the bit, and the one key and query are never
    spread over the heads."""
    monkeypatch.setattr(la, "_kernel_backend", lambda: kernel)
    q, k, v, g = rule_operands(S=3, dv=64)
    N, dk, dv = 8, 16, 64
    before = jax.random.normal(jax.random.PRNGKey(5), (2, 3, N, dk, dv))
    pool = jax.vmap(jax.vmap(la.fold_state))(before)
    assert la.state_step_kind(pool, N, dv, True) == \
        ("kernel" if kernel else "rule")
    live = jnp.array([True, False, True])
    o, after = jax.jit(la.step_pool)(q, k, v, g, None, pool, 1, live)
    for slot in (0, 2):
        want_o, want = la.ssm_recurrent(
            q[slot:slot + 1], k[slot:slot + 1], v[slot:slot + 1],
            g[slot:slot + 1], before[1, slot])
        np.testing.assert_allclose(o[slot], want_o[0], atol=1e-4)
        np.testing.assert_allclose(
            la.unfold_state(after[1, slot], N, dv), want, atol=1e-5)
    np.testing.assert_array_equal(after[1, 1], pool[1, 1])
    np.testing.assert_array_equal(after[0], pool[0])


def test_the_convolution_takes_a_bias_in_both_forms():
    x = jax.random.normal(jax.random.PRNGKey(0), (9, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(2), (6,))
    whole = la.causal_conv(x, w, bias=b)
    np.testing.assert_allclose(
        whole, jax.nn.silu(la.causal_conv(x, w, silu=False) + b), atol=1e-6)
    tail = la.conv_tail(x, 8, 4)[None]
    step, _ = la.causal_conv_step(x[8:9], w, tail, bias=b)
    np.testing.assert_allclose(step[0], whole[8], atol=1e-6)


# ----------------------------------------------------------- the stack

def test_the_tree_is_a_group_a_layer_with_experts_in_every_layer(model):
    groups = model[0]["layers"]
    assert isinstance(groups, tuple) and len(groups) == 3
    assert ["ssm" in g for g in groups] == [True, False, True]
    assert all(g["mlp"]["router"].shape == (1, 64, 8)        # all 8 scored
               and g["mlp"]["wgu"].shape == (1, 4, 2, 64, 16)  # 4 held
               and g["shared"]["wgu"].shape == (1, 2, 64, 32) for g in groups)
    ssm = groups[0]["ssm"]
    assert ssm["win"].shape == (1, 64, 128 + 160 + 8)       # z | xBC | dt
    assert ssm["conv"].shape == (1, 4, 160)
    assert ssm["conv_bias"].shape == (1, 160)
    assert ssm["norm"].shape == (1, 128) and "lm_head" not in model[0]
    kp, vp = pools()
    assert kp.shape == (1, SLOTS * MAXP + 1, PAGE, 2 * 16)  # ONE full layer
    assert vp.state.shape == (2, SLOTS, 1, 16, 128) and \
        vp.state.dtype == jnp.float32
    assert vp.conv.shape == (2, SLOTS, 3 * 160)
    axes = llama.llama_param_axes(CFG)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(
        a, tuple) and not isinstance(a[0], dict)) \
        == jax.tree.structure(model[0])


@pytest.mark.parametrize("rung", [12])
def test_prefill_then_token_steps_through_the_pools_is_the_full_forward(
        model, rung):
    """Whatever the rung pads (a padded position gets a = 1 and an input of
    0) and whatever the slot held before: slot 1 is first filled with
    another prompt."""
    _, (forward, prefill, decode) = model
    want = forward(TOKENS[None])[0]
    kp, vp = pools()
    _, kp, vp = prefill_at(prefill, 7, 12, 1, kp, vp)    # what it held
    n = 11
    logits, kp, vp = prefill_at(prefill, n, rung, 1, kp, vp)
    np.testing.assert_allclose(logits[0], want[n - 1], atol=2e-5)
    rows = vp.state[:, 1]
    for at in range(n, n + 8):
        tok, pos = np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), np.int32)
        tok[1], pos[1] = TOKENS[at], at
        logits, kp, vp = decode(tok, pos, kp, vp, TABLE)
        np.testing.assert_allclose(logits[1], want[at], atol=2e-5)
    assert float(jnp.abs(vp.state[:, 1] - rows).max()) > 0
    assert float(jnp.abs(vp.state[:, 0]).max()) == 0      # parked: untouched
    assert float(jnp.abs(vp.state[:, 2]).max()) == 0


def test_the_rung_leaves_the_same_rows(model):
    _, (_, prefill, _) = model
    a = prefill_at(prefill, 11, 12, 0, *pools())[2]
    b = prefill_at(prefill, 11, 32, 0, *pools())[2]
    np.testing.assert_allclose(a.state, b.state, atol=1e-6)
    np.testing.assert_allclose(a.conv, b.conv, atol=1e-6)
    short = prefill_at(prefill, 2, 12, 0, *pools())[2]
    tail = np.asarray(short.conv[0, 0]).reshape(3, 160)
    assert not tail[0].any() and tail[1:].all()   # zeros left of position 0


def test_the_seeded_decay_neither_forgets_at_once_nor_never(model):
    """``a = exp(-exp(A_log) softplus(dt + dt_bias))`` on seeded weights and
    normed inputs: its median inside (0.5, 0.999) in every ssm layer."""
    params = model[0]
    h = jax.random.normal(jax.random.PRNGKey(3), (256, 64))
    for group in (params["layers"][0], params["layers"][2]):
        a = jax.tree.map(lambda leaf: leaf[0], group["ssm"])
        delta = jax.nn.softplus((h @ a["win"])[:, -8:] + a["dt_bias"])
        decay = jnp.exp(-jnp.exp(a["A_log"]) * delta)
        assert 0.5 < float(jnp.median(decay)) < 0.999
        assert float(jnp.min(jnp.exp(a["A_log"]))) >= 1.0


def test_the_ten_largest_softmaxed_are_the_softmaxs_ten_renormalised():
    """GraniteMoe keeps the top-k LOGITS and softmaxes those; the program's
    router softmaxes all and renormalises its top-k (SDAR's path): the same
    experts and the same gates, at this model's 10 of 72."""
    from ray_tpu.ops.moe import _route
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (64, 72))
    gates, experts = _route(logits, None, 10, "softmax", True, 1.0)
    kept, chosen = jax.lax.top_k(logits, 10)
    np.testing.assert_array_equal(experts, chosen)
    np.testing.assert_allclose(gates, jax.nn.softmax(kept, axis=-1),
                               rtol=1e-5)


@pytest.fixture(scope="module")
def scaled():
    """A one-layer dense model with the four multipliers at Granite's
    values: (configuration, parameters, tokens, its logits, its text)."""
    base = dataclasses.replace(
        LlamaConfig.tiny(), num_layers=1, dtype=jnp.float32, remat=False,
        attention="dense", embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.0625,
        logits_scaling=16.0)
    params = llama.llama_init(jax.random.PRNGKey(1), base)
    tokens = TOKENS[None, :8]
    program = jax.jit(lambda p, t: llama.llama_forward(p, t, base))
    return (base, params, tokens, program(params, tokens),
            program.lower(params, tokens).as_text())


@pytest.mark.parametrize("name,at_one", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.0), ("logits_scaling", 1.0)])
def test_a_multiplier_counts_and_is_absent_where_it_is_left_alone(
        scaled, name, at_one):
    """Each of the four moves the logits at its published value (the
    reference holds the values themselves: ``tests/benchmark/
    test_granite_hybrid_reference.py``), and a configuration that leaves it
    alone lowers to a shorter text: the operation is gone, not multiplied by
    one (``attention_multiplier`` 0 is ``head_dim ** -0.5``)."""
    base, params, tokens, got, text = scaled
    plain = dataclasses.replace(base, **{name: at_one})
    program = jax.jit(lambda p, t: llama.llama_forward(p, t, plain))
    assert float(jnp.abs(got - program(params, tokens)).max()) > 1e-6
    assert len(program.lower(params, tokens).as_text()) < len(text)


def test_a_configuration_without_the_new_fields_lowers_as_before():
    """``scripts/lowered_texts.py`` holds the benchmark's configurations to
    byte-equal texts against the parent; here the tiny default: every
    multiplier at its default adds no ``multiply`` or ``divide`` to it."""
    cfg = LlamaConfig.tiny()
    params = llama.llama_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    scaled = dataclasses.replace(cfg, logits_scaling=2.0)
    base, more = (jax.jit(lambda p, t, c=c: llama.llama_forward(
        p, t, c)).lower(params, tokens).as_text() for c in (cfg, scaled))
    assert more.count("stablehlo.divide") == base.count("stablehlo.divide") + 1


@pytest.mark.parametrize("change", [
    {"layer_pattern": ("ssm", "ssm", "full")},            # full last
    {"layer_pattern": ("full", "ssm", "ssm")},            # full first
    {"num_experts": 0, "experts_per_token": 0, "expert_share": (0, 1),
     "shared_experts": 0, "norm_topk_prob": False}])      # scanned periods
def test_what_check_allows_now(change):
    cfg = dataclasses.replace(CFG, **change)
    params = jax.eval_shape(lambda: llama.llama_init(jax.random.PRNGKey(0),
                                                     cfg))
    assert isinstance(params["layers"], tuple)
    assert len(params["layers"]) == 3


@pytest.mark.parametrize("change,message", [
    ({"layer_pattern": ("ssm", "linear", "full")}, "ONE other kind"),
    ({"layer_pattern": ("ssm", "conv", "full")}, "ONE other kind"),
    ({"linear_heads": 0}, "linear layers need"),
    ({"linear_gate_rank": 8}, "no correction"),
    ({"linear_neg_eigval": True}, "no correction"),
    ({"hc_mult": 2}, "hc_mult"),
    ({"ut_steps": 2, "post_norm": True}, "ut_steps > 1"),
    ({"attention_multiplier": 0.1, "kv_lora_rank": 8, "qk_nope_dim": 8,
      "qk_rope_dim": 8, "v_head_dim": 8}, "softmax scale of its own")])
def test_what_check_still_refuses(change, message):
    with pytest.raises(ValueError, match=message):
        llama._check(dataclasses.replace(CFG, **change))


def test_what_is_not_written_refuses_with_a_message(model):
    with pytest.raises(NotImplementedError, match="ssm layers"):
        llama.llama_loss(model[0], {"tokens": TOKENS[None, :9]}, CFG)
    with pytest.raises(NotImplementedError, match="logits_scaling"):
        cfg = dataclasses.replace(LlamaConfig.tiny(), logits_scaling=2.0)
        llama.llama_loss(llama.llama_init(jax.random.PRNGKey(0), cfg),
                         {"tokens": jnp.zeros((1, 9), jnp.int32)}, cfg)
    with pytest.raises(NotImplementedError, match="start"):
        llama.llama_prefill(model[0], CFG, TOKENS[None, :8], jnp.int32(8),
                            *pools(), TABLE[:1], 0, jnp.int32(0))
