"""``ops/linear_attention.py``'s rule with a decay a KEY CHANNEL (ISSUE 51,
Kimi Delta Attention): the chunked form with its sub-chunks and the one-step
form agree with the recurrence at 1e-5 in float32, a rung's padded tail
leaves the state alone, a channel may decay to 1e-3 a position over a whole
chunk without anything overflowing, and with every channel of a head equal
the rule is the scalar one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import linear_attention as la


def inputs(S, N, dk, dv, seed=0, least=0.5):
    """q, k (unit length, q scaled), v, g = log alpha with alpha uniform in
    (least, 1) a key channel, beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = la.l2_normalise(jax.random.normal(ks[0], (S, N, dk))) * dk ** -0.5
    k = la.l2_normalise(jax.random.normal(ks[1], (S, N, dk)))
    v = jax.random.normal(ks[2], (S, N, dv))
    g = jnp.log(jax.random.uniform(ks[3], (S, N, dk), minval=least,
                                   maxval=1.0))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (S, N)))
    return q, k, v, g, beta


# (heads, key width, value width, positions): the published head at a few
# positions past a chunk, a plain narrow one, a folded one (two heads a
# panel), a whole number of chunks; lengths that are no whole chunk or
# sub-chunk among them
SHAPES = [(2, 128, 128, 70), (3, 8, 16, 150), (2, 8, 192, 37), (4, 16, 8, 128)]


@pytest.mark.parametrize("N,dk,dv,S", SHAPES)
def test_the_chunked_scan_with_sub_chunks_is_the_recurrence(N, dk, dv, S):
    q, k, v, g, beta = inputs(S, N, dk, dv)
    want_o, want_s = la.gated_delta_recurrent(q, k, v, g, beta)
    got_o, got_s = jax.jit(la.kda_chunked)(q, k, v, g, beta)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    assert float(jnp.abs(want_o).max()) > 0.02


@pytest.mark.parametrize("chunk,sub,group", [
    (64, 16, 8), (64, 64, 8), (32, 8, 8), (16, 16, 8),
    # groups of chunks: two whole groups, and a last group that is padding
    (16, 8, 4), (16, 8, 3), (32, 16, 1)])
def test_any_cut_into_groups_chunks_and_sub_chunks_is_the_same_scan(
        chunk, sub, group):
    q, k, v, g, beta = inputs(100, 2, 8, 16, seed=5)
    want_o, want_s = la.gated_delta_recurrent(q, k, v, g, beta)
    got_o, got_s = la.kda_chunked(q, k, v, g, beta, chunk=chunk, sub=sub,
                                  group=group)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    # and with a padded tail inside the last group
    got_o, got_s = la.kda_chunked(q, k, v, g, beta, length=jnp.int32(90),
                                  chunk=chunk, sub=sub, group=group)
    want_o, want_s = la.gated_delta_recurrent(
        *(a[:90] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(got_o[:90], want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)


@pytest.mark.parametrize("N,dk,dv,S", SHAPES[:2])
def test_a_padded_tail_leaves_the_state_as_at_length(N, dk, dv, S):
    q, k, v, g, beta = inputs(S, N, dk, dv, seed=1)
    length = S - 7
    got_o, got_s = la.kda_chunked(q, k, v, g, beta, length=jnp.int32(length))
    want_o, want_s = la.gated_delta_recurrent(
        *(a[:length] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(got_o[:length], want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    # without the length the tail's positions do write
    _, moved = la.kda_chunked(q, k, v, g, beta)
    assert float(jnp.abs(moved - want_s).max()) > 1e-2


@pytest.mark.parametrize("N,dk,dv,S", SHAPES)
def test_the_step_on_the_folded_state_is_the_recurrence(N, dk, dv, S):
    q, k, v, g, beta = inputs(S, N, dk, dv, seed=2)
    at = S - 3
    _, before = la.gated_delta_recurrent(*(a[:at] for a in (q, k, v, g, beta)))
    want_o, want_s = la.gated_delta_recurrent(
        *(a[at:at + 1] for a in (q, k, v, g, beta)), state=before)
    # two slots: the second an empty state with the same inputs
    folded = jnp.stack([la.fold_state(before),
                        jnp.zeros_like(la.fold_state(before))])
    row = tuple(jnp.stack([a[at], a[at]]) for a in (q, k, v, g, beta))
    got_o, got_s = jax.jit(la.kda_step)(*row, folded)
    np.testing.assert_allclose(got_o[0], want_o[0], atol=1e-5)
    np.testing.assert_allclose(la.unfold_state(got_s[0], N, dv), want_s,
                               atol=1e-5)
    # from nothing the read-out is beta (k . q) v
    np.testing.assert_allclose(
        got_o[1], (beta[at] * jnp.sum(k[at] * q[at], -1))[:, None] * v[at],
        atol=1e-5)


@pytest.mark.parametrize("alpha", [1e-3, 0.05, 0.5])
def test_a_channel_that_forgets_in_a_position_overflows_nothing(alpha):
    """Every channel at ``alpha`` over two whole chunks: exp(-G) of the
    plain product form would be 1e+192 at alpha 1e-3."""
    q, k, v, _, beta = inputs(128, 2, 16, 8, seed=3)
    g = jnp.full((128, 2, 16), np.log(alpha), jnp.float32)
    # one channel of every head keeps everything, beside those that forget
    g = g.at[:, :, 0].set(0.0)
    want_o, want_s = la.gated_delta_recurrent(q, k, v, g, beta)
    got_o, got_s = jax.jit(la.kda_chunked)(q, k, v, g, beta)
    assert bool(jnp.isfinite(got_o).all() and jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)


@pytest.mark.parametrize("form", ["recurrent", "chunked", "step"])
def test_with_every_channel_equal_it_is_the_scalar_rule(form):
    N, dk, dv, S = 2, 8, 192, 70
    q, k, v, g, beta = inputs(S, N, dk, dv, seed=4)
    g_head = g[..., 0]
    g_all = jnp.broadcast_to(g_head[..., None], g.shape)
    if form == "step":
        folded = la.fold_state(jax.random.normal(
            jax.random.PRNGKey(9), (N, dk, dv)))[None]
        got = la.kda_step(*(a[:1] for a in (q, k, v, g_all, beta)), folded)
        want = la.gated_delta_step(
            *(a[:1] for a in (q, k, v, g_head, beta)), folded)
    else:
        kda, scalar = {"recurrent": (la.gated_delta_recurrent,     # one
                                     la.gated_delta_recurrent),    # form
                       "chunked": (la.kda_chunked,
                                   la.gated_delta_chunked)}[form]
        got, want = kda(q, k, v, g_all, beta), scalar(q, k, v, g_head, beta)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_gate_is_a_decay_a_channel_in_float32():
    N, dk = 3, 4
    f = jax.random.normal(jax.random.PRNGKey(0), (5, N * dk), jnp.bfloat16)
    A_log = jnp.log(jnp.array([0.5, 2.0, 8.0]))
    dt_bias = jnp.linspace(-4.0, 0.0, N * dk)
    g = la.kda_gate(f, A_log, dt_bias)
    assert g.shape == (5, N, dk) and g.dtype == jnp.float32
    want = -jnp.exp(A_log)[:, None] * jax.nn.softplus(
        f.astype(jnp.float32) + dt_bias).reshape(5, N, dk)
    np.testing.assert_allclose(g, want, rtol=1e-6)
    assert float(g.max()) < 0.0          # alpha inside (0, 1)
