"""``ops/linear_attention.py`` (ISSUE 48): the gated delta rule's three forms
agree, a rung's padded tail leaves the state alone, the folded layout holds
every value once, and the convolution's two forms are one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import linear_attention as la


def inputs(S, N, dk, dv, seed=0):
    """q, k (unit length, q scaled), v, g = log alpha in (-0.5, 0), beta in
    (0, 2): about half the betas are over 1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = la.l2_normalise(jax.random.normal(ks[0], (S, N, dk))) * dk ** -0.5
    k = la.l2_normalise(jax.random.normal(ks[1], (S, N, dk)))
    v = jax.random.normal(ks[2], (S, N, dv))
    g = -0.5 * jax.random.uniform(ks[3], (S, N))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (S, N)))
    return q, k, v, g, beta


# (heads, key width, value width, positions): folded with two heads a panel
# (the published 192), plain (16), folded with four a panel (160), whole
# panels only (256); lengths that are no multiple of 64, and one that is
SHAPES = [(2, 8, 192, 100), (3, 8, 16, 70), (4, 8, 160, 33), (4, 8, 256, 64)]


@pytest.mark.parametrize("N,dk,dv,S", SHAPES)
def test_the_chunked_scan_is_the_recurrence(N, dk, dv, S):
    q, k, v, g, beta = inputs(S, N, dk, dv)
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    want_o, want_s = la.gated_delta_recurrent(q, k, v, g, beta)
    got_o, got_s = jax.jit(la.gated_delta_chunked)(q, k, v, g, beta)
    np.testing.assert_allclose(got_o, want_o, atol=5e-6)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6)
    assert float(jnp.abs(want_o).max()) > 0.5


@pytest.mark.parametrize("N,dk,dv,S", SHAPES[:2])
def test_a_padded_tail_leaves_the_state_as_at_length(N, dk, dv, S):
    q, k, v, g, beta = inputs(S, N, dk, dv, seed=1)
    length = S - 7
    got_o, got_s = la.gated_delta_chunked(q, k, v, g, beta,
                                          length=jnp.int32(length))
    want_o, want_s = la.gated_delta_recurrent(
        *(a[:length] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(got_o[:length], want_o, atol=5e-6)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6)
    # without the length the tail's positions do write
    _, moved = la.gated_delta_chunked(q, k, v, g, beta)
    assert float(jnp.abs(moved - want_s).max()) > 1e-2


@pytest.mark.parametrize("N,dk,dv,S", SHAPES)
def test_the_step_on_the_folded_state_is_the_recurrence(N, dk, dv, S):
    q, k, v, g, beta = inputs(S, N, dk, dv, seed=2)
    at = S - 3
    _, state = la.gated_delta_recurrent(*(a[:at] for a in (q, k, v, g, beta)))
    folded = la.fold_state(state)
    assert folded.shape == la.state_shape(N, dk, dv)
    assert folded.size == state.size                 # nothing padded
    assert jnp.array_equal(la.unfold_state(folded, N, dv), state)
    rows = [a[at:at + 1] for a in (q, k, v, g, beta)]
    want_o, want_s = la.gated_delta_recurrent(*rows, state)
    # two slots: the second's state is another, and stays its own
    other = la.fold_state(2.0 * state)
    got_o, got = jax.jit(la.gated_delta_step)(
        *(jnp.concatenate([a, a]) for a in rows), jnp.stack([folded, other]))
    np.testing.assert_allclose(got_o[0], want_o[0], atol=2e-6)
    np.testing.assert_allclose(la.unfold_state(got[0], N, dv), want_s,
                               atol=2e-6)
    assert float(jnp.abs(got_o[1] - got_o[0]).max()) > 1e-3


def test_the_published_state_folds_into_45_panels_of_128_lanes():
    assert la.state_shape(30, 96, 192) == (45, 96, 128)
    assert 45 * 96 * 128 == 30 * 96 * 192
    assert la.state_shape(30, 96, 128) == (30, 96, 128)
    assert la.state_shape(3, 8, 192) == (3, 8, 192)   # 3 heads: no pairs


def test_the_convolutions_two_forms_are_one():
    K, C, S = 4, 12, 20
    x = jax.random.normal(jax.random.PRNGKey(3), (S, C))
    w = jax.random.normal(jax.random.PRNGKey(4), (K, C))
    y = la.causal_conv(x, w)
    # causal: position t is silu(sum_j w_j x_{t-3+j}), zeros before 0
    want = sum(w[j] * x[5 - 3 + j] for j in range(K))
    np.testing.assert_allclose(y[5], jax.nn.silu(want), rtol=1e-6)
    np.testing.assert_allclose(y[0], jax.nn.silu(w[3] * x[0]), rtol=1e-6)
    for length in (1, 2, 13):
        tail = la.conv_tail(x, jnp.int32(length), K)
        assert tail.shape == ((K - 1) * C,)
        if length < K - 1:                   # zeros where nothing was yet
            assert not tail[:(K - 1 - length) * C].any()
        got, nxt = la.causal_conv_step(x[length][None], w, tail[None])
        np.testing.assert_allclose(got[0], y[length], rtol=1e-6, atol=1e-7)
        assert jnp.array_equal(nxt[0], la.conv_tail(x, length + 1, K))


def test_the_gates():
    a = jnp.array([[-3.0, 0.0, 3.0]])
    g, beta = la.decay_and_beta(a, a, jnp.zeros(3), jnp.zeros(3), True)
    assert (g < 0).all() and (jnp.diff(g[0]) < 0).all()   # more a: forgets
    np.testing.assert_allclose(beta, 2 * jax.nn.sigmoid(a))
    _, plain = la.decay_and_beta(a, a, jnp.zeros(3), jnp.zeros(3), False)
    np.testing.assert_allclose(plain, jax.nn.sigmoid(a))
