"""The short causal convolution in its second form (ISSUE 55): width 3 and NO
activation after it, which is the whole of a gated short-convolution layer's
mixing over time (``models/llama.py::_conv_operator``, LFM2's
``Lfm2ShortConv``), against an explicit three-term sum; a prefill's tail and
the one-position step give the whole sequence's result for prompts of 1, 2, 3
positions and one that fills no rung; a rung's padding leaves the tail alone;
and the first form (SiLU, any width) is what it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.linear_attention import (causal_conv, causal_conv_step,
                                          conv_tail)

K, C = 3, 16


@pytest.fixture(scope="module")
def taps():
    return jax.random.uniform(jax.random.PRNGKey(0), (K, C), jnp.float32,
                              -K ** -0.5, K ** -0.5)


def explicit(u, w):
    """c_t = w[0] u[t-2] + w[1] u[t-1] + w[2] u[t], zeros before the
    sequence, a position at a time."""
    u, w = np.asarray(u, np.float64), np.asarray(w, np.float64)
    out = np.zeros_like(u)
    for t in range(len(u)):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                out[t] += w[j] * u[t - (K - 1) + j]
    return out


def test_the_convolution_is_the_three_term_sum_and_nothing_after_it(taps):
    u = jax.random.normal(jax.random.PRNGKey(1), (11, C))
    got = causal_conv(u, taps, silu=False)
    want = explicit(u, taps)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert (want < -0.5).any()          # a SiLU would have clipped these
    with_silu = causal_conv(u, taps)
    np.testing.assert_allclose(with_silu, jax.nn.silu(jnp.asarray(
        want, jnp.float32)), atol=1e-6)
    assert float(jnp.abs(with_silu - got).max()) > 0.3
    # the first form at its own width is untouched by the argument
    w4 = jax.random.normal(jax.random.PRNGKey(2), (4, C))
    np.testing.assert_array_equal(causal_conv(u, w4),
                                  causal_conv(u, w4, True))


@pytest.mark.parametrize("length", [1, 2, 3, 21])
def test_prefill_then_step_is_the_whole_sequence(taps, length):
    """A prompt of ``length`` positions padded to a rung of 32 (the padding
    is OTHER tokens' values, not zeros: a tail taken at the rung's end would
    show), its tail handed to a slot, then 6 positions stepped one at a
    time: every position's result is the whole sequence's."""
    total, rung = length + 6, 32
    u = jax.random.normal(jax.random.PRNGKey(3), (total, C))
    want = explicit(u, taps)
    padded = jnp.concatenate([u[:length], 7.0 + jnp.ones((rung - length, C))])
    prefill = causal_conv(padded, taps, silu=False)
    np.testing.assert_allclose(prefill[:length], want[:length], atol=1e-6)
    tail = conv_tail(padded, length, K)
    assert tail.shape == ((K - 1) * C,)
    # zeros on the left of a prompt shorter than the tail
    have = min(length, K - 1)
    np.testing.assert_array_equal(tail[:(K - 1 - have) * C], 0.0)
    np.testing.assert_array_equal(
        tail[(K - 1 - have) * C:], u[length - have:length].reshape(-1))
    tails = jnp.stack([jnp.full_like(tail, 5.0), tail])     # slot 1 of two
    for t in range(length, total):
        x = jnp.stack([jnp.zeros((C,)), u[t]])
        out, tails = causal_conv_step(x, taps, tails, silu=False)
        np.testing.assert_allclose(out[1], want[t], atol=1e-6)
    assert tails.shape == (2, (K - 1) * C)


def test_a_stale_tail_is_another_result(taps):
    """What the hand-over is for: stepping on from a tail one position old,
    or from the rung's end, gives other numbers at the first steps."""
    u = jax.random.normal(jax.random.PRNGKey(4), (9, C))
    want = explicit(u, taps)
    padded = jnp.concatenate([u[:5], 3.0 * jnp.ones((3, C))])
    for stale in (conv_tail(padded, 4, K), conv_tail(padded, 8, K)):
        out, _ = causal_conv_step(u[5][None], taps, stale[None], silu=False)
        assert np.abs(np.asarray(out[0]) - want[5]).max() > 0.05


def test_bfloat16_inputs_accumulate_in_float32(taps):
    u = jax.random.normal(jax.random.PRNGKey(5), (13, C)).astype(jnp.bfloat16)
    got = causal_conv(u, taps.astype(jnp.bfloat16), silu=False)
    assert got.dtype == jnp.bfloat16
    want = explicit(u.astype(jnp.float32),
                    taps.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=2.0 ** -7, rtol=2.0 ** -7)
