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


# ------------------------------------------- the step's kernel, on the pool

from ray_tpu.ops import linear_state  # noqa: E402


def step_inputs(B, N, dk, dv, channel, seed=0):
    """A decode batch's q, k, v, g, beta: ``inputs`` with the slots where the
    positions are, the decay a key channel's if ``channel`` (then beta in
    (0, 1), as Kimi Delta Attention has it)."""
    q, k, v, g, beta = inputs(B, N, dk, dv, seed)
    if channel:
        g = -0.5 * jax.random.uniform(jax.random.PRNGKey(seed + 9),
                                      (B, N, dk))
        beta = 0.5 * beta
    return q, k, v, g, beta


def on_the_kernel(monkeypatch, on=True):
    """``step_pool`` picks as on the chip (the kernel, which on this backend
    runs in the interpreter) or as on the CPU."""
    monkeypatch.setattr(la, "_kernel_backend", lambda: on)


# (heads, key width, value width, the decay a key channel's): Olmo-Hybrid's
# published head (45 panels, 15 of them two heads side by side), Kimi-Linear's
# (32 whole panels), four heads a panel, two whole panels a head
KERNEL_SHAPES = [(30, 96, 192, False), (32, 128, 128, True),
                 (4, 8, 160, True), (4, 16, 256, False)]


@pytest.mark.parametrize("N,dk,dv,channel", KERNEL_SHAPES)
def test_the_kernel_is_the_rule_on_a_layer_of_the_pool(
        monkeypatch, N, dk, dv, channel):
    """Against ``gated_delta_step`` / ``kda_step`` on a stacked pool at a
    layer other than 0: the read-out and the layer's rows agree, the other
    layers' rows are untouched, and a parked slot's rows come back to the
    bit (where the rule's are recomputed and selected away)."""
    B, L, layer = 3, 3, 1
    q, k, v, g, beta = step_inputs(B, N, dk, dv, channel)
    pool = jax.random.normal(jax.random.PRNGKey(7),
                             (L, B, *la.state_shape(N, dk, dv)))
    live = jnp.array([True, False, True])
    on_the_kernel(monkeypatch)
    assert la.state_step_kind(pool, N, dv) == "kernel"
    got_o, got = jax.jit(lambda *a: la.step_pool(*a))(
        q, k, v, g, beta, pool, jnp.int32(layer), live)
    rule = la.kda_step if channel else la.gated_delta_step
    want_o, want = rule(q, k, v, g, beta, pool[layer])
    np.testing.assert_allclose(got_o[live], want_o[live], atol=2e-6)
    np.testing.assert_allclose(got[layer][live], want[live], atol=2e-6)
    assert float(jnp.abs(want - pool[layer]).max()) > 0.1    # it did move
    assert got.dtype == jnp.float32 and got.shape == pool.shape
    assert jnp.array_equal(got[layer, 1], pool[layer, 1])    # parked
    for other in (0, 2):
        assert jnp.array_equal(got[other], pool[other])
    # the CPU's program is the rule, the select and the write-back
    on_the_kernel(monkeypatch, False)
    cpu_o, cpu = jax.jit(lambda *a: la.step_pool(*a))(
        q, k, v, g, beta, pool, jnp.int32(layer), live)
    np.testing.assert_allclose(cpu_o, want_o, atol=1e-6)
    np.testing.assert_allclose(cpu[layer][live], want[live], atol=1e-6)
    assert jnp.array_equal(cpu[layer, 1], pool[layer, 1])
    assert jnp.array_equal(cpu[0], pool[0])


@pytest.mark.parametrize("N,dk,dv,channel", KERNEL_SHAPES)
def test_the_kernel_over_positions_is_the_recurrence(
        monkeypatch, N, dk, dv, channel):
    """Five positions through the kernel, the pool carried from one to the
    next, against ``gated_delta_recurrent`` from the same state."""
    S = 5
    q, k, v, g, beta = step_inputs(S, N, dk, dv, channel, seed=3)
    state = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (N, dk, dv))
    want_o, want_s = la.gated_delta_recurrent(q, k, v, g, beta, state)
    on_the_kernel(monkeypatch)

    @jax.jit
    def steps(pool):
        def one(pool, row):
            o, pool = la.step_pool(*(a[None] for a in row), pool,
                                   jnp.int32(1), jnp.ones((1,), bool))
            return pool, o[0]
        return jax.lax.scan(one, pool, (q, k, v, g, beta))
    pool = jnp.zeros((2, 1, *la.state_shape(N, dk, dv))).at[1, 0].set(
        la.fold_state(state))
    pool, got_o = steps(pool)
    np.testing.assert_allclose(got_o, want_o, atol=5e-6)
    np.testing.assert_allclose(la.unfold_state(pool[1, 0], N, dv), want_s,
                               atol=5e-6)
    assert not pool[0].any()


@pytest.mark.parametrize("slots,unroll", [(1, 1), (2, 2), (4, 1)])
def test_the_kernel_at_several_slots_a_grid_step(slots, unroll):
    """``slots`` slots a grid step, and ``unroll`` groups of heads a turn of
    the kernel's loop, give one slot's results, parked slots in a block
    beside live ones included."""
    B, N, dk, dv = 4, 4, 8, 192
    q, k, v, g, beta = step_inputs(B, N, dk, dv, False, seed=4)
    pool = jax.random.normal(jax.random.PRNGKey(8),
                             (1, B, *la.state_shape(N, dk, dv)))
    live = jnp.array([True, True, False, True])
    _, whole, side = la._panel_plan(N, dv)
    rows = jnp.stack([la._values_to_panels(a, N, dv) for a in (
        v, *(jnp.broadcast_to(b[..., None], v.shape)
             for b in (beta, jnp.sum(k * q, axis=-1))))], axis=1)
    cols = linear_state.columns(
        jnp.exp(jnp.broadcast_to(g[..., None], k.shape)), k, q)
    got_o, got = linear_state.state_step(
        pool, 0, live, cols, rows, heads=N, whole=whole, side=side,
        slots=slots, unroll=unroll)
    want_o, want = la.gated_delta_step(q, k, v, g, beta, pool[0])
    np.testing.assert_allclose(la._panels_to_values(got_o, N, dv)[live],
                               want_o[live], atol=2e-6)
    np.testing.assert_allclose(got[0][live], want[live], atol=2e-6)
    assert jnp.array_equal(got[0, 2], pool[0, 2])


@pytest.mark.parametrize("case,want", [
    ("the chip, Olmo-Hybrid's pool", "kernel"),
    ("the chip, Kimi-Linear's pool", "kernel"),
    ("the CPU", "rule"),
    ("a plain layout: 16 lanes", "rule"),
    ("key channels no whole sublane tile", "rule"),
    ("a pool in bfloat16", "rule"),
    ("more heads than a lane tile holds columns for", "rule"),
    ("a planted step", "rule"),
])
def test_who_steps_the_states(monkeypatch, case, want):
    """``state_step_kind`` from the backend, the pool's shape and type, and
    whether the module's two steps are its own (a numerics tool's planted
    step has to reach the program on every backend)."""
    on_the_kernel(monkeypatch, case != "the CPU")
    N, dk, dv = {"the chip, Kimi-Linear's pool": (32, 128, 128),
                 "a plain layout: 16 lanes": (3, 8, 16),
                 "key channels no whole sublane tile": (30, 12, 192),
                 "more heads than a lane tile holds columns for":
                     (44, 96, 128)}.get(
                     case, (30, 96, 192))
    pool = jax.ShapeDtypeStruct(
        (2, 4, *la.state_shape(N, dk, dv)),
        jnp.bfloat16 if case == "a pool in bfloat16" else jnp.float32)
    if case == "a planted step":
        real = la.gated_delta_step
        monkeypatch.setattr(la, "gated_delta_step",
                            lambda *a: real(*a[:4], 2.0 * a[4], a[5]))
    assert la.state_step_kind(pool, N, dv) == want
    if case == "a planted step":
        # and the planted step is what runs
        q, k, v, g, beta = step_inputs(4, N, dk, dv, False)
        pool = jnp.zeros(pool.shape)
        live = jnp.ones((4,), bool)
        o, _ = la.step_pool(q, k, v, g, beta, pool, 0, live)
        np.testing.assert_allclose(o, 2.0 * real(
            q, k, v, g, beta, pool[0])[0], rtol=1e-6)


def test_the_kernel_refuses_a_pool_it_is_not_written_for():
    with pytest.raises(ValueError, match="no linear-state kernel"):
        linear_state.state_step(
            jnp.zeros((1, 2, 3, 8, 16)), 0, jnp.ones((2,), bool),
            jnp.zeros((2, 8, 128)), jnp.zeros((2, 3, 3, 16)), heads=3,
            whole=1, side=0)
    for how in ({"slots": 2}, {"unroll": 2}):     # 3 slots, 1 group
        with pytest.raises(ValueError, match="no whole number of"):
            linear_state.state_step(
                jnp.zeros((1, 3, 3, 8, 128)), 0, jnp.ones((3,), bool),
                jnp.zeros((3, 8, 128)), jnp.zeros((3, 3, 3, 128)), heads=2,
                whole=1, side=2, **how)


def test_a_turn_of_the_kernels_loop():
    """The module's choice of groups a turn: the hybrid's fifteen groups of
    three panels go one a turn, Kimi's thirty-two heads eight a turn."""
    assert linear_state._turn(15, 3) == 1
    assert linear_state._turn(32, 1) == 8
    assert linear_state._turn(1, 3) == 1 and linear_state._turn(6, 1) == 6
