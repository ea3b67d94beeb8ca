"""``ops/moe.py::moe_dropless`` told which experts it holds (ISSUE 51): the
routing is over all the router's experts, each share computes the part its
own experts give, and the shares' parts, with the shared expert counted
once, add up to the uncut layer; the assignments kept over the shares are
all the assignments made."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.moe import moe_dropless

T, D, M, R, K = 24, 32, 16, 16, 4


def layer(seed=0, scoring="sigmoid"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {"router": jax.random.normal(ks[0], (D, R)),
         "wgu": 0.3 * jax.random.normal(ks[1], (R, 2, D, M)),
         "wd": 0.3 * jax.random.normal(ks[2], (R, M, D))}
    if scoring == "sigmoid":
        p["router_bias"] = 0.1 * jax.random.normal(ks[3], (R,))
    shared = {"wgu": 0.3 * jax.random.normal(ks[4], (2, D, M)),
              "wd": 0.3 * jax.random.normal(ks[5], (M, D))}
    x = jax.random.normal(ks[6], (T, D))
    live = jnp.arange(T) < T - 5
    return p, shared, x, live


def share_of(p, at, of):
    held = R // of
    return {**p, "wgu": p["wgu"][at * held:(at + 1) * held],
            "wd": p["wd"][at * held:(at + 1) * held]}


@pytest.mark.parametrize("scoring,norm", [("sigmoid", True),
                                          ("softmax", False)])
@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares, scoring, norm):
    p, shared, x, live = layer(scoring=scoring)
    kw = dict(top_k=K, norm_topk_prob=norm, scoring=scoring,
              routed_scaling=2.446 if scoring == "sigmoid" else 1.0,
              live=live)
    whole, load = moe_dropless(x, p, shared=shared, **kw)
    alone, _ = moe_dropless(x, p, **kw)          # no shared expert
    parts, loads = zip(*(
        moe_dropless(x, share_of(p, at, shares), **kw,
                     first_expert=at * (R // shares))
        for at in range(shares)))
    # what every chip computes alike, the shared expert, counted once
    np.testing.assert_allclose(sum(parts) + (whole - alone), whole,
                               atol=2e-5)
    # a share's load is over ITS experts; side by side, the uncut layer's
    np.testing.assert_array_equal(jnp.concatenate(loads), load)
    assert all(part_load.shape == (R // shares,) for part_load in loads)
    assert int(sum(part_load.sum() for part_load in loads)) \
        == int(live.sum()) * K                   # kept, over all the shares
    if shares > 1:
        assert float(jnp.abs(parts[0] - alone).max()) > 1e-2


def test_a_share_nobody_chose_adds_nothing_and_no_nan():
    p, _, x, _ = layer(seed=1)
    # a router that sends everybody to the first four experts
    p = {**p, "router": jnp.zeros_like(p["router"]),
         "router_bias": jnp.where(jnp.arange(R) < K, 1.0, -1.0)}
    y, load = moe_dropless(x, share_of(p, 3, 4), top_k=K, scoring="sigmoid",
                           first_expert=12)
    assert int(load.sum()) == 0
    np.testing.assert_array_equal(y, jnp.zeros_like(y))
    y, load = moe_dropless(x, share_of(p, 0, 4), top_k=K, scoring="sigmoid",
                           first_expert=0)
    assert load.tolist() == [T] * K
    assert bool(jnp.isfinite(y).all()) and float(jnp.abs(y).max()) > 0.1


def test_in_a_stack_of_layers_a_share_reads_its_own_layer():
    p, shared, x, _ = layer(seed=2)
    other = layer(seed=3)[0]
    stack = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                         share_of(other, 1, 4), share_of(p, 1, 4))
    want, want_load = moe_dropless(x, share_of(p, 1, 4), top_k=K,
                                   scoring="sigmoid", first_expert=4,
                                   shared=shared)
    got, load = jax.jit(lambda layer: moe_dropless(
        x, stack, top_k=K, scoring="sigmoid", first_expert=4, shared=shared,
        layer=layer))(jnp.int32(1))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(load, want_load)
