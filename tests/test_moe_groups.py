"""``ops/moe.py::_route`` choosing inside groups (ISSUE 57; DeepSeek-V3's
``noaux_tc`` with ``n_group`` / ``topk_group``): against a plain loop a token;
one group is today's routing, to the traced program; and the sixteen shares
of a grouped layer, the shared expert counted once, add up to the uncut
layer as the benchmark's plain reference computes it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.moe import _route, moe_dropless

T, D, M, R, K = 24, 32, 16, 32, 4


def by_the_loop(logits, bias, top_k, groups, scale, norm=True):
    """The equations a token at a time, in numpy."""
    n, best = groups
    logits, gates_of = np.asarray(logits, np.float64), []
    for row in logits:
        score = 1.0 / (1.0 + np.exp(-row))
        choice = score + (0.0 if bias is None else np.asarray(bias))
        per = choice.reshape(n, -1)
        mark = per.max(-1) if bias is None else \
            np.sort(per, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-mark, kind="stable")[:best]
        open_ = np.full(n, -np.inf)
        open_[kept] = 0.0
        masked = (per + open_[:, None]).reshape(-1)
        chosen = np.argsort(-masked, kind="stable")[:top_k]
        gates = score[chosen]
        if norm:
            gates = gates / (gates.sum() + 1e-20)
        gates_of.append(dict(zip(chosen.tolist(), (gates * scale).tolist())))
    return gates_of


@pytest.mark.parametrize("groups", [(8, 4), (4, 1), (1, 1)])
@pytest.mark.parametrize("biased", [True, False])
def test_grouped_routing_is_the_loops(groups, biased):
    key = jax.random.PRNGKey(groups[0] * 10 + groups[1])
    logits = 2.0 * jax.random.normal(key, (T, R))
    bias = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (R,)) \
        if biased else None
    gates, experts = _route(logits, bias, K, "sigmoid", True, 2.5,
                            groups=groups)
    want = by_the_loop(logits, bias, K, groups, 2.5)
    width = R // groups[0]
    for t in range(T):
        assert set(np.asarray(experts[t]).tolist()) == set(want[t])
        for e, g in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            assert abs(float(g) - want[t][int(e)]) < 1e-5
        # never an expert of a group beyond the best
        assert len({int(e) // width for e in np.asarray(experts[t])}) \
            <= groups[1]
    if groups == (4, 1):             # the limit bites: other experts score
        free = _route(logits, bias, K, "sigmoid", True, 2.5)[1]   # higher
        assert (np.sort(np.asarray(free)) != np.sort(np.asarray(
            experts))).any()


def test_one_group_is_todays_program():
    logits = jnp.zeros((T, R))
    bias = jnp.zeros((R,))
    one = jax.make_jaxpr(lambda lg, b: _route(
        lg, b, K, "sigmoid", True, 2.5, groups=(1, 1)))(logits, bias)
    none = jax.make_jaxpr(lambda lg, b: _route(
        lg, b, K, "sigmoid", True, 2.5))(logits, bias)
    assert str(one) == str(none)
    limited = jax.make_jaxpr(lambda lg, b: _route(
        lg, b, K, "sigmoid", True, 2.5, groups=(4, 2)))(logits, bias)
    assert str(limited) != str(none)


def test_the_sixteen_shares_add_up_to_the_uncut_reference():
    """32 experts in 8 groups of 4, top-4 inside the 4 best groups, held two
    a share by sixteen shares: their parts and the shared expert once are
    the whole layer of ``benchmark/reference/deepseek_v32.py``."""
    from benchmark.reference import deepseek_v32 as reference
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    p = {"router": jax.random.normal(ks[0], (D, R)),
         "router_bias": 0.2 * jax.random.normal(ks[3], (R,)),
         "wgu": 0.3 * jax.random.normal(ks[1], (R, 2, D, M)),
         "wd": 0.3 * jax.random.normal(ks[2], (R, M, D))}
    shared = {"wgu": 0.3 * jax.random.normal(ks[4], (2, D, M)),
              "wd": 0.3 * jax.random.normal(ks[5], (M, D))}
    x = jax.random.normal(ks[6], (T, D))
    config = {"n_group": 8, "topk_group": 4, "num_experts_per_tok": K,
              "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        gates = reference.gate_matrix(x, p["router"], p["router_bias"],
                                      config)
        want = reference.routed(x, gates, p["wgu"], p["wd"]) \
            + reference.dense(x, shared)
    kw = dict(top_k=K, norm_topk_prob=True, scoring="sigmoid",
              routed_scaling=2.5, groups=(8, 4))
    # (the router is wider than the stack, so ``first_expert`` may be traced:
    # one program serves the sixteen shares)
    part = jax.jit(lambda wgu, wd, first: moe_dropless(
        x, {**p, "wgu": wgu, "wd": wd}, first_expert=first, **kw))
    parts, loads = zip(*(part(p["wgu"][at:at + 2], p["wd"][at:at + 2], at)
                         for at in range(0, R, 2)))
    once = moe_dropless(x, {**p, "wgu": p["wgu"][:2], "wd": p["wd"][:2]},
                        shared=shared, **kw)[0] - parts[0]
    np.testing.assert_allclose(sum(parts) + once, want, atol=3e-5)
    assert int(sum(load.sum() for load in loads)) == T * K
    assert float(jnp.abs(parts[0]).max()) > 1e-2
    # without the limit it is another layer
    free = moe_dropless(x, p, **{**kw, "groups": (1, 1)})[0]
    assert float(jnp.abs(free - sum(parts)).max()) > 1e-2
