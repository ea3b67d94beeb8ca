"""The engine stores each parameter in the dtype its two programs read it
in (``gpt_serving_params`` / ``llama_serving_params``), at tiny widths on
the CPU.

What the programs return for the stored tree is what they return for the
caller's f32 tree, bit for bit; the leaves the steps cast are stored in
``cfg.dtype`` and no f32 copy of them is kept; every other leaf is the
caller's own array; and the programs traced on the stored tree cast no
parameter any more, so the list beside the steps is the steps' own.
"""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt import GPTConfig, gpt_init
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

PAGE, PROMPT, NEW, BATCH = 8, 16, 8, 4
LLAMA = LlamaConfig(vocab_size=97, max_seq_len=PROMPT + NEW, num_layers=2,
                    num_heads=4, num_kv_heads=2, embed_dim=32, mlp_dim=48,
                    attention="dense", remat=False)
FAMILIES = {
    "llama-dense": ("llama", LLAMA, llama_init),
    "llama-experts": ("llama", dataclasses.replace(
        LLAMA, num_kv_heads=4, mlp_dim=16, num_experts=8,
        experts_per_token=3, qk_norm=True), llama_init),
    "gpt": ("gpt", GPTConfig(vocab_size=97, max_seq_len=PROMPT + NEW,
                             num_layers=2, num_heads=4, embed_dim=32,
                             attention="dense", remat=False), gpt_init),
}
# the leaves the steps cast with .astype(cfg.dtype), by path from the root
CAST = {
    "llama-dense": {"wte", "lm_head", "layers/attn/wq", "layers/attn/wkv",
                    "layers/attn/wo", "layers/mlp/wgu", "layers/mlp/wd"},
    "llama-experts": {"wte", "lm_head", "layers/attn/wq", "layers/attn/wkv",
                      "layers/attn/wo"},
    "gpt": {"wte", "wpe", "layers/attn/wqkv", "layers/attn/wo",
            "layers/attn/bo", "layers/mlp/wi", "layers/mlp/bi",
            "layers/mlp/wo", "layers/mlp/bo"},
}


def leaves(tree):
    """{"layers/attn/wq": leaf, ...}"""
    return {"/".join(k.key for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def build(family, dtype=jnp.bfloat16):
    """(the caller's f32 tree, an engine made from it)"""
    model, cfg, init = FAMILIES[family]
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = init(jax.random.PRNGKey(3), cfg)
    engine = InferenceEngine(
        EngineConfig(model=model, model_config=cfg, page_size=PAGE,
                     num_pages=BATCH * (PROMPT + NEW) // PAGE + 1,
                     max_batch=BATCH, max_prompt_len=PROMPT,
                     max_new_tokens=NEW), params=params)
    return params, engine


def program_args(engine, program, params):
    """Arguments of one call of ``program`` on ``params``: a prefill of an
    11-token prompt, or the decode step that follows it in slot 0 (its
    pools filled by that prefill, run on the same tree)."""
    maxp = engine._maxp
    tokens = np.zeros((1, PROMPT), np.int32)
    tokens[0, :11] = np.arange(11) * 7 % 97
    table = np.zeros((BATCH, maxp), np.int32)
    table[0] = np.arange(1, maxp + 1)
    prefill = (params, tokens, np.int32(11), engine._k_pages,
               engine._v_pages, table[:1])
    if program == "prefill":
        return prefill
    _, kp, vp, *_ = engine._prefill_program(*prefill)
    token, pos = np.zeros((BATCH,), np.int32), np.zeros((BATCH,), np.int32)
    token[0], pos[0] = 5, 11
    return params, token, pos, kp, vp, table


def parameter_casts(jaxpr):
    """convert_element_type f32 -> bf16 applied to an input of the jaxpr it
    sits in (a parameter, or a layer's slice of one inside the scan's
    body), in this jaxpr and every one nested in it."""
    found = 0
    for eqn in jaxpr.eqns:
        operand = eqn.invars[0] if eqn.invars else None
        if (eqn.primitive.name == "convert_element_type"
                and eqn.params["new_dtype"] == jnp.bfloat16
                and operand.aval.dtype == jnp.float32
                and any(operand is v for v in jaxpr.invars)):
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += parameter_casts(sub)
    return found


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("family", FAMILIES)
def test_stored_tree_gives_the_callers_bits(family, program):
    params, engine = build(family)
    try:
        run = getattr(engine, f"_{program}_program")
        args = {"caller": program_args(engine, program, params),
                "stored": program_args(engine, program, engine._params)}
        want, got = run(*args["caller"]), run(*args["stored"])
        assert len(got) == (4 if "experts" in family else 3)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.abs(np.asarray(got[0])).max() > 0
        # the steps' own casts: one a cast leaf on the caller's tree (the
        # tables are read twice), none left on the stored tree
        casts = {name: parameter_casts(jax.make_jaxpr(run)(*a).jaxpr)
                 for name, a in args.items()}
        assert casts["stored"] == 0
        assert casts["caller"] >= len(CAST[family])
    finally:
        engine.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_each_leaf_is_stored_as_the_steps_read_it(family):
    params, engine = build(family)
    try:
        given, stored = leaves(params), leaves(engine._params)
        assert jax.tree.structure(engine._params) == \
            jax.tree.structure(params)
        assert CAST[family] < set(given)
        for path, leaf in stored.items():
            assert leaf.shape == given[path].shape
            if path in CAST[family]:
                assert leaf.dtype == jnp.bfloat16, path
            else:                 # norms, q/k norms, router, experts
                assert leaf.dtype == jnp.float32, path
                assert leaf is given[path], path
        if "experts" in family:
            assert stored["layers/mlp/wgu"].ndim == 5   # [L, E, 2, D, M]
        assert engine.stats()["weight_bytes"] == sum(
            leaf.nbytes for leaf in stored.values())
        assert engine.stats()["weight_bytes"] < sum(
            leaf.nbytes for leaf in given.values())
    finally:
        engine.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_float32_engine_stores_the_callers_arrays(family):
    params, engine = build(family, jnp.float32)
    try:
        given, stored = leaves(params), leaves(engine._params)
        assert all(stored[path] is given[path] for path in given)
        assert engine.stats()["weight_bytes"] == sum(
            leaf.nbytes for leaf in given.values())
    finally:
        engine.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_engine_keeps_no_f32_copy_of_a_cast_leaf(family):
    params, engine = build(family)
    try:
        given = leaves(params)
        watched = {path: weakref.ref(given[path]) for path in CAST[family]}
        kept = weakref.ref(given["ln_f/scale"])
        del params, given
        gc.collect()
        assert [path for path, ref in watched.items()
                if ref() is not None] == []
        assert kept() is not None      # the engine's own leaf now
    finally:
        engine.close()
