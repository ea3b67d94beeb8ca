"""The seam between ``models/llama.py``'s one layer and what it is run
against (ISSUE 47).

The layer takes its attention state from outside: a kind ``models/llama.py``
does not know, written here (a plain K/V buffer a sequence, no pages), handed
to the served trunk gives the paged token step's logits.  And the engine takes
a model's programs from ``models/serving.py``'s record, by no name of its own:
a function replaced on ``models.llama`` before an engine is constructed is
the one that engine traces.
"""

import ast
import concurrent.futures
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, serving_model
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

PAGE, PROMPT, NEW, BATCH = 8, 16, 8, 2
SEQ = PROMPT + NEW
MAXP = SEQ // PAGE
KV = LlamaConfig(vocab_size=97, max_seq_len=SEQ, num_layers=2, num_heads=4,
                 num_kv_heads=2, embed_dim=32, mlp_dim=48,
                 dtype=jnp.float32, attention="dense", remat=False)
CONFIGS = {
    "kv": KV,
    "latent": dataclasses.replace(
        KV, num_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_dim=8,
        qk_rope_dim=4, v_head_dim=6,
        rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0)),
    "looped": dataclasses.replace(KV, ut_steps=3, post_norm=True),
    "block": dataclasses.replace(KV, qk_norm_per_head=True, block_length=4,
                                 denoise_steps=2, mask_token=96),
}


def buffer_state(cfg, pos):
    """A state kind of this file's: every sequence's keys and values in a
    buffer of its own, ``pools`` = two arrays [pool layers, B, SEQ, NKV, H];
    a step writes row ``pos`` and attends to the rows up to it."""
    def kv(p, layer, pools, q, k, v):
        kbuf, vbuf = pools
        B, N, H = q.shape
        rows = jnp.arange(B)
        kbuf = kbuf.at[layer, rows, pos].set(k)
        vbuf = vbuf.at[layer, rows, pos].set(v)
        grouped = q.reshape(B, cfg.num_kv_heads, -1, H)
        scores = jnp.einsum("bgrh,btgh->bgrt", grouped, kbuf[layer]) \
            / np.sqrt(H)
        seen = jnp.arange(SEQ)[None] <= pos[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None], scores, -1e30), axis=-1)
        o = jnp.einsum("bgrt,btgh->bgrh", probs, vbuf[layer])
        return o.reshape(B, N, H), (kbuf, vbuf)
    return llama.AttentionState(kv)


def buffer_step(params, cfg, token, pos, kbuf, vbuf):
    """``llama_decode_step`` as it is written, on the buffer's state."""
    cos_t, sin_t = llama.rope_tables(cfg.max_seq_len, cfg.head_dim,
                                     cfg.rope_theta)
    x = llama._embed(cfg, params, token)
    (x, kbuf, vbuf), _ = llama._served_trunk(
        cfg, params, x, cos_t[pos][:, None], sin_t[pos][:, None],
        buffer_state(cfg, pos), pos > 0, kbuf, vbuf)
    return jnp.einsum("bd,dv->bv", x, params["lm_head"]), kbuf, vbuf


@pytest.mark.parametrize("name", ["kv", "looped"])
def test_a_state_kind_the_model_does_not_know_gives_the_paged_logits(name):
    cfg = CONFIGS[name]
    params = llama.llama_init(jax.random.PRNGKey(1), cfg)
    tokens = np.random.default_rng(0).integers(0, 97, (BATCH, 11))
    table = 1 + np.arange(BATCH * MAXP, dtype=np.int32).reshape(BATCH, MAXP)
    kp, vp = llama.llama_init_paged_cache(cfg, BATCH * MAXP + 1, PAGE)
    pool_layers = cfg.ut_steps * cfg.num_layers
    assert kp.shape[0] == pool_layers
    kbuf = vbuf = jnp.zeros((pool_layers, BATCH, SEQ, cfg.num_kv_heads,
                             cfg.head_dim), jnp.float32)
    paged = jax.jit(lambda *a: llama.llama_decode_step(params, cfg, *a))
    plain = jax.jit(lambda *a: buffer_step(params, cfg, *a))
    for at in range(tokens.shape[1]):
        token = jnp.asarray(tokens[:, at], jnp.int32)
        pos = jnp.full((BATCH,), at, jnp.int32)
        want, kp, vp = paged(token, pos, kp, vp, jnp.asarray(table))
        got, kbuf, vbuf = plain(token, pos, kbuf, vbuf)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert np.abs(np.asarray(want)).max() > 1e-3


def test_the_engine_names_no_model():
    """``engine.py`` imports nothing from ``models.gpt`` or ``models.llama``
    and compares ``model`` with no name: an ``ast`` walk."""
    import ray_tpu.serve.engine.engine as engine
    tree = ast.parse(open(engine.__file__).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert node.module not in ("ray_tpu.models.gpt",
                                       "ray_tpu.models.llama"), node.lineno
            if node.module == "ray_tpu.models":
                assert not {a.name for a in node.names} & {"gpt", "llama"}
        if isinstance(node, ast.Import):
            assert not any(a.name.startswith(("ray_tpu.models.gpt",
                                              "ray_tpu.models.llama"))
                           for a in node.names), node.lineno
        if isinstance(node, ast.Compare):
            assert not any(isinstance(side, ast.Constant)
                           and side.value in ("gpt", "llama")
                           for side in (node.left, *node.comparators)), \
                node.lineno


def test_an_unknown_model_is_refused_as_before():
    with pytest.raises(ValueError, match="unknown engine model 'bert'"):
        InferenceEngine(EngineConfig(model="bert"))


@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_engine_traces_what_the_module_holds_when_it_is_built(
        name, monkeypatch):
    """The programs come through the record alone, and the record looks the
    module's functions up when it is asked: spies planted on ``models.llama``
    before construction are what every rung of the engine traces."""
    cfg = CONFIGS[name]
    step_name = "llama_block_step" if cfg.block_length \
        else "llama_decode_step"
    traced = {"llama_prefill": 0, step_name: 0}

    def spy(fn_name):
        real = getattr(llama, fn_name)

        def planted(*args):
            traced[fn_name] += 1
            return real(*args)
        monkeypatch.setattr(llama, fn_name, planted)
        return planted
    prefill, step = spy("llama_prefill"), spy(step_name)
    served = serving_model("llama", cfg)
    assert served.prefill is prefill and served.step is step
    assert served.block == cfg.block_length
    engine = InferenceEngine(EngineConfig(
        model="llama", model_config=cfg, page_size=PAGE,
        num_pages=BATCH * MAXP + 1, max_batch=BATCH, max_prompt_len=PROMPT,
        max_new_tokens=NEW))
    try:
        programs = [*engine._rung_programs.values(),
                    *engine._decode_programs.values()]
        concurrent.futures.wait(programs)
        for program in programs:
            program.result()         # a rung that failed to compile raises
        assert traced["llama_prefill"] == len(engine._rungs)
        assert traced[step_name] == len(engine._decode_rungs)
        assert (engine._v_pages is None) == bool(cfg.kv_lora_rank)
    finally:
        engine.close()
