"""The served prefill's causal attention through the flash forward kernel
(``models/llama.py::llama_prefill``), at tiny widths on the CPU, the kernel
interpreted.

One selector decides (``llama_prefill_attention``:
``ops/attention.py::resolve_attention`` of the config's ``attention`` and the
padded rung, dense for a block model and a latent one whatever the rung);
where it says flash the prefill gives the logits and writes the pages the
dense function gives, a padded tail and grouped k and v included; and the engine says of every prefill which of the
two its rung ran, on ``rt:engine.prefill`` and in ``stats()``.
"""

import asyncio
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import host_regions
from ray_tpu.models import gpt
from ray_tpu.models.llama import (LlamaConfig, llama_init,
                                  llama_init_paged_cache, llama_prefill,
                                  llama_prefill_attention)
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

PAGE = 16
BASE = LlamaConfig(vocab_size=97, max_seq_len=160, num_layers=2, num_heads=4,
                   num_kv_heads=2, embed_dim=64, mlp_dim=96, remat=False,
                   dtype=jnp.float32)
MODELS = {
    "gqa": BASE,
    "mha": dataclasses.replace(BASE, num_kv_heads=4),
    "gqa-qk-norm-experts": dataclasses.replace(
        BASE, qk_norm_per_head=True, mlp_dim=16, num_experts=8,
        experts_per_token=2),
}
BLOCK = dataclasses.replace(BASE, block_length=4, denoise_steps=2,
                            mask_token=96)
LATENT = dataclasses.replace(
    BASE, num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=12, rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0))


def prefill(cfg, params, S, length):
    tokens = np.zeros((1, S), np.int32)
    tokens[0, :length] = (np.arange(length) * 7 + 3) % cfg.vocab_size
    maxp = S // PAGE
    pools = llama_init_paged_cache(cfg, maxp + 1, PAGE)
    table = np.arange(1, maxp + 1, dtype=np.int32)[None]
    return jax.jit(lambda p, *a: llama_prefill(p, cfg, *a))(
        params, tokens, np.int32(length), *pools, table)


@pytest.mark.parametrize("S, length", [(128, 128), (128, 77), (64, 3)])
@pytest.mark.parametrize("model", MODELS)
def test_flash_prefill_gives_the_dense_logits_and_pages(model, S, length,
                                                        monkeypatch):
    """Pinned ``flash`` against pinned ``dense``: the logits at the last
    real position, the pages of the real positions and the scratch page of
    the padded ones (the k and v the kernel read are the ones written), an
    expert model's load; several sub-tiles a grid block."""
    import importlib
    monkeypatch.setattr(importlib.import_module(
        "ray_tpu.ops.flash_attention"), "_SUB_TILE", 32)
    cfg = MODELS[model]
    params = llama_init(jax.random.PRNGKey(5), cfg)
    dense = prefill(dataclasses.replace(cfg, attention="dense"), params, S,
                    length)
    flash = prefill(dataclasses.replace(cfg, attention="flash"), params, S,
                    length)
    assert len(flash) == len(dense) == (4 if cfg.num_experts else 3)
    np.testing.assert_allclose(flash[0], dense[0], atol=2e-5, rtol=1e-5)
    for got, want in zip(flash[1:3], dense[1:3]):
        real = -(-length // PAGE)               # pages 1..real hold a prompt
        assert np.abs(np.asarray(want[:, 1:real + 1])).max() > 0
        # layer 0's pages are written before any attention: the same bits
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_allclose(got, want, atol=2e-5)
    if cfg.num_experts:
        np.testing.assert_array_equal(flash[3], dense[3])


def test_auto_is_what_resolve_attention_says_of_the_rung(monkeypatch):
    """One selector: ``auto`` follows ``ops/attention.py::resolve_attention``
    rung by rung (dense on this backend, whatever the rung), a pin is the
    pin."""
    assert [llama_prefill_attention(BASE, S) for S in (512, 1024, 2048)] == \
        ["dense"] * 3                           # the CPU backend
    monkeypatch.setattr(gpt.jax, "default_backend", lambda: "tpu")
    assert [llama_prefill_attention(BASE, S)
            for S in (128, 512, 1024, 1040, 2048)] == \
        ["dense", "dense", "flash", "dense", "flash"]
    for pin in ("dense", "flash"):
        cfg = dataclasses.replace(BASE, attention=pin)
        assert {llama_prefill_attention(cfg, S) for S in (128, 2048)} == {pin}


@pytest.mark.parametrize("cfg", [BLOCK, LATENT], ids=["block", "latent"])
def test_block_and_latent_models_stay_dense(cfg, monkeypatch):
    """A mask causal over blocks, and q/k heads wider than v's: neither is
    written for the kernel.  ``auto`` runs them dense at every rung, on the
    chip too; a pinned ``flash`` raises, from the selector and so from the
    prefill, before anything is traced."""
    monkeypatch.setattr(gpt.jax, "default_backend", lambda: "tpu")
    assert llama_prefill_attention(cfg, 2048) == "dense"
    assert llama_prefill_attention(
        dataclasses.replace(cfg, attention="dense"), 2048) == "dense"
    pinned = dataclasses.replace(cfg, attention="flash")
    with pytest.raises(NotImplementedError, match="flash kernel"):
        llama_prefill_attention(pinned, 2048)
    with pytest.raises(NotImplementedError, match="flash kernel"):
        prefill(pinned, None, 64, 8)


# ------------------------------------------------------------- the engine

PROMPT, NEW = 256, 4
PROMPTS = ([5, 17, 3, 88, 41], list(range(1, 200)), [7, 8, 9])


def engine_run(cfg, tmp_path):
    """Three prompts through an engine of two rungs (128, 256), the last
    two under the profiler: (stats(), the traced ``rt:engine.prefill``
    regions' attributes)."""
    cfg = dataclasses.replace(cfg, max_seq_len=PROMPT + NEW)
    model = "llama" if isinstance(cfg, LlamaConfig) else "gpt"

    async def go():
        engine = InferenceEngine(EngineConfig(
            model=model, model_config=cfg, page_size=PAGE, num_pages=40,
            max_batch=2, max_prompt_len=PROMPT, max_new_tokens=NEW))

        async def consume(prompt):
            return [t async for t in engine.generate(prompt, NEW)]
        await consume(PROMPTS[0])
        jax.profiler.start_trace(str(tmp_path))
        try:
            for prompt in PROMPTS[1:]:
                await consume(prompt)
        finally:
            jax.profiler.stop_trace()
        stats = engine.stats()
        engine.close()
        return stats
    stats = asyncio.run(go())
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    return stats, [attrs for name, _, _, attrs
                   in host_regions.read_profile(path)["regions"]
                   if name == "rt:engine.prefill"]


@pytest.mark.parametrize("model, want", [
    ("llama-flash", "flash"), ("llama-auto", "dense"), ("gpt", "dense")])
def test_the_engine_says_what_each_prefill_ran(model, want, tmp_path):
    """``rt:engine.prefill`` carries ``attention`` beside the rung, and
    ``stats()["prefill"]["attention"]`` counts the prefills by it: the
    pinned kernel at both rungs, and dense for ``auto`` on this backend and
    for the GPT family, whose prefill has no kernel."""
    cfg = {"llama-flash": dataclasses.replace(BASE, attention="flash"),
           "llama-auto": BASE,
           "gpt": gpt.GPTConfig(vocab_size=97, num_layers=2, num_heads=4,
                                embed_dim=32, attention="flash", remat=False,
                                dtype=jnp.float32)}[model]
    stats, regions = engine_run(cfg, tmp_path)
    other = "dense" if want == "flash" else "flash"
    assert stats["prefill"] == {"attention": {want: 3, other: 0,
                                              "latent_chunk": 0}}
    assert stats["prefill_shapes"] == {128: 2, 256: 1}
    assert [(r["padded_len"], r["attention"]) for r in regions] == \
        [(256, want), (128, want)]
