"""The second half of ``test_tpu_compile.py``: the looped model's, the latent
model's and the block model's programs and weights compiled for a described
v5e at the published widths (Ouro-2.6B, Xing4.0-29B-A4B, SDAR-30B-A3B-Chat).
The SAME tests, moved here by PR 61 so that ``--dist loadfile`` can give the
two halves (172 s and 256 s of one worker under load) to two workers; the
fixtures and helpers stay in ``test_tpu_compile.py``, as the other
``test_tpu_compile_*.py`` files have it."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (PAGE, POOLS, _COMPILED,  # noqa: F401
                              _compile, _expert_kernels, _fits,
                              _llama_engine_program, _narrowest_is_no_larger,
                              _on, _paged_read_kernels, _pools_in_place,
                              _scoped, compiled_experts, compiled_paged_read,
                              no_persistent_cache, topo)


# Ouro-2.6B whole: published widths, all 48 layers run four times, with the
# engine of benchmark/configs/ouro-2.6b.json (12 slots, 241 pages: a pool of
# 192 layers, 6.06 GB, beside 5.34 GB of bf16 weights).  The pools are carried
# through BOTH loops and still updated where they lie.  What the programs
# need beyond arguments is not cache: the compiler hoists a relayout of whole
# weight stacks out of both loops (wq and wkv in decode, 1.13 GiB; wgu too in
# prefill, 3.19 GiB: PERF.md section 7), which is why 16 slots (8.08 GB of
# pool) compile to 15.68 GiB and 12 is what fits.
OURO_PROMPT, OURO_NEW, OURO_BATCH = 128, 192, 12
OURO_BUDGET = {"prefill": int(13.9 * 1024 ** 3), "decode": 12 * 1024 ** 3,
               "decode@narrow": 12 * 1024 ** 3}


def _ouro():
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=49152, num_layers=48, num_heads=16,
                       num_kv_heads=16, embed_dim=2048, mlp_dim=5632,
                       rope_theta=1e6, rms_eps=1e-6, ut_steps=4,
                       post_norm=True, max_seq_len=OURO_PROMPT + OURO_NEW)


@pytest.mark.parametrize("program", ["prefill", "decode", "decode@narrow"])
def test_ouro_engine_program_compiles(topo, program):
    cfg = _ouro()
    params, compiled, text = _llama_engine_program(
        topo, cfg, program, OURO_PROMPT, OURO_NEW,
        OURO_BATCH * (OURO_PROMPT + OURO_NEW) // PAGE + 1,
        max_batch=OURO_BATCH, gather_is_the_temporaries=False)
    assert params["layers"]["ln1_post"]["scale"].dtype == jnp.float32
    assert params["layers"]["mlp"]["wgu"].shape == (48, 2, 2048, 5632)
    assert "convert(%p__" not in text
    if program != "prefill":
        assert _scoped(text, "paged_read")
    assert _scoped(text, "paged_append") and _scoped(text, "loop_norm")
    # a pool layer for every pass: 192 of them, 1.5 MiB a cached position
    assert f"bf16[192,{OURO_BATCH * 20 + 1},16,2048]" in text
    assert _fits(compiled) < OURO_BUDGET[program]


def test_ouro_weights_are_made_as_the_stored_tree(topo):
    """The benchmark's family hands the replica ``llama_serving_params(
    llama_init(...))`` inside one jit: 5.34 GB come out and no f32 stack of
    the layers' matrices is ever held (made f32 first and cast by the
    engine they were 10.7 GB beside the 5.3 that stay)."""
    from ray_tpu.models.llama import llama_init, llama_serving_params
    cfg = _ouro()
    one = SingleDeviceSharding(topo.devices[0])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    compiled, text = _compile(
        lambda k: llama_serving_params(llama_init(k, cfg), cfg), key)
    memory = compiled.memory_analysis()
    stored = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(jax.eval_shape(
                     lambda: llama_serving_params(
                         llama_init(jax.random.PRNGKey(0), cfg), cfg))))
    assert 5.3e9 < stored < 5.4e9
    assert memory.output_size_in_bytes < stored * 1.001
    assert memory.temp_size_in_bytes < 64 * 1024 ** 2
    # f32 buffers with a leading 48 (what the entry computation's own
    # instructions produce; inside a fusion nothing is materialised): the
    # norm scales [48, 2048] only
    entry = text[text.index("\nENTRY "):]
    stacks = set(re.findall(r" = f32\[48,[\d,]*\]", entry))
    assert stacks == {" = f32[48,2048]"}, stacks


# Xing4.0-29B-A4B at its published widths, the leading dense layer and five of
# its 38 expert layers, with the engine of
# benchmark/configs/xing4.0-29b-a4b-6l.json (32 slots of 4,096 positions): ONE
# pool of latent pages [6, 8193, 16, 640] (a position's 576 values in five
# whole lane tiles), 1.007 GB, beside 9.6 GB of stored weights (bf16 matrices,
# the 64 routed experts among them).  Compiled sizes (PERF.md, PR 34, the pool
# folded [6, 8193, 16 x 576] and its read a gather): decode 10.10 GiB, prefill
# at the 1024 rung 9.94 GiB.
XING_PROMPT, XING_NEW, XING_BATCH = 1024, 3072, 32
XING_BUDGET = int(10.5 * 1024 ** 3)


def _xing():
    from benchmark import spec
    config = spec.load_json("configs", "xing4.0-29b-a4b-6l.json")
    family = spec.load_part("families", "xing")
    return family, family.program_config(config, XING_PROMPT + XING_NEW)


@pytest.mark.parametrize("program", ["prefill", "prefill@256", "decode",
                                     "decode@64"])
def test_xing_engine_program_compiles(topo, compiled_experts,
                                      compiled_paged_read, program):
    from ray_tpu.models.llama import (llama_decode_step,
                                      llama_init_paged_cache, llama_prefill)
    family, cfg = _xing()
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), cfg)))
    kp, vp = jax.eval_shape(lambda: llama_init_paged_cache(
        cfg, XING_BATCH * 256 + 1, PAGE))
    assert vp is None and kp.shape == (6, 8193, PAGE, 640)
    kp = _on(one, kp)
    maxp = (XING_PROMPT + XING_NEW) // PAGE

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    if program.startswith("decode"):
        # the top rung of the decode ladder, or its narrowest
        from ray_tpu.serve.engine.engine import decode_rungs
        width = int(program.partition("@")[2] or maxp)
        assert width in (decode_rungs(maxp)[0], maxp)

        def decode(width):
            if ("xing", width) not in _COMPILED:
                _COMPILED["xing", width] = _compile(
                    lambda p, *a: llama_decode_step(p, cfg, *a), params,
                    arg((XING_BATCH,)), arg((XING_BATCH,)), kp, None,
                    arg((XING_BATCH, width)), donate=POOLS)
            return _COMPILED["xing", width]
        compiled, text = decode(width)
        if width < maxp:
            _narrowest_is_no_larger(compiled, decode(maxp)[0])
    else:
        rung = int(program.partition("@")[2] or XING_PROMPT)
        compiled, text = _compile(
            lambda p, *a: llama_prefill(p, cfg, *a), params,
            arg((1, rung)), arg(()), kp, None, arg((1, maxp)), donate=POOLS)
    _pools_in_place(compiled, text, kp, pools=1)
    # the pool is the program's parameter in the layout it computes in: no
    # relayout of it on the way in or out (a last axis of 576 had one each)
    assert f"bf16[6,8193,{PAGE},640]{{3,2,1,0:" in text
    assert "bf16[6,8193,16,576]" not in text
    assert params["layers"]["mlp"]["wgu"].dtype == jnp.bfloat16
    assert params["layers"]["hc_mlp"]["proj"].dtype == jnp.float32
    assert "convert(%p__" not in text
    scopes = ["latent_append", "hc_coeff", "hc_mix", "moe_router",
              "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"]
    if program.startswith("decode"):
        scopes += ["latent_read", "mla_absorb"]
        # the read is the kernel that walks the page table, the one pool
        # its operand as it is stored (once in the unrolled dense layer,
        # once in the expert layers' scan), and nothing is gathered or laid
        # out again: the gather's rows were [32 slots x W pages, 16 x 576]
        assert len(_paged_read_kernels(text, "bf16[6,8193,16,640]",
                                       "latent_read", pools=1)) == 2
        assert "bf16[4096,9216]" not in text
        assert "bf16[32,2048,576]" not in text
        assert not re.search(r"bf16\[(8192|2048),16,640\]", text)
    for scope in scopes:
        assert _scoped(text, scope), scope
    # two grouped matmuls an expert layer, on the stacked bf16 experts of
    # the five expert layers, where they lie
    assert len(_expert_kernels(text, "bf16[640,3584,1024]",
                               "bf16[320,1024,3584]")) == 2
    assert "ragged-dot" not in text
    assert _fits(compiled) < XING_BUDGET


def test_xing_weights_are_made_as_the_stored_tree(topo):
    """The family's ``init`` inside one jit: 9.6 GB come out and no f32
    stack of matrices is held on the way (made f32 first they are 19 GB)."""
    family, cfg = _xing()
    one = SingleDeviceSharding(topo.devices[0])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    compiled, _ = _compile(lambda k: family.init(k, cfg), key)
    memory = compiled.memory_analysis()
    stored = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(jax.eval_shape(
                     lambda: family.init(jax.random.PRNGKey(0), cfg))))
    assert 9.55e9 < stored < 9.65e9
    assert memory.output_size_in_bytes < stored * 1.001
    assert memory.temp_size_in_bytes < 64 * 1024 ** 2


# SDAR-30B-A3B-Chat at its published widths, six of its 48 layers, with the
# engine of benchmark/configs/sdar-30b-a3b-chat-6l.json (32 slots of 1,536
# positions): K/V pools [6, 3073, 16, 512], 0.604 GB, beside 8.73 GB of stored
# weights (bf16 matrices, the 128 experts of a layer among them).  Its decode
# program is a block's pass: 4 positions a slot, 128 rows through the experts
# and the head, the unmasking behind it.
SDAR_PROMPT, SDAR_NEW, SDAR_BATCH = 512, 1024, 32
SDAR_BUDGET = int(10.0 * 1024 ** 3)


def _sdar():
    from benchmark import spec
    config = spec.load_json("configs", "sdar-30b-a3b-chat-6l.json")
    family = spec.load_part("families", "sdar")
    return family, family.program_config(config, SDAR_PROMPT + SDAR_NEW)


@pytest.mark.parametrize("program", ["prefill", "prefill@128", "decode",
                                     "decode@24"])
def test_sdar_engine_program_compiles(topo, compiled_experts, program):
    from ray_tpu.models.llama import (block_unmask, llama_block_step,
                                      llama_init_paged_cache, llama_prefill)
    family, cfg = _sdar()
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), cfg)))
    kp, vp = _on(one, jax.eval_shape(lambda: llama_init_paged_cache(
        cfg, SDAR_BATCH * 96 + 1, PAGE)))
    assert kp.shape == vp.shape == (6, 3073, PAGE, 4 * 128)
    maxp = (SDAR_PROMPT + SDAR_NEW) // PAGE

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if program.startswith("decode"):
        from ray_tpu.serve.engine.engine import decode_rungs
        width = int(program.partition("@")[2] or maxp)
        assert width in (decode_rungs(maxp)[0], maxp)
        rows = (SDAR_BATCH, cfg.block_length)

        def step(p, state, end, k, v, table):    # as the engine's loop has it
            logits, k, v, load = llama_block_step(p, cfg, state, end, k, v,
                                                  table)
            return None, k, v, load, block_unmask(cfg, logits, state, end)
        compiled, text = _compile(
            step, params, (arg(rows), arg(rows, jnp.bool_),
                           arg(rows[:1]), arg(rows[:1])), arg(rows[:1]),
            kp, vp, arg((SDAR_BATCH, width)), donate=POOLS)
        scopes = ["paged_append", "paged_read", "lm_head", "block_unmask"]
    else:
        rung = int(program.partition("@")[2] or SDAR_PROMPT)
        compiled, text = _compile(
            lambda p, *a: llama_prefill(p, cfg, *a), params,
            arg((1, rung)), arg(()), kp, vp, arg((1, maxp)), donate=POOLS)
        scopes = ["paged_append"]
        # no logits: the head is no argument of the program at all
        assert "lm_head" not in text
    _pools_in_place(compiled, text, kp)
    assert params["layers"]["mlp"]["wgu"].dtype == jnp.bfloat16
    assert "convert(%p__" not in text
    for scope in scopes + ["moe_router", "moe_dispatch", "moe_experts",
                           "moe_combine"]:
        assert _scoped(text, scope), scope
    # two grouped matmuls a layer, on the stacked bf16 experts of the six
    # layers, where they lie
    assert len(_expert_kernels(text, "bf16[1536,2048,768]",
                               "bf16[768,768,2048]")) == 2
    assert "ragged-dot" not in text
    assert _fits(compiled) < SDAR_BUDGET


def test_sdar_weights_are_made_as_the_stored_tree(topo):
    """The family's ``init`` inside one jit: 8.7 GB come out and no f32
    stack of matrices is held on the way (made f32 first they are 17 GB)."""
    family, cfg = _sdar()
    one = SingleDeviceSharding(topo.devices[0])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    compiled, _ = _compile(lambda k: family.init(k, cfg), key)
    memory = compiled.memory_analysis()
    stored = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(jax.eval_shape(
                     lambda: family.init(jax.random.PRNGKey(0), cfg))))
    assert 8.70e9 < stored < 8.75e9
    assert memory.output_size_in_bytes < stored * 1.001
    assert memory.temp_size_in_bytes < 64 * 1024 ** 2
