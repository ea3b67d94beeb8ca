"""The token step's paged read as a kernel (``ops/paged_read.py``) against
the jnp ``paged_attention`` on the same pools, in Pallas interpret mode on
the CPU (ISSUE 49), and the selector that picks between the two; the latent
kind (one pool of padded rows, ONE copy a page for both products: ISSUE 50)
against ``paged_latent_attention``'s gather the same way.

Tolerance.  In float32 the two differ by the order of a float32 sum (the
kernel sums block by block under a running maximum): 1e-5 absolute on
results of order 1.  In bfloat16 the kernel rounds the probabilities (2**-9
relative) for the product with V and the result (2**-8 relative: one step,
0.0078 at 1 and 0.0156 at 2) and nothing else, so against the gather IN
FLOAT32 on the same bfloat16 values it is held to 2**-7 absolute and as much
relative.  The bfloat16 gather is the looser of the two: its einsum rounds
the SCORES to bfloat16 (a step of 2**-6 at scores of 2 to 4: a probability
off by 1.6%, a result of order 1 by 0.01-0.02) and the probabilities after
the normalisation; against it the bound is 2**-5.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_read
from ray_tpu.ops.paged_read import paged_read_attention, supported

# the module: ray_tpu.ops re-exports the function under the same name
pa = importlib.import_module("ray_tpu.ops.paged_attention")

H, PAGE, PAGES_PER_BLOCK, WIDTH, LAYERS = 128, 16, 2, 6, 3
BLOCK = PAGE * PAGES_PER_BLOCK
# a parked slot (page 0, one position), a page's last position, a block's
# first position, the whole table, and a length inside a page
LENGTHS = np.array([1, PAGE, BLOCK + 1, WIDTH * PAGE, 37], np.int32)
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -5}


def pools(kv_heads, rep, dtype, seed=0):
    """(q, poisoned K and V, clean K and V, table): every sequence's pages
    scattered out of order over the pool, slot 0 parked on page 0, and NaN
    in every page and row that no sequence holds (the clean pools hold 0
    there: the gather multiplies what it masks by a probability of 0)."""
    rng = np.random.default_rng(seed)
    slots, columns = len(LENGTHS), kv_heads * H
    pages = slots * WIDTH + 1
    k, v = (rng.standard_normal((LAYERS, pages, PAGE, columns))
            .astype(np.float32) for _ in range(2))
    q = rng.standard_normal((slots, kv_heads * rep, H)).astype(np.float32)
    table = rng.permutation(np.arange(1, pages)).reshape(
        slots, WIDTH).astype(np.int32)
    table[0] = 0
    held = np.zeros((pages, PAGE), bool)
    for slot, length in enumerate(LENGTHS):
        at = np.arange(length)
        held[table[slot, at // PAGE], at % PAGE] = True
    poisoned = [np.where(held[None, :, :, None], a, np.nan) for a in (k, v)]
    clean = [np.where(held[None, :, :, None], a, 0.0) for a in (k, v)]

    def cast(a):
        return jnp.asarray(a, dtype)
    return cast(q), [cast(a) for a in poisoned], [cast(a) for a in clean], \
        jnp.asarray(table)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kv_heads,rep", [(8, 4), (16, 1), (30, 1), (4, 8)])
def test_kernel_reads_what_the_gather_reads(kv_heads, rep, dtype):
    """The served head shapes at toy widths, ``layer`` traced under
    ``lax.scan``: the kernel on the POISONED pools (a read past ``lengths``
    is a NaN) against the gather on the clean ones, layer by layer."""
    q, poisoned, clean, table = pools(kv_heads, rep, dtype)
    lengths = jnp.asarray(LENGTHS)

    def every_layer(read, k_pages, v_pages):
        def body(_, layer):
            return _, read(q, k_pages, v_pages, layer, lengths, table)
        return jax.jit(lambda: jax.lax.scan(
            body, None, jnp.arange(LAYERS, dtype=jnp.int32))[1])()

    got = every_layer(
        lambda *a: paged_read_attention(
            *a, sm_scale=H ** -0.5, interpret=True,
            pages_per_block=PAGES_PER_BLOCK), *poisoned)
    want = every_layer(pa.paged_attention, *clean)
    assert got.shape == want.shape == (LAYERS, len(LENGTHS), kv_heads * rep,
                                       H)
    assert got.dtype == q.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got, want, rtol=TOLERANCE[dtype],
                               atol=TOLERANCE[dtype])
    if dtype == jnp.bfloat16:
        exact = np.asarray(every_layer(
            lambda q, *a: pa.paged_attention(q.astype(jnp.float32), *a),
            *(a.astype(jnp.float32) for a in clean)))
        np.testing.assert_allclose(got, exact, rtol=2.0 ** -7, atol=2.0 ** -7)
    # the layers are not each other's: layer l was read at index l
    assert np.abs(want[0] - want[1]).max() > 0.1


def test_one_block_holds_the_table():
    """The default block (256 positions) over a table of 6 pages: one
    block a sequence, cut to the table's width."""
    q, poisoned, clean, table = pools(8, 4, jnp.float32, seed=1)
    lengths = jnp.asarray(LENGTHS)
    got = paged_read_attention(q, *poisoned, 2, lengths, table,
                               sm_scale=H ** -0.5, interpret=True)
    want = pa.paged_attention(q, *clean, 2, lengths, table)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# the latent kind's lengths: a parked slot (page 0, one position), a page's
# last position, a block's last and the next block's first, inside a page,
# and into a third block; the table (6 pages, 96 positions) wider than any
LATENT_LENGTHS = np.array([1, PAGE, BLOCK, BLOCK + 1, 37, 2 * BLOCK + 5],
                          np.int32)


def latent_pool(rank, rope, dtype, seed=0):
    """(q [slots, 4, rank + rope], the poisoned pool, the clean pool, table)
    of latent pages as ``llama_init_paged_cache`` lays them out: rows padded
    with zeros to whole lane tiles; NaN (the clean pool: 0) in every page and
    row that no sequence holds, padding columns included."""
    rng = np.random.default_rng(seed)
    slots, W = len(LATENT_LENGTHS), rank + rope
    Wp, pages = pa.latent_width(rank, rope), slots * WIDTH + 1
    rows = rng.standard_normal((LAYERS, pages, PAGE, Wp)).astype(np.float32)
    rows[..., W:] = 0.0
    q = rng.standard_normal((slots, 4, W)).astype(np.float32)
    table = rng.permutation(np.arange(1, pages)).reshape(
        slots, WIDTH).astype(np.int32)
    table[0] = 0
    held = np.zeros((pages, PAGE), bool)
    for slot, length in enumerate(LATENT_LENGTHS):
        at = np.arange(length)
        held[table[slot, at // PAGE], at % PAGE] = True
    held = held[None, :, :, None]
    return (jnp.asarray(q, dtype), jnp.asarray(np.where(held, rows, np.nan),
                                               dtype),
            jnp.asarray(np.where(held, rows, 0.0), dtype), jnp.asarray(table))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rank,rope", [(512, 64), (128, 128), (32, 8)])
def test_latent_kernel_reads_what_the_gather_reads(rank, rope, dtype,
                                                   monkeypatch):
    """Xing's row (576 values in 640 columns, the result cut to the values'
    512 inside the kernel), a row that is whole tiles already, and the tiny
    configurations' (40 in 128, the cut outside): ``paged_latent_attention``
    told the backend is the chip's, on the POISONED pool at two pages a
    block, against its gather on the clean one, ``layer`` traced under
    ``lax.scan``; in the K/V cases' tolerances."""
    q, poisoned, clean, table = latent_pool(rank, rope, dtype)
    lengths = jnp.asarray(LATENT_LENGTHS)
    scale = (rank + rope) ** -0.5

    def every_layer(q, pool):
        def body(_, layer):
            return _, pa.paged_latent_attention(
                q, pool, layer, lengths, table, sm_scale=scale, rank=rank)
        return jax.jit(lambda: jax.lax.scan(
            body, None, jnp.arange(LAYERS, dtype=jnp.int32))[1])()

    want = every_layer(q, clean)
    with monkeypatch.context() as patched:
        patched.setattr(pa, "_kernel_backend", lambda: True)
        patched.setattr(paged_read, "_BLOCK_TOKENS", BLOCK)
        patched.setattr(paged_read, "_BLOCK_BYTES", 0)
        lowered = jax.jit(pa.paged_latent_attention, static_argnames=(
            "sm_scale", "rank")).lower(q, poisoned, 1, lengths, table,
                                       sm_scale=scale, rank=rank).as_text()
        assert "gather" not in lowered
        got = every_layer(q, poisoned)
    assert got.shape == want.shape == (LAYERS, len(LATENT_LENGTHS), 4, rank)
    assert got.dtype == q.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got, want, rtol=TOLERANCE[dtype],
                               atol=TOLERANCE[dtype])
    if dtype == jnp.bfloat16:
        exact = np.asarray(every_layer(q.astype(jnp.float32),
                                       clean.astype(jnp.float32)))
        np.testing.assert_allclose(got, exact, rtol=2.0 ** -7, atol=2.0 ** -7)
    assert np.abs(want[0] - want[1]).max() > 0.1


@pytest.mark.parametrize("columns,pools,pages", [
    (1024, 2, 16), (2048, 2, 16), (3840, 2, 16), (640, 1, 64), (128, 1, 256)],
    ids=["mistral", "olmoe-ouro", "olmo-hybrid", "xing", "one-tile"])
def test_a_block_is_as_many_pages_as_the_pages_bytes_say(columns, pools,
                                                         pages):
    """256 positions where that is a MiB of copies or more (every K/V
    shape, as measured), doubled to a MiB where a page is small (Xing's
    latent page of 20 KB: 1,024 positions); a caller's own count stands."""
    page_bytes = 16 * columns * 2 * pools
    assert paged_read._resolve(16, page_bytes, None, True) == (pages, True)
    assert paged_read._resolve(16, page_bytes, 2, False) == (2, False)


def test_one_copy_a_page_gives_what_two_give():
    """The latent kind of the walker (one pool, the values the copied rows'
    first columns) against the K/V kind handed the same pool twice."""
    q, poisoned, _, table = latent_pool(512, 64, jnp.float32, seed=1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 64)))
    args = (2, jnp.asarray(LATENT_LENGTHS), table)
    how = dict(sm_scale=0.07, interpret=True, pages_per_block=PAGES_PER_BLOCK)
    one = paged_read_attention(q, poisoned, None, *args, columns=512, **how)
    two = paged_read_attention(q, poisoned, poisoned, *args, **how)
    assert one.shape == (len(LATENT_LENGTHS), 4, 512) and two.shape == q.shape
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two[..., :512]))
    with pytest.raises(ValueError, match="no paged-read kernel"):
        paged_read_attention(q[..., :512], poisoned, None, *args, **how)


def test_the_selector_reads_the_backend_and_the_shapes(monkeypatch):
    """On the CPU backend the public function is the gather whatever the
    shapes (the CPU suite's numerics are the parent's by construction);
    told the backend is the chip's, it picks the kernel for heads of whole
    lane tiles on pages of whole sublane tiles, and nothing else."""
    def kind(heads, kv_heads, head, page, dtype=jnp.bfloat16, q_dtype=None):
        return pa.paged_read_kind(
            jax.ShapeDtypeStruct((4, heads, head), q_dtype or dtype),
            jax.ShapeDtypeStruct((2, 9, page, kv_heads * head), dtype))

    def latent_kind(page, dtype):
        from ray_tpu.models.llama import LlamaConfig, llama_paged_read
        cfg = LlamaConfig(num_heads=32, kv_lora_rank=512, qk_rope_dim=64,
                          q_lora_rank=768, qk_nope_dim=128, v_head_dim=128,
                          dtype=dtype)
        pool = jax.ShapeDtypeStruct((6, 9, page, pa.latent_width(512, 64)),
                                    dtype)
        return llama_paged_read(cfg, pool)

    served = [(32, 8, 128, 16), (16, 16, 128, 16), (30, 30, 128, 16)]
    assert {kind(*shape) for shape in served} == {"gather"}
    assert latent_kind(16, jnp.bfloat16) == "gather"
    q, _, clean, table = latent_pool(32, 8, jnp.float32)
    lowered = jax.jit(pa.paged_latent_attention, static_argnames=(
        "sm_scale", "rank")).lower(q, clean, 1, jnp.asarray(LATENT_LENGTHS),
                                   table, sm_scale=1.0, rank=32).as_text()
    assert "gather" in lowered and "custom_call" not in lowered
    q, _, clean, table = pools(8, 4, jnp.float32)
    lowered = jax.jit(pa.paged_attention).lower(
        q, *clean, 1, jnp.asarray(LENGTHS), table).as_text()
    assert "gather" in lowered and "custom_call" not in lowered

    monkeypatch.setattr(pa, "_kernel_backend", lambda: True)
    assert {kind(*shape) for shape in served} == {"kernel"}
    assert kind(32, 8, 128, 8, jnp.float32) == "kernel"
    assert kind(32, 8, 128, 8) == "gather"      # the engine's default page
    assert kind(12, 12, 64, 16) == "gather"     # GPT-2's heads of 64
    assert kind(32, 8, 128, 16, q_dtype=jnp.float32) == "gather"
    assert not supported((4, 32, 128), jnp.bfloat16, (2, 9, 8, 1024),
                         jnp.bfloat16)
    with pytest.raises(ValueError, match="no paged-read kernel"):
        paged_read_attention(
            jnp.zeros((4, 12, 64)), jnp.zeros((2, 9, 16, 768)),
            jnp.zeros((2, 9, 16, 768)), 0, jnp.ones((4,), jnp.int32),
            jnp.zeros((4, 3), jnp.int32), sm_scale=1.0)
    # the latent read asks the same of its padded queries and its one pool
    assert latent_kind(16, jnp.bfloat16) == "kernel"
    assert latent_kind(8, jnp.float32) == "kernel"
    assert latent_kind(8, jnp.bfloat16) == "gather"  # the engine's default
    # picked, the kernel is what the public function traces
    q, poisoned, clean, table = pools(8, 4, jnp.float32)
    got = pa.paged_attention(q, *poisoned, 1, jnp.asarray(LENGTHS), table)
    monkeypatch.setattr(pa, "_kernel_backend", lambda: False)
    want = pa.paged_attention(q, *clean, 1, jnp.asarray(LENGTHS), table)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def _engine(pages):
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine.engine import EngineConfig, InferenceEngine
    # latent pages: a row of 32 + 8 values stored in one lane tile
    kind = dict(num_kv_heads=2, kv_lora_rank=32, q_lora_rank=48,
                qk_nope_dim=16, qk_rope_dim=8, v_head_dim=12,
                rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0)) \
        if pages == "latent" else dict(num_kv_heads=1)
    model = LlamaConfig(vocab_size=256, max_seq_len=64, num_layers=2,
                        num_heads=2, embed_dim=256, mlp_dim=256,
                        dtype=jnp.float32, **kind)
    return InferenceEngine(EngineConfig(
        model="llama", model_config=model, page_size=8, num_pages=33,
        max_batch=3, max_prompt_len=32, max_new_tokens=32))


@pytest.mark.parametrize("pages", ["kv", "latent"])
def test_an_engine_counts_what_its_steps_read_the_pages_with(monkeypatch,
                                                             pages):
    """Two engines of one model (heads of 128 or latent rows of one lane
    tile, float32, pages of 8: whole sublane tiles), one told the backend
    is the chip's: the same tokens,
    the steps counted by their read, and ``kv_gathered_token_steps`` what
    the read fetches: under the kernel every slot's own positions in whole
    pages (a parked slot one page), under the gather the rung for every
    slot."""
    import asyncio
    prompts, new = ([7] * 5, [9] * 19), 12

    def served(engine):
        async def run():
            async def one(prompt):
                return [t async for t in engine.generate(prompt, new)]
            return [await one(prompt) for prompt in prompts]
        try:
            return asyncio.run(run()), engine.stats()
        finally:
            engine.close()

    want, gathered = served(_engine(pages))
    monkeypatch.setattr(pa, "_kernel_backend", lambda: True)
    got, walked = served(_engine(pages))
    assert walked["kv_page_kind"] == gathered["kv_page_kind"] == pages
    assert got == want and all(len(tokens) == new for tokens in got)
    steps = walked["steps"]
    assert steps == gathered["steps"] == 2 * (new - 1)
    assert gathered["decode"]["paged_read"] == {"gather": steps, "kernel": 0}
    assert walked["decode"]["paged_read"] == {"gather": 0, "kernel": steps}
    # one sequence at a time, two slots parked on a page each: at the step
    # that writes position ``pos`` it holds ``pos // 8 + 1`` pages
    assert walked["kv_gathered_token_steps"] == sum(
        8 * (pos // 8 + 1 + 2) for prompt in prompts
        for pos in range(len(prompt), len(prompt) + new - 1))
    assert gathered["kv_gathered_token_steps"] == sum(
        3 * 8 * width * count
        for width, count in gathered["decode_shapes"].items())
    assert walked["kv_live_token_steps"] == gathered["kv_live_token_steps"] \
        < walked["kv_gathered_token_steps"] \
        < gathered["kv_gathered_token_steps"]
