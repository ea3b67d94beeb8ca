"""The page-table walker (``ops/paged_read.py``) at heads of HALF a lane tile
(64: LFM2's 32 query heads on 8 K/V heads, a row of 512 columns = 4 tiles, two
K/V heads a tile) against dense attention and against the jnp gather on the
same pools, in Pallas interpret mode (ISSUE 55).  The queries go in
block-diagonal and the result's diagonal blocks are cut out after the call, so
nothing inside the kernel cuts a tile; the 128-wide cases trace what they
traced (``tests/test_paged_attention_kernel.py`` holds those, unedited).

Tolerances as that file argues them: 1e-5 in float32 (the order of a sum),
2**-5 against the bfloat16 gather, 2**-7 against the gather in float32 on the
same bfloat16 values.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.paged_read import paged_read_attention, supported

pa = importlib.import_module("ray_tpu.ops.paged_attention")

H, PAGE, PAGES_PER_BLOCK, WIDTH, LAYERS = 64, 16, 2, 6, 2
BLOCK = PAGE * PAGES_PER_BLOCK
LENGTHS = np.array([1, PAGE, BLOCK + 1, WIDTH * PAGE, 37], np.int32)
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -5}


def pools(kv_heads, rep, dtype, seed=0):
    """(q, poisoned K and V, clean K and V, table) as the 128-wide file
    makes them: pages scattered out of order, slot 0 parked on page 0, NaN
    wherever no sequence holds a row (0 in the clean pools)."""
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


def dense(q, k_pages, v_pages, layer, lengths, table):
    """Attention written out a sequence and a head at a time in float64."""
    q, k_pages, v_pages = (np.asarray(a, np.float64)
                           for a in (q, k_pages, v_pages))
    B, N, _ = q.shape
    rep = N // (k_pages.shape[3] // H)
    out = np.zeros((B, N, H))
    for b, length in enumerate(lengths):
        at = np.arange(length)
        rows = table[b, at // PAGE], at % PAGE
        k, v = k_pages[layer][rows], v_pages[layer][rows]       # [T, NKV*H]
        for n in range(N):
            cols = slice(n // rep * H, (n // rep + 1) * H)
            s = k[:, cols] @ q[b, n] * H ** -0.5
            p = np.exp(s - s.max())
            out[b, n] = (p / p.sum()) @ v[:, cols]
    return out


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kv_heads,rep", [(8, 4), (2, 2), (4, 8)])
def test_the_walker_at_heads_of_64_reads_what_dense_attention_reads(
        kv_heads, rep, dtype):
    """LFM2's 32 / 8 and two more groupings, ``layer`` traced under a scan:
    the kernel on the POISONED pools against dense attention and against the
    gather on the clean ones."""
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
    exact = np.stack([dense(q.astype(jnp.float32), *(
        a.astype(jnp.float32) for a in clean), layer, LENGTHS,
        np.asarray(table)) for layer in range(LAYERS)])
    tight = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(got, exact, rtol=tight, atol=tight)
    assert np.abs(want[0] - want[1]).max() > 0.1


def test_the_default_block_and_the_public_function(monkeypatch):
    """The block ``_resolve`` picks for a row of 512 bfloat16 columns (32
    pages: 1 MiB of K and V), and ``paged_attention`` itself once the backend
    is said to be the chip's."""
    from ray_tpu.ops import paged_read
    assert paged_read._resolve(16, 16 * 512 * 2 * 2, None, True) == (32, True)
    q, poisoned, clean, table = pools(8, 4, jnp.float32, seed=1)
    lengths = jnp.asarray(LENGTHS)
    want = pa.paged_attention(q, *clean, 1, lengths, table)
    monkeypatch.setattr(pa, "_kernel_backend", lambda: True)
    got = pa.paged_attention(q, *poisoned, 1, lengths, table)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_supported_says_which_heads_of_64(monkeypatch):
    """Half a lane tile with several query heads a K/V head in rows of whole
    tiles, and nothing else new: GPT-2's equal heads stay with the gather, a
    row of an odd number of heads too, and the 128-wide answers stand."""
    bf16 = jnp.bfloat16
    assert supported((4, 32, 64), bf16, (2, 9, 16, 512), bf16)     # LFM2
    assert supported((4, 4, 64), bf16, (2, 9, 16, 128), bf16)
    assert not supported((4, 12, 64), bf16, (2, 9, 16, 768), bf16)  # GPT-2
    assert not supported((4, 6, 64), bf16, (2, 9, 16, 192), bf16)  # 1.5 tiles
    assert not supported((4, 32, 64), bf16, (2, 9, 8, 512), bf16)  # the page
    assert not supported((4, 32, 64), jnp.float32, (2, 9, 16, 512), bf16)
    assert not supported((4, 8, 32), bf16, (2, 9, 16, 128), bf16)  # a quarter
    assert supported((4, 32, 128), bf16, (2, 9, 16, 1024), bf16)
    assert supported((4, 16, 128), bf16, (2, 9, 16, 2048), bf16)
    assert not supported((4, 32, 128), bf16, (2, 9, 8, 1024), bf16)

    def kind(heads, kv_heads, head, page=16):
        return pa.paged_read_kind(
            jax.ShapeDtypeStruct((4, heads, head), bf16),
            jax.ShapeDtypeStruct((2, 9, page, kv_heads * head), bf16))
    assert kind(32, 8, 64) == "gather"           # the CPU: always
    monkeypatch.setattr(pa, "_kernel_backend", lambda: True)
    assert kind(32, 8, 64) == "kernel" and kind(12, 12, 64) == "gather"
    from ray_tpu.models.llama import LlamaConfig, llama_paged_read
    cfg = LlamaConfig(num_heads=32, num_kv_heads=8, head_size=64,
                      embed_dim=2048)
    assert llama_paged_read(cfg, jax.ShapeDtypeStruct(
        (2, 9, 16, 512), bf16)) == "kernel"
