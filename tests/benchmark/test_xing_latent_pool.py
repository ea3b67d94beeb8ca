"""``test_xing_reference.py::test_absorbed_decode_is_expanded_decode`` on the
latent pool as PR 50 stores it, ``[L, P, page, Wp]`` (a row's 40 values in one
128-lane tile): that test asserts the folded shape ``[L, P, page * W]`` of PR
34, which ``tests/conftest.py`` marks as expected to fail; its other
assertions run here, through the gather and through the kernel that walks
the page table (interpreted)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_xing_reference import SEQ, TINY, family, params  # noqa: F401

pa = importlib.import_module("ray_tpu.ops.paged_attention")


@pytest.mark.parametrize("read", ["gather", "kernel"])
def test_absorbed_decode_is_expanded_decode_on_padded_pages(
        family, params, read, monkeypatch):  # noqa: F811
    """One query a sequence against latent pages: the absorbed path
    (``wkv_b`` in the query and after the attention, the pool read as it
    lies) against keys and values expanded per head from the same rows."""
    from ray_tpu.models import llama
    monkeypatch.setattr(pa, "_kernel_backend", lambda: read == "kernel")
    cfg = family.program_config(TINY, SEQ, dtype=jnp.float32)
    p = jax.tree.map(lambda a: a[1].astype(jnp.float32),
                     {"attn": params["layers"]["attn"]})
    B, page, maxp, W = 3, 8, 6, 40
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    pages, none = llama.llama_init_paged_cache(cfg, B * maxp + 1, page)
    assert none is None and pages.shape == (3, B * maxp + 1, page, 128)
    assert llama.llama_paged_read(cfg, pages) == read
    table = jnp.arange(1, B * maxp + 1).reshape(B, maxp)
    lengths = jnp.array([5, 17, 40])
    rows = jax.random.normal(key[0], (48, B, W))
    for pos in range(48):                   # fill layer 1, a position a time
        pages = pa.append_latent(pages, 1, rows[pos], jnp.full((B,), pos),
                                 table)
    assert not np.asarray(pages[..., W:]).any()      # the padding stays zero
    assert not np.asarray(pages[0]).any() and not np.asarray(pages[2]).any()
    np.testing.assert_array_equal(
        np.asarray(pages[1, 1:, :, :W]).reshape(B, 48, W),
        np.moveaxis(np.asarray(rows), 0, 1))
    q_nope = jax.random.normal(key[1], (B, 4, 16))
    q_rope = jax.random.normal(key[2], (B, 4, 8))
    got = llama._mla_absorbed(cfg, p, q_nope, q_rope, pages, 1, lengths,
                              table)
    # expanded: per-head keys and values of every cached position
    lat = jnp.moveaxis(rows, 0, 1)                               # [B, S, W]
    kv = jnp.einsum("bsc,cnh->bsnh", lat[..., :32], p["attn"]["wkv_b"])
    scores = (jnp.einsum("bnh,bsnh->bns", q_nope, kv[..., :16])
              + jnp.einsum("bnh,bsh->bns", q_rope, lat[..., 32:])) \
        * llama.mla_softmax_scale(cfg)
    valid = jnp.arange(48)[None] < lengths[:, None]
    probs = jax.nn.softmax(jnp.where(valid[:, None], scores, -jnp.inf), -1)
    want = jnp.einsum("bns,bsnh->bnh", probs, kv[..., 16:])
    assert got.shape == (B, 4, 12)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
