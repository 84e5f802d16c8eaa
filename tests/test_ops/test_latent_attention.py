"""The two latent attention kernels (`ops/flash_attention.py`) in interpret
mode against their XLA fallback (gather + masked einsum): one page serves
every head as K and, in its leading columns, as V; rows of unequal extent,
an extent that ends mid-page, extent 0 rows, sentinel pages; float32 to
1e-5 and bfloat16 (the served type) to its rounding."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("easydist_tpu.ops.flash_attention")

N_PAGES, PT, WIDTH, VALUES, HEADS = 12, 8, 128, 48, 4
SENT = N_PAGES
TABLE = jnp.asarray([[3, 5, 1, SENT], [7, SENT, SENT, SENT],
                     [SENT] * 4, [2, 4, 6, 8]], jnp.int32)


def _pages(dtype):
    pages = jax.random.normal(jax.random.PRNGKey(0), (N_PAGES, PT, WIDTH),
                              jnp.float32)
    return pages.at[:, :, 72:].set(0.0).astype(dtype)   # 72 real values


def _tol(dtype):
    return dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 \
        else dict(atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pages_per_step", [None, 1, 2])
def test_the_decode_kernel_is_its_fallback(dtype, pages_per_step):
    lengths = jnp.asarray([20, 5, 0, 32], jnp.int32)
    q = (0.3 * jax.random.normal(jax.random.PRNGKey(1), (4, HEADS, WIDTH),
                                 jnp.float32)).astype(dtype)
    pages = _pages(dtype)
    want = fa.latent_decode_attention(q, pages, TABLE, lengths, VALUES,
                                      backend="xla")
    got = fa.flash_latent_decode_attention(
        q, pages, TABLE, lengths, VALUES, pages_per_step=pages_per_step,
        interpret=True)
    assert got.shape == (4, HEADS, VALUES) and got.dtype == dtype
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               **_tol(dtype))
    assert not np.asarray(got, np.float32)[~live].any()   # reads nothing


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [8, 3], ids=["a_page", "verify_width"])
def test_the_chunk_kernel_is_its_fallback(dtype, chunk):
    extents = jnp.asarray([24, 8, 0, 29], jnp.int32)
    extents = jnp.where(extents > 0, jnp.maximum(extents, chunk), 0)
    q = (0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                 (4, HEADS, chunk, WIDTH),
                                 jnp.float32)).astype(dtype)
    pages = _pages(dtype)
    q_pos = extents[:, None] - chunk + jnp.arange(chunk)[None]
    want = fa._latent_attention_xla(q, pages, TABLE, q_pos, VALUES)
    got = fa.flash_latent_chunk_attention(q, pages, TABLE, extents, VALUES,
                                          interpret=True)
    assert got.shape == (4, HEADS, chunk, VALUES) and got.dtype == dtype
    live = np.asarray(extents) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               **_tol(dtype))
    assert not np.asarray(got, np.float32)[~live].any()
    # the dispatcher finds the extents from the positions and the table
    via = fa.latent_chunk_attention(q, pages, TABLE, q_pos, VALUES,
                                    backend="paged")
    np.testing.assert_array_equal(np.asarray(via, np.float32),
                                  np.asarray(got, np.float32))


def test_the_heads_are_attended_in_blocks_that_fit_the_budget():
    """The cell's shapes: 64 heads x 256 queries of 640 are 21 MB of q
    alone, so a grid step holds a block of heads; every block reads the
    same page (one head, shared)."""
    assert fa._latent_head_block(64, 256, 640, 512, 256, jnp.bfloat16) == 2
    assert fa._latent_head_block(4, 16, 128, 48, 8, jnp.float32) == 4
    shared = fa._paged_kv_index_map(0, 1, 8, 12, shared=True)
    own = fa._paged_kv_index_map(0, 1, 8, 12)
    tbl, lens = np.asarray(TABLE), np.asarray([20, 5, 0, 32])
    assert shared(0, 3, 1, tbl, lens)[1] == 0
    assert own(0, 3, 1, tbl, lens)[1] == 3


def test_an_unknown_backend_is_refused():
    q = jnp.zeros((4, HEADS, WIDTH))
    with pytest.raises(ValueError, match="unknown decode attention backend"):
        fa.latent_decode_attention(q, _pages(jnp.float32), TABLE,
                                   jnp.zeros((4,), jnp.int32), VALUES,
                                   backend="cuda")
