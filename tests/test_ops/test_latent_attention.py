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

from . import _walk

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


@pytest.mark.parametrize("dead", sorted(_walk.DEAD_ENTRIES))
@pytest.mark.parametrize("case", sorted(_walk.CASES))
@pytest.mark.parametrize("chunk", [0, 8], ids=["decode", "chunk"])
def test_the_walk_reads_live_pages_only(chunk, case, dead):
    """The paged decode kernel's cases (`_walk.CASES`) over latent pages, a
    decode round and a chunk of 8 queries, NaN in every page no live entry
    names: live rows equal the fallback over the clean pages, a row that
    walks nothing gives zeros."""
    table, lengths, live, named = _walk.table_for(case, dead,
                                                  min_length=chunk)
    rs = np.random.RandomState(3)
    pages = rs.standard_normal((_walk.N_PAGES, _walk.PT, WIDTH)) \
        .astype(np.float32)
    q = jnp.asarray(0.3 * rs.standard_normal(
        (len(lengths), HEADS) + ((chunk,) if chunk else ()) + (WIDTH,)),
        jnp.float32)
    table = jnp.asarray(table)
    bad = jnp.asarray(_walk.poisoned(pages, named))
    kw = dict(pages_per_step=_walk.PAGES_PER_STEP, interpret=True)
    if chunk:
        pos = lengths[:, None] - chunk + np.arange(chunk, dtype=np.int32)
        want = fa._latent_attention_xla(q, jnp.asarray(pages), table,
                                        jnp.asarray(pos), VALUES)
        got = fa.flash_latent_chunk_attention(
            q, bad, table, jnp.asarray(lengths), VALUES, **kw)
    else:
        want = fa.latent_decode_attention(
            q, jnp.asarray(pages), table, jnp.asarray(lengths), VALUES,
            backend="xla")
        got = fa.flash_latent_decode_attention(
            q, bad, table, jnp.asarray(lengths), VALUES, **kw)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got[live], want[live], atol=1e-5, rtol=1e-5)
    assert not got[~live].any()


def test_the_heads_are_attended_in_blocks_that_fit_the_budget():
    """The cell's shapes: 64 heads x 256 queries of 640 are 21 MB of q
    alone, so a grid step holds a block of heads; every block reads the
    same page (one head, shared)."""
    assert fa._latent_head_block(64, 256, 640, 512, 256, jnp.bfloat16) == 2
    assert fa._latent_head_block(4, 16, 128, 48, 8, jnp.float32) == 4
    # ... so the slots a block's walk copies into ([2, pages a window, heads,
    # page_tokens, width]) hold ONE head, whatever the blocks of heads,
    # where a GQA kernel's hold a group of KV heads
    def slots(call, *avals):
        return _walk.slot_shapes(jax.make_jaxpr(call)(*avals).jaxpr)

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    ints = (jax.ShapeDtypeStruct((4, 4), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.int32))
    assert slots(lambda q, p, t, n: fa.flash_latent_chunk_attention(
        q, p, t, n, VALUES, interpret=True),
        aval(4, HEADS, 16, WIDTH), aval(N_PAGES, PT, WIDTH), *ints) \
        == [(2, 4, 1, PT, WIDTH)]
    assert slots(lambda q, k, v, t, n: fa.flash_paged_chunk_attention(
        q, k, v, t, n, interpret=True),
        aval(4, 8, 16, WIDTH), aval(N_PAGES, 4, PT, WIDTH),
        aval(N_PAGES, 4, PT, WIDTH), *ints) == [(2, 4, 4, PT, WIDTH)] * 2


def test_an_unknown_backend_is_refused():
    q = jnp.zeros((4, HEADS, WIDTH))
    with pytest.raises(ValueError, match="unknown decode attention backend"):
        fa.latent_decode_attention(q, _pages(jnp.float32), TABLE,
                                   jnp.zeros((4,), jnp.int32), VALUES,
                                   backend="cuda")
