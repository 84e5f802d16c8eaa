"""The latent arena (`kv/arena.py::init_latent_arena`): one leaf a layer,
[n_pages, page_tokens, width], no heads axis — written by the three writes
every paged step uses (a row, `s` rows, a whole page), through sentinels
that drop, and moved a page at a time in the wire format `{"latent":
[layers, page_tokens, width]}`."""

import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.kv.arena import (export_page, import_page,
                                   init_latent_arena, write_chunk, write_row,
                                   write_rows)

LAYERS, N_PAGES, PT, WIDTH = 2, 6, 4, 8


def _arena():
    return init_latent_arena(LAYERS, N_PAGES, PT, WIDTH, jnp.float32)


def test_one_leaf_a_layer_and_no_heads():
    arena = _arena()
    assert list(arena) == ["latent"] and len(arena["latent"]) == LAYERS
    for leaf in arena["latent"]:
        assert leaf.shape == (N_PAGES, PT, WIDTH) and not leaf.any()
    # leaves that shared a buffer could not each be donated
    assert arena["latent"][0] is not arena["latent"][1]
    with pytest.raises(ValueError, match="n_pages"):
        init_latent_arena(1, 0, PT, WIDTH, jnp.float32)
    with pytest.raises(ValueError, match="page_tokens"):
        init_latent_arena(1, 2, 0, WIDTH, jnp.float32)


def test_a_row_lands_at_its_page_and_offset_and_a_sentinel_drops():
    leaf = _arena()["latent"][0]
    new = jnp.arange(3 * WIDTH, dtype=jnp.float32).reshape(3, WIDTH) + 1
    out = np.array(write_row(leaf, new, jnp.asarray([4, N_PAGES, 0]),
                             jnp.asarray([1, 2, 3])))
    np.testing.assert_array_equal(out[4, 1], np.asarray(new[0]))
    np.testing.assert_array_equal(out[0, 3], np.asarray(new[2]))
    out[4, 1] = out[0, 3] = 0
    assert not out.any()          # the sentinel row went nowhere


def test_rows_may_straddle_a_page_boundary():
    leaf = _arena()["latent"][0]
    new = jnp.arange(2 * 3 * WIDTH, dtype=jnp.float32).reshape(2, 3, WIDTH) + 1
    pages = jnp.asarray([[1, 1, 5], [N_PAGES] * 3])
    offsets = jnp.asarray([[2, 3, 0], [0, 1, 2]])
    out = np.asarray(write_rows(leaf, new, pages, offsets))
    np.testing.assert_array_equal(out[1, 2:], np.asarray(new[0, :2]))
    np.testing.assert_array_equal(out[5, 0], np.asarray(new[0, 2]))
    assert np.count_nonzero(out.any(-1)) == 3


def test_a_chunk_fills_one_page():
    leaf = _arena()["latent"][0]
    new = jnp.ones((2, PT, WIDTH))
    out = np.asarray(write_chunk(leaf, new, jnp.asarray([3, N_PAGES])))
    assert out[3].all() and not np.delete(out, 3, axis=0).any()


def test_a_page_round_trips_in_the_wire_format():
    arena = _arena()
    arena = {"latent": tuple(
        write_chunk(leaf, jnp.full((1, PT, WIDTH), li + 1.0),
                    jnp.asarray([2]))
        for li, leaf in enumerate(arena["latent"]))}
    page = export_page(arena, jnp.asarray(2))
    assert page["latent"].shape == (LAYERS, PT, WIDTH)
    np.testing.assert_array_equal(np.asarray(page["latent"][1]), 2.0)
    moved = import_page(_arena(), page, jnp.asarray(5))
    for li, leaf in enumerate(moved["latent"]):
        leaf = np.asarray(leaf)
        assert (leaf[5] == li + 1).all()
        assert not np.delete(leaf, 5, axis=0).any()
