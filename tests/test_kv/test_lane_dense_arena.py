"""The lane-dense leaf of an exact arena with narrow heads (`kv/arena.py`:
heads of 64 two positions to a 128-lane row, of 32 four): every write
(a decode row, a verify step's rows across a page boundary, a prefill
chunk), dead rows included, leaves the bytes a plain `[pages, heads, tokens,
dim]` arena holds after the same writes; a page leaves and enters in the
old wire format; the forms that stay as they were stay so (heads of 128, a
page whose positions do not divide, the int8 arena); and the adapter,
told the model's head, reads a page's positions off either form."""

import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.kv import arena
from easydist_tpu.models import gpt
from easydist_tpu.models.decoder import Paged

N_PAGES, HEADS, PT = 6, 3, 16
F32 = jnp.float32


def _pair(hd, pt=PT, dtype=F32):
    dense = arena.init_page_arena(1, N_PAGES, HEADS, pt, hd, dtype)["k"][0]
    return dense, jnp.zeros((N_PAGES, HEADS, pt, hd), dtype)


@pytest.mark.parametrize("hd,parts", [(64, 2), (32, 4), (16, 8)])
def test_narrow_heads_are_stored_whole_lanes(hd, parts):
    dense, plain = _pair(hd)
    assert arena.lane_parts(hd, PT) == parts
    assert dense.shape == (N_PAGES, HEADS, PT // parts, 128)
    assert arena.plain_pages(dense, hd).shape == plain.shape
    assert Paged.page_tokens({"k": (dense,)}, hd) == PT
    assert Paged.page_tokens({"k": (plain,)}, hd) == PT


@pytest.mark.parametrize("hd,pt,quant", [
    (128, 16, None), (256, 16, None), (96, 16, None), (64, 7, None),
    (32, 6, None), (64, 16, "int8")])
def test_every_other_leaf_is_laid_out_as_it_was(hd, pt, quant):
    """Heads of 128 and wider, a width that divides no 128, a page whose
    positions do not divide by the parts, and the int8 arena (payload and
    scale pages): byte for byte the leaves of before."""
    got = arena.init_page_arena(2, N_PAGES, HEADS, pt, hd, F32, quant)
    assert all(leaf.shape == (N_PAGES, HEADS, pt, hd)
               for key in ("k", "v") for leaf in got[key])
    if quant:
        assert got["k"][0].dtype == jnp.int8
        assert got["k_scale"][0].shape == (N_PAGES, HEADS, pt, 1)
    assert arena.plain_pages(got["k"][0], hd) is got["k"][0]


@pytest.mark.parametrize("hd", [64, 32])
def test_rows_row_batches_and_chunks_equal_a_plain_arena(hd):
    rng = np.random.default_rng(hd)
    dense, plain = _pair(hd)

    def same(a, b):
        np.testing.assert_array_equal(arena.plain_pages(a, hd), b)

    # a decode round: four rows, the third one dead (the sentinel page)
    for offsets in ([3, PT - 1, 1, 0], [4, 0, 9, 1]):
        new = jnp.asarray(rng.normal(size=(4, HEADS, hd)), F32)
        page, off = jnp.asarray([0, 2, N_PAGES, 5]), jnp.asarray(offsets)
        dense = arena.write_row(dense, new, page, off)
        plain = arena.write_row(plain, new, page, off)
        same(dense, plain)
    # a verify step: five rows that straddle a page boundary, a dead row
    new = jnp.asarray(rng.normal(size=(2, HEADS, 5, hd)), F32)
    pages = jnp.asarray([[1, 1, 1, 3, 3], [N_PAGES] * 5])
    offs = jnp.asarray([[PT - 3, PT - 2, PT - 1, 0, 1], [0, 1, 2, 3, 4]])
    dense = arena.write_rows(dense, new, pages, offs)
    plain = arena.write_rows(plain, new, pages, offs)
    same(dense, plain)
    # a prefill chunk: one whole page a row, a dead row
    new = jnp.asarray(rng.normal(size=(2, HEADS, PT, hd)), F32)
    dense = arena.write_chunk(dense, new, jnp.asarray([4, N_PAGES]))
    plain = arena.write_chunk(plain, new, jnp.asarray([4, N_PAGES]))
    same(dense, plain)
    assert float(jnp.abs(plain[4]).sum()) > 0


@pytest.mark.parametrize("hd", [64, 32])
def test_a_page_travels_in_the_old_wire_format(hd):
    rng = np.random.default_rng(hd + 1)
    plain = jnp.asarray(rng.normal(size=(N_PAGES, HEADS, PT, hd)), F32)
    parts = arena.lane_parts(hd, PT)
    dense = {"k": (plain.reshape(N_PAGES, HEADS, PT // parts, 128),) * 2}
    page = arena.export_page(dense, 4, hd)
    assert page["k"].shape == (2, HEADS, PT, hd)
    np.testing.assert_array_equal(page["k"][1], plain[4])
    # and what export_page of a plain arena gives, bit for bit
    np.testing.assert_array_equal(
        page["k"], arena.export_page({"k": (plain,) * 2}, 4)["k"])
    empty = {"k": tuple(jnp.zeros_like(leaf) for leaf in dense["k"])}
    back = arena.import_page(empty, page, 2)
    np.testing.assert_array_equal(arena.plain_pages(back["k"][0], hd)[2],
                                  plain[4])
    np.testing.assert_array_equal(
        arena.export_page(back, 2, hd)["k"], page["k"])
    assert float(jnp.abs(back["k"][0][3]).sum()) == 0


def test_a_page_that_does_not_divide_falls_back():
    assert arena.lane_parts(64, 7) == 1 and arena.lane_parts(32, 6) == 1
    dense, plain = _pair(64, pt=7)
    assert dense.shape == plain.shape
    new = jnp.ones((1, HEADS, 64), F32)
    got = arena.write_row(dense, new, jnp.asarray([1]), jnp.asarray([6]))
    assert float(got[1, :, 6].sum()) == HEADS * 64


def test_gpt2s_heads_of_64_get_the_lane_dense_arena():
    """GPT-2's heads are 64 wide: its exact arena is lane-dense wherever a
    page's positions are even, its int8 arena as it was."""
    cfg = gpt.GPTConfig(vocab=64, seq=64, dim=128, heads=2, layers=1,
                        dtype="float32")
    pages = gpt.init_kv_pages(cfg, 4, 16)
    assert pages["k"][0].shape == (4, 2, 8, 128)
    quant = gpt.init_kv_pages(cfg, 4, 16, quant_dtype="int8")
    assert quant["k"][0].shape == (4, 2, 16, 64)
