"""Cross-lower every Pallas entry point of ops/flash_attention.py for TPU from
the CPU (`lowering_platforms=("tpu",)`), so a BlockSpec the TPU lowering
refuses fails tier-1 instead of waiting for a chip.  This runs Pallas' own
jaxpr -> Mosaic lowering; Mosaic's compile is chip_smoke.py's job.

Shapes: the ones chip_smoke.py uses (GPT-2 small: 12 heads of 64, bf16,
64-token pages) and a GQA shape (32 query heads over 8 kv heads of 128);
the paged kernels also at the chat cell's own shape (32 slots, 32 windows)
and at a wide MHA shape whose pages crowd the VMEM budget; the paged chunk
kernel at the serving cells' chunk programs and a verify step; the decode
round of every serving cell, and A.X-K1's two latent kernels.  The paged
kernels copy pages by hand, which Mosaic does for whole 128-lane rows only:
heads of 64 are handed over two positions to a row and the int8 arena's
scales a column (Mosaic's own compile of both, for a described v5e, is in
tests/test_kv/test_arena_inplace.py: one file alone may load libtpu).
Also a kernel inside a program emitted for a (2, 2) mesh, whole and with
its rows sharded, and the int8 paged kernel against its XLA reference under
the interpreter, which no other test compares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops.flash_attention import (
    _PAGED_STEP_TOKENS, _PAGED_VMEM_BUDGET, _paged_decode_attention_quant_xla,
    _paged_step_bytes, _paged_step_shape, _vmem_block_bytes,
    flash_attention, flash_decode_attention,
    flash_latent_chunk_attention, flash_latent_decode_attention,
    flash_paged_chunk_attention, flash_paged_decode_attention,
    flash_paged_decode_quant_attention, kv_quantize)

BF16 = jnp.bfloat16
SEQ, PAGE_TOKENS, N_PAGES = 1024, 64, 48
# (batch, heads, kv_heads, head_dim)
SHAPES = [pytest.param(8, 12, 12, 64, id="gpt2-small"),
          pytest.param(4, 32, 8, 128, id="gqa-32-8-128")]
# the paged kernels: (batch, heads, kv_heads, head_dim, max_pages)
PAGED_SHAPES = [
    pytest.param(8, 12, 12, 64, SEQ // PAGE_TOKENS, id="gpt2-small"),
    pytest.param(4, 32, 8, 128, SEQ // PAGE_TOKENS, id="gqa-32-8-128"),
    pytest.param(32, 32, 8, 128, 32, id="chat-cell"),
    pytest.param(8, 32, 32, 128, 24, id="mha-32-128"),
]


def _lower_for_tpu(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# the training kernels also at the train cell's own call on a chip (25 of
# its 100 rows of 1,024 x 64: a row's other side held whole) and at a
# length that streams (8,192 x 128)
TRAIN_SHAPES = [pytest.param(8, 12, 64, SEQ, id="gpt2-small"),
                pytest.param(4, 32, 128, SEQ, id="gqa-32-8-128"),
                pytest.param(1, 25, 64, 1024, id="train-cell"),
                pytest.param(1, 4, 128, 8192, id="streamed")]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("b,h,d,seq", TRAIN_SHAPES)
def test_flash_forward_and_backward_lower(b, h, d, seq, causal):
    qkv = _aval((b, h, seq, d), BF16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal, interpret=False).astype(
            jnp.float32).sum()

    _lower_for_tpu(lambda q, k, v: flash_attention(
        q, k, v, causal, interpret=False), qkv, qkv, qkv)
    text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv,
                          qkv).as_text()
    # three kernels, and lse and delta reach each with a Q block's
    # positions on the lanes: none holds a [rows, t, 1] operand
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3
    assert all(f"tensor<{b * h}x{seq // 256}x1x256xf32>" in ln
               and f"x{seq}x1xf32>" not in ln for ln in calls)


@pytest.mark.parametrize("b,h,kvh,d", SHAPES)
def test_bucketed_decode_lowers(b, h, kvh, d):
    cache = _aval((b, h, SEQ, d), BF16)
    _lower_for_tpu(
        lambda q, k, v, n: flash_decode_attention(q, k, v, n,
                                                  interpret=False),
        _aval((b, h, d), BF16), cache, cache, _aval((b,), jnp.int32))


@pytest.mark.parametrize("b,h,kvh,d,max_pages", PAGED_SHAPES)
def test_paged_decode_lowers(b, h, kvh, d, max_pages):
    pages = _aval((N_PAGES, kvh, PAGE_TOKENS, d), BF16)
    _lower_for_tpu(
        lambda q, k, v, t, n: flash_paged_decode_attention(
            q, k, v, t, n, interpret=False),
        _aval((b, h, d), BF16), pages, pages,
        _aval((b, max_pages), jnp.int32), _aval((b,), jnp.int32))


# every serving cell's decode round: (slots, heads, kv_heads, page_tokens,
# max_pages, arena pages), heads of 128
CELL_DECODE_SHAPES = [
    pytest.param(32, 32, 8, 64, 32, 576, id="mistral-cell"),
    pytest.param(64, 32, 8, 256, 16, 1024, id="granite-cell"),
    pytest.param(64, 64, 8, 256, 32, 2048, id="kexaone-cell"),
    pytest.param(40, 30, 30, 256, 16, 288, id="olmo-cell"),
]


@pytest.mark.parametrize("b,h,kvh,pt,max_pages,n_pages", CELL_DECODE_SHAPES)
def test_paged_decode_lowers_at_the_cells(b, h, kvh, pt, max_pages, n_pages):
    pages = _aval((n_pages, kvh, pt, 128), BF16)
    text = _lower_for_tpu(
        lambda q, k, v, t, n: flash_paged_decode_attention(
            q, k, v, t, n, interpret=False),
        _aval((b, h, 128), BF16), pages, pages,
        _aval((b, max_pages), jnp.int32), _aval((b,), jnp.int32)).as_text()
    # ONE custom call, and each arena leaf handed to it once, whole
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert call.count(f"tensor<{n_pages}x{kvh}x{pt}x128xbf16>") == 2


# A.X-K1's cell: 32 slots and 2 prefill rows, 64 heads, a latent row stored
# 640 wide whose leading 512 columns are its values, 64 pages of 256 a
# bucket, 2,048 arena pages
@pytest.mark.parametrize("chunk", [0, 256], ids=["decode", "chunk"])
def test_latent_kernels_lower_at_the_cell(chunk):
    pages = _aval((2048, 256, 640), BF16)
    rows = 2 if chunk else 32
    q = _aval((rows, 64) + ((chunk,) if chunk else ()) + (640,), BF16)
    call = flash_latent_chunk_attention if chunk \
        else flash_latent_decode_attention
    _lower_for_tpu(
        lambda q, p, t, n: call(q, p, t, n, 512, interpret=False),
        q, pages, _aval((rows, 64), jnp.int32), _aval((rows,), jnp.int32))


# the chunk kernel: (rows, heads, kv_heads, chunk, page_tokens, max_pages),
# the three serving cells' chunk programs and a verify step's few queries
CHUNK_SHAPES = [
    pytest.param(4, 32, 8, 64, 64, 32, id="mistral-cell"),
    pytest.param(4, 32, 8, 256, 256, 16, id="granite-cell"),
    pytest.param(2, 64, 8, 256, 256, 32, id="kexaone-cell"),
    pytest.param(32, 32, 8, 5, 64, 32, id="verify-k4"),
    pytest.param(8, 12, 12, 64, 64, 16, id="gpt2-small"),
]
# Olmo Hybrid's chunk program: ONE row, a query row a KV head (MHA)
OLMO_CHUNK = pytest.param(1, 30, 30, 256, 256, 16, id="olmo-cell")


@pytest.mark.parametrize("rows,h,kvh,chunk,pt,max_pages",
                         CHUNK_SHAPES + [OLMO_CHUNK])
def test_paged_chunk_lowers(rows, h, kvh, chunk, pt, max_pages):
    d = 64 if h == 12 else 128
    pages = _aval((N_PAGES, kvh, pt, d), BF16)
    _lower_for_tpu(
        lambda q, k, v, t, n: flash_paged_chunk_attention(
            q, k, v, t, n, interpret=False),
        _aval((rows, h, chunk, d), BF16), pages, pages,
        _aval((rows, max_pages), jnp.int32), _aval((rows,), jnp.int32))


# the LFM2 cell (256 slots, 4 prefill rows, 32 query heads on 8 KV heads of
# SIXTY-FOUR, 1,536 pages of 256): the leaf lane-dense as the arena stores
# it, [pages, 8, 128, 128], and plain, [pages, 8, 256, 64]
@pytest.mark.parametrize("leaf", ["lane_dense", "as_is"])
@pytest.mark.parametrize("chunk", [0, 256], ids=["decode", "chunk"])
def test_paged_kernels_lower_at_the_lfm2_cell(chunk, leaf):
    """Both forms lower to the SAME kernel — the lane-dense leaf is handed
    to the custom call as it is stored, the plain one through a reshape to
    that very shape (which on the chip is a copy of the leaf: the compile
    for a v5e in tests/test_kv/test_arena_inplace.py counts them)."""
    n_pages, rows = 1536, 4 if chunk else 256
    stored = (n_pages, 8, 128, 128) if leaf == "lane_dense" \
        else (n_pages, 8, 256, 64)
    pages = _aval(stored, BF16)
    q = _aval((rows, 32) + ((chunk,) if chunk else ()) + (64,), BF16)
    call = flash_paged_chunk_attention if chunk \
        else flash_paged_decode_attention
    text = _lower_for_tpu(
        lambda q, k, v, t, n: call(q, k, v, t, n, interpret=False),
        q, pages, pages, _aval((rows, 16), jnp.int32),
        _aval((rows,), jnp.int32)).as_text()
    (line,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert line.count(f"tensor<{n_pages}x8x128x128xbf16>") == 2
    reshapes = [ln for ln in text.splitlines() if "reshape" in ln
                and f"{n_pages}x8x256x64xbf16" in ln]
    assert len(reshapes) == (0 if leaf == "lane_dense" else 2)


@pytest.mark.parametrize("n_blocks", [1, 2])
@pytest.mark.parametrize("b,h,kvh,d,max_pages", PAGED_SHAPES)
def test_int8_paged_decode_lowers(b, h, kvh, d, max_pages, n_blocks):
    pages = _aval((N_PAGES, kvh, PAGE_TOKENS, d), jnp.int8)
    scales = _aval((N_PAGES, kvh, PAGE_TOKENS, n_blocks), jnp.float32)
    _lower_for_tpu(
        lambda q, k, v, ks, vs, t, n: flash_paged_decode_quant_attention(
            q, k, v, ks, vs, t, n, interpret=False),
        _aval((b, h, d), BF16), pages, pages, scales, scales,
        _aval((b, max_pages), jnp.int32), _aval((b,), jnp.int32))


def _arena_avals(kvh, d, quant_blocks=0):
    if not quant_blocks:
        return (_aval((N_PAGES, kvh, PAGE_TOKENS, d), BF16),) * 2
    return ((_aval((N_PAGES, kvh, PAGE_TOKENS, d), jnp.int8),) * 2
            + (_aval((N_PAGES, kvh, PAGE_TOKENS, quant_blocks),
                     jnp.float32),) * 2)


@pytest.mark.parametrize("quant_blocks", [0, 1, 2])
@pytest.mark.parametrize("b,h,kvh,d,max_pages", PAGED_SHAPES)
def test_paged_step_shape_rule(b, h, kvh, d, max_pages, quant_blocks):
    """The block of the paged kernels' grid: divisors of kv_heads and of
    max_pages, at most `_PAGED_STEP_TOKENS` tokens, under the VMEM budget,
    the largest such, and a function of the shapes alone."""
    pages = _arena_avals(kvh, d, quant_blocks)
    g, n = _paged_step_shape(max_pages, pages)
    assert (g, n) == _paged_step_shape(max_pages,
                                       _arena_avals(kvh, d, quant_blocks))
    assert kvh % g == 0 and max_pages % n == 0
    assert n * PAGE_TOKENS <= _PAGED_STEP_TOKENS
    assert _paged_step_bytes(pages, g, n) <= _PAGED_VMEM_BUDGET
    # whole pages, unless one page of all heads is over the budget
    assert g == kvh or _paged_step_bytes(pages, 2 * g, 1) > _PAGED_VMEM_BUDGET
    # the next divisor up would break one of the two limits
    bigger = [m for m in range(n + 1, max_pages + 1) if max_pages % m == 0]
    assert not bigger or bigger[0] * PAGE_TOKENS > _PAGED_STEP_TOKENS \
        or _paged_step_bytes(pages, g, bigger[0]) > _PAGED_VMEM_BUDGET


def test_paged_step_shape_by_hand():
    cell = _arena_avals(8, 128)
    # a bf16 page of 8 heads x 64 tokens x 128 is 128 KiB; K and V, double-
    # buffered: 512 KiB a page of the block, beside 4 x 256 KiB of f32 work
    assert _vmem_block_bytes(cell[0].shape[1:], BF16) == 128 * 1024
    assert _paged_step_bytes(cell, 8, 4) == 3 * 2 ** 20
    assert _paged_step_shape(32, cell) == (8, 4)            # 256 tokens
    assert _paged_step_shape(32, cell, want=8) == (8, 8)    # a sweep's ask
    assert _paged_step_shape(6, cell, want=4) == (8, 3)     # 4 divides no 6
    assert _paged_step_shape(7, cell) == (8, 1)             # a prime
    # minor dims pad to the (sublane, 128-lane) tile: an f32 scale page
    # [32, 64, 1] takes as much VMEM as [32, 64, 128]
    assert _vmem_block_bytes((32, 64, 1), jnp.float32) == 32 * 64 * 128 * 4
    assert _vmem_block_bytes((12, 64, 64), BF16) == 12 * 64 * 128 * 2
    # a page too large for the budget is split over groups of KV heads
    assert _paged_step_shape(24, _arena_avals(32, 128, 1)) == (16, 2)
    wide = (_aval((N_PAGES, 64, PAGE_TOKENS, 256), jnp.float32),) * 2
    assert _paged_step_shape(8, wide) == (16, 1)


@pytest.mark.parametrize("rows,h,kvh,chunk,pt,max_pages", CHUNK_SHAPES)
def test_paged_step_shape_counts_a_chunks_query_rows(rows, h, kvh, chunk, pt,
                                                     max_pages):
    """A chunk's group x chunk query rows (their blocks, accumulator,
    statistics and a page's scores) count against the same budget, so the
    KV heads a step holds shrink as the rows grow: the three cells' chunk
    programs by hand, and never a wider step than the decode kernel's."""
    pages = (_aval((N_PAGES, kvh, pt, 64 if h == 12 else 128), BF16),) * 2
    q_rows = (h // kvh) * -(-chunk // 16) * 16
    g, n = _paged_step_shape(max_pages, pages, rows=q_rows)
    by_hand = {(64, 64): (4, 4), (256, 256): (1, 1), (5, 64): (8, 4)}
    if h != 12:
        assert (g, n) == by_hand[chunk, pt]
    assert kvh % g == 0 and max_pages % n == 0
    assert n <= _paged_step_shape(max_pages, pages)[1]
    assert g == 1 or _paged_step_bytes(pages, g, n, q_rows) \
        <= _PAGED_VMEM_BUDGET
    assert _paged_step_bytes(pages, g, n, q_rows) \
        > _paged_step_bytes(pages, g, n)


@pytest.mark.parametrize("n_blocks", [1, 2])
@pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2)])
def test_int8_paged_kernel_matches_xla_reference(h, kvh, n_blocks):
    pt, max_pages, n_pages, d = 8, 4, 16, 16
    lengths = [32, 17, 1]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (len(lengths), h, d), jnp.float32)
    kq, ks = kv_quantize(
        jax.random.normal(keys[1], (n_pages, kvh, pt, d)), n_blocks)
    vq, vs = kv_quantize(
        jax.random.normal(keys[2], (n_pages, kvh, pt, d)), n_blocks)
    table = np.full((len(lengths), max_pages), n_pages, np.int32)
    for row, n in enumerate(lengths):
        live = -(-n // pt)
        table[row, :live] = np.arange(live) * len(lengths) + row
    table = jnp.asarray(table)
    lens = jnp.asarray(lengths, jnp.int32)
    scale = 1.0 / np.sqrt(d)

    got = flash_paged_decode_quant_attention(q, kq, vq, ks, vs, table, lens,
                                             interpret=True)
    want = _paged_decode_attention_quant_xla(q, kq, vq, ks, vs, table, lens,
                                             scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_inside_a_sharded_program_lowers(monkeypatch, cpu_devices):
    """On a multi-device mesh the emitted program must carry its Pallas
    kernels under a shard_map: inside a jit that GSPMD partitions, the TPU
    lowering refuses a Mosaic custom call outright ("Mosaic kernels cannot
    be automatically partitioned") — which is how the flash-attention train
    step first failed on the four-chip host."""
    import importlib

    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.jaxfront.api import compile_step
    from easydist_tpu.models import GPTConfig, make_gpt_train_step

    # trace the kernels as a TPU would: compiled, not interpreted
    monkeypatch.setattr(
        importlib.import_module("easydist_tpu.ops.flash_attention"),
        "_default_interpret", lambda: False)
    mesh = make_device_mesh((2, 2), ("dp", "tp"), devices=cpu_devices[:4])
    cfg = GPTConfig(vocab=512, seq=128, dim=128, heads=2, layers=1,
                    dtype="bfloat16", attention="flash")
    step, init_state = make_gpt_train_step(cfg)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = _aval((4, cfg.seq), jnp.int32)
    result = compile_step(step, (state, tokens, tokens), {}, mesh=mesh)
    assert "pallas_call" in str(result.closed_jaxpr)
    result.jitted.trace(*result.in_avals).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("batch,local_rows,heads,seq", [
    pytest.param(4, 5, 5, 128, id="rows-over-both-axes"),
    pytest.param(2, 5, 5, 128, id="rows-over-dp-only"),
    pytest.param(1, 5, 5, 128, id="rows-whole"),
    pytest.param(4, 25, 25, 1024, id="train-cell")])
def test_row_sharded_kernels_lower(monkeypatch, cpu_devices, batch,
                                   local_rows, heads, seq):
    """The flash kernels re-bound at their shard's row count (5 heads x
    `batch` rows over a (2, 2) mesh: 20 / 4, 10 / 2, and 5 left whole,
    which neither axis divides; and the train cell's own 100 rows of 1,024
    x 64, 25 a chip) pass Pallas' TPU lowering, and the lowered module
    holds them at that extent."""
    import importlib
    import re

    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.jaxfront.api import compile_step
    from easydist_tpu.models import GPTConfig, make_gpt_train_step

    monkeypatch.setattr(
        importlib.import_module("easydist_tpu.ops.flash_attention"),
        "_default_interpret", lambda: False)
    mesh = make_device_mesh((2, 2), ("dp", "tp"), devices=cpu_devices[:4])
    cfg = GPTConfig(vocab=512, seq=seq, dim=64 * heads, heads=heads,
                    layers=1, dtype="bfloat16", attention="flash")
    step, init_state = make_gpt_train_step(cfg)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = _aval((batch, cfg.seq), jnp.int32)
    result = compile_step(step, (state, tokens, tokens), {}, mesh=mesh)
    text = result.jitted.trace(*result.in_avals).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3
    block = min(seq, 256)   # q, k, v, dO, and lse and delta a Q block a row
    for line in calls:
        shapes = re.findall(
            rf"tensor<(\d+)x(?:{seq}x64|{seq // block}x1x{block})x", line)
        assert shapes and set(shapes) == {str(local_rows)}, line
