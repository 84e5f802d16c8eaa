"""Paged decode attention: gather_pages reconstruction (incl. GQA),
bitwise parity of the gather-fallback vs the contiguous reference on
live rows, the Pallas page-chasing kernel (interpret mode) vs the
fallback, garbage-page/dead-window masking, and backend dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops.flash_attention import (
    _decode_attention_xla, _paged_decode_attention_quant_xla,
    _paged_decode_attention_xla, decode_attention,
    flash_paged_decode_attention, flash_paged_decode_quant_attention,
    gather_pages, kv_quantize, paged_decode_attention)

from . import _walk

PT = 8          # page_tokens
MP = 4          # max_pages per row -> virtual cache length 32
NP = 16         # arena pages


def _arena(kvh=4, d=16, seed=0, n_pages=NP):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    k = jax.random.normal(ks[0], (n_pages, kvh, PT, d), jnp.float32)
    v = jax.random.normal(ks[1], (n_pages, kvh, PT, d), jnp.float32)
    return k, v


def _paged_setup(lengths, h=4, kvh=4, d=16, seed=0):
    """Rows mapped to disjoint arena pages (row b gets pages b*MP..),
    plus the contiguous twin cache the gather must reproduce."""
    b = len(lengths)
    kp, vp = _arena(kvh=kvh, d=d, seed=seed)
    table = np.full((b, MP), NP, np.int32)
    for bi in range(b):
        n_live = -(-lengths[bi] // PT)
        for j in range(n_live):
            table[bi, j] = bi * MP + j
    q = jax.random.normal(jax.random.PRNGKey(seed + 7), (b, h, d),
                          jnp.float32)
    # contiguous twin: gather each row's mapped pages back-to-back,
    # clipped-sentinel windows land on the row's LAST live page
    kc = np.zeros((b, kvh, MP * PT, d), np.float32)
    vc = np.zeros((b, kvh, MP * PT, d), np.float32)
    for bi in range(b):
        for j in range(MP):
            pid = min(table[bi, j], NP - 1) if table[bi, j] == NP else \
                table[bi, j]
            if table[bi, j] == NP:      # sentinel clips to NP-1
                pid = NP - 1
            kc[bi, :, j * PT:(j + 1) * PT] = np.asarray(kp[pid])
            vc[bi, :, j * PT:(j + 1) * PT] = np.asarray(vp[pid])
    return q, kp, vp, jnp.asarray(table), kc, vc


class TestGatherPages:
    def test_reconstructs_contiguous_cache(self):
        q, kp, vp, table, kc, _ = _paged_setup([32, 17])
        got = gather_pages(kp, table)
        np.testing.assert_array_equal(np.asarray(got), kc)

    def test_gqa_repeats_after_gather(self):
        _, kp, _, table, kc, _ = _paged_setup([32, 17], kvh=2, h=4)
        got = gather_pages(kp, table, n_heads=4)
        assert got.shape == (2, 4, MP * PT, 16)
        # repeat-then-attend order: heads 0,1 mirror kv head 0
        np.testing.assert_array_equal(np.asarray(got[:, 0]),
                                      np.asarray(got[:, 1]))
        np.testing.assert_array_equal(np.asarray(got[:, 0]), kc[:, 0])

    def test_sentinel_clips_to_last_page(self):
        _, kp, _, table, _, _ = _paged_setup([8])   # 1 live page, 3 dead
        got = gather_pages(kp, table)
        # dead windows hold the CLIPPED page (NP-1) — finite garbage
        np.testing.assert_array_equal(np.asarray(got[0, :, PT:2 * PT]),
                                      np.asarray(kp[NP - 1]))


class TestXlaFallbackParity:
    @pytest.mark.parametrize("lengths", [[32, 17], [1, 8], [9, 25],
                                         [32, 32]])
    def test_bitwise_vs_contiguous_reference(self, lengths):
        # same virtual length, same einsum shapes -> bitwise equality,
        # the parity spine the paged serving path stands on
        q, kp, vp, table, kc, vc = _paged_setup(lengths)
        L = jnp.asarray(lengths, jnp.int32)
        scale = 1.0 / np.sqrt(q.shape[-1])
        paged = _paged_decode_attention_xla(q, kp, vp, table, L, scale)
        ref = _decode_attention_xla(q, jnp.asarray(kc), jnp.asarray(vc),
                                    L, scale)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(ref))

    def test_garbage_pages_unobservable(self):
        # poison every UNMAPPED arena page; masked rows contribute
        # exactly zero softmax weight so outputs cannot move
        q, kp, vp, table, _, _ = _paged_setup([17, 9])
        L = jnp.asarray([17, 9], jnp.int32)
        base = _paged_decode_attention_xla(q, kp, vp, table, L, 0.25)
        mapped = {int(p) for p in np.asarray(table).ravel() if p < NP}
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        for pid in range(NP):
            if pid not in mapped:
                kp2[pid] = 1e4
                vp2[pid] = -1e4
        noisy = _paged_decode_attention_xla(q, jnp.asarray(kp2),
                                            jnp.asarray(vp2), table, L,
                                            0.25)
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(noisy))

    def test_gqa_matches_contiguous_gqa(self):
        q, kp, vp, table, kc, vc = _paged_setup([25, 32], kvh=2, h=4)
        L = jnp.asarray([25, 32], jnp.int32)
        paged = _paged_decode_attention_xla(q, kp, vp, table, L, 0.25)
        kf = jnp.repeat(jnp.asarray(kc), 2, axis=1)
        vf = jnp.repeat(jnp.asarray(vc), 2, axis=1)
        ref = _decode_attention_xla(q, kf, vf, L, 0.25)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(ref))


class TestFlashPagedKernelInterpret:
    @pytest.mark.parametrize("lengths", [[32, 17], [1, 8], [9, 25]])
    def test_matches_fallback(self, lengths):
        q, kp, vp, table, _, _ = _paged_setup(lengths)
        L = jnp.asarray(lengths, jnp.int32)
        scale = 1.0 / np.sqrt(q.shape[-1])
        ref = _paged_decode_attention_xla(q, kp, vp, table, L, scale)
        out = flash_paged_decode_attention(q, kp, vp, table, L,
                                           scale=scale, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_gqa_matches_fallback(self):
        q, kp, vp, table, _, _ = _paged_setup([25, 10], kvh=2, h=4)
        L = jnp.asarray([25, 10], jnp.int32)
        ref = _paged_decode_attention_xla(q, kp, vp, table, L, 0.25)
        out = flash_paged_decode_attention(q, kp, vp, table, L,
                                           scale=0.25, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    # (head_dim, positions to a 128-lane row, the page's form): the kernel
    # copies whole 128-lane rows, so narrow pages are packed or padded
    @pytest.mark.parametrize("d,parts,form", [
        (16, 8, (PT // 8, 128)), (64, 2, (PT // 2, 128)),
        (24, 1, (PT, 128)), (128, 1, (PT, 128)), (256, 1, (PT, 256))])
    @pytest.mark.parametrize("kind", ["exact", "int8"])
    def test_every_width_reaches_the_kernel_in_whole_lanes(self, kind, d,
                                                           parts, form):
        import importlib
        fa = importlib.import_module("easydist_tpu.ops.flash_attention")
        lengths = [25, 10, 32, 1]
        q, kp, vp, table, _, _ = _paged_setup(lengths, kvh=2, h=4, d=d)
        L = jnp.asarray(lengths, jnp.int32)
        assert fa._row_parts((kp, vp)) == parts
        assert fa._whole_lanes(kp, parts).shape == kp.shape[:2] + form
        if kind == "exact":
            ref = _paged_decode_attention_xla(q, kp, vp, table, L, 0.25)
            out = flash_paged_decode_attention(q, kp, vp, table, L,
                                               scale=0.25, interpret=True)
        else:
            (kq, ks), (vq, vs) = kv_quantize(kp, 2), kv_quantize(vp, 2)
            ref = _paged_decode_attention_quant_xla(q, kq, vq, ks, vs,
                                                    table, L, 0.25)
            out = flash_paged_decode_quant_attention(
                q, kq, vq, ks, vs, table, L, scale=0.25, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_heads_not_multiple_of_kv_heads_raises(self):
        q, kp, vp, table, _, _ = _paged_setup([8], kvh=4, h=4)
        with pytest.raises(ValueError, match="kv_heads"):
            flash_paged_decode_attention(q[:, :3], kp, vp, table,
                                         jnp.asarray([8], jnp.int32),
                                         interpret=True)


# ---- the blocked kernel: every head of a row and several pages a grid step

BMP = 6         # max_pages: 2 and 3 divide it, 4 does not
BNP = 28        # arena pages: at most 24 live below, the rest unmapped
GARBAGE = 1e4   # large and finite, like stale KV


def _boundary_lengths(pages_per_step):
    """1, one short of / exactly at / one past a page and a block boundary,
    and a full bucket."""
    block = (pages_per_step or BMP) * PT
    return sorted({1, PT - 1, PT, PT + 1, block - 1, block,
                   min(block + 1, BMP * PT), BMP * PT})


def _blocked_setup(lengths, rep, kvh=2, d=16, seed=0, garbage=False):
    """Rows scattered over the arena through a permuted table.  With
    `garbage`, every row the mask must hide holds GARBAGE: unmapped arena
    pages, the tail of a row's last live page, and whole dead windows that
    still map a (stale) page instead of the sentinel."""
    b = len(lengths)
    rs = np.random.RandomState(seed)
    kp, vp = (rs.standard_normal((BNP, kvh, PT, d)).astype(np.float32)
              for _ in range(2))
    q = jnp.asarray(rs.standard_normal((b, kvh * rep, d)), jnp.float32)
    perm = [int(p) for p in rs.permutation(BNP)]
    table = np.full((b, BMP), BNP, np.int32)
    for row, n in enumerate(lengths):
        live = -(-n // PT)
        table[row, :live] = [perm.pop() for _ in range(live)]
        if garbage:
            last = table[row, live - 1]
            kp[last, :, n - (live - 1) * PT:] = GARBAGE
            vp[last, :, n - (live - 1) * PT:] = -GARBAGE
    if garbage:
        for pid in perm:                     # never mapped live
            kp[pid], vp[pid] = GARBAGE, -GARBAGE
        for row, n in enumerate(lengths):    # stale mappings, dead windows
            table[row, -(-n // PT):] = perm[row % len(perm)]
    return (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


class TestBlockedKernelInterpret:
    @pytest.mark.parametrize("pages_per_step", [2, 4, None],
                             ids=["divides", "does-not-divide", "rule"])
    @pytest.mark.parametrize("rep", [1, 4, 8])
    def test_matches_fallback(self, rep, pages_per_step):
        q, kp, vp, table, L = _blocked_setup(
            _boundary_lengths(pages_per_step), rep, garbage=True)
        ref = _paged_decode_attention_xla(q, kp, vp, table, L, 0.25)
        out = flash_paged_decode_attention(
            q, kp, vp, table, L, scale=0.25, pages_per_step=pages_per_step,
            interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("n_blocks", [1, 2])
    @pytest.mark.parametrize("pages_per_step", [2, 4],
                             ids=["divides", "does-not-divide"])
    @pytest.mark.parametrize("rep", [1, 4])
    def test_int8_matches_fallback(self, rep, pages_per_step, n_blocks):
        q, kp, vp, table, L = _blocked_setup(
            _boundary_lengths(pages_per_step), rep, garbage=True)
        kq, ks = kv_quantize(kp, n_blocks)
        vq, vs = kv_quantize(vp, n_blocks)
        ref = _paged_decode_attention_quant_xla(q, kq, vq, ks, vs, table, L,
                                                0.25)
        out = flash_paged_decode_quant_attention(
            q, kq, vq, ks, vs, table, L, scale=0.25,
            pages_per_step=pages_per_step, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("n_blocks", [0, 1, 2],
                             ids=["exact", "int8-nb1", "int8-nb2"])
    def test_garbage_pages_unobservable(self, n_blocks):
        # the kernel twin of TestXlaFallbackParity's: what sits in dead
        # windows, unmapped pages and a last page's tail cannot move a bit
        lengths = _boundary_lengths(2)
        outs = []
        for garbage in (False, True):
            q, kp, vp, table, L = _blocked_setup(lengths, 4, garbage=garbage)
            if n_blocks:
                kq, ks = kv_quantize(kp, n_blocks)
                vq, vs = kv_quantize(vp, n_blocks)
                outs.append(flash_paged_decode_quant_attention(
                    q, kq, vq, ks, vs, table, L, scale=0.25,
                    pages_per_step=2, interpret=True))
            else:
                outs.append(flash_paged_decode_attention(
                    q, kp, vp, table, L, scale=0.25, pages_per_step=2,
                    interpret=True))
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))

    def test_kv_heads_split_when_a_page_is_over_the_vmem_budget(
            self, monkeypatch):
        import importlib
        fa = importlib.import_module("easydist_tpu.ops.flash_attention")
        q, kp, vp, table, L = _blocked_setup(_boundary_lengths(2), 4,
                                             kvh=4, garbage=True)
        # room for two of the four KV heads of a page, one page a step
        monkeypatch.setattr(fa, "_PAGED_VMEM_BUDGET", 2 * (2 * 2 + 4) * 4096)
        assert fa._paged_step_shape(BMP, (kp, vp)) == (2, 1)
        ref = _paged_decode_attention_xla(q, kp, vp, table, L, 0.25)
        out = flash_paged_decode_attention(q, kp, vp, table, L, scale=0.25,
                                           interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_bf16_arena_matches_fallback(self):
        # the arena's own dtype goes to the first product: bf16 x bf16 is
        # exact in the f32 accumulator, so only the summation order differs
        q, kp, vp, table, L = _blocked_setup(_boundary_lengths(2), 4)
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        ref = _paged_decode_attention_xla(q, kp, vp, table, L, 0.25)
        out = flash_paged_decode_attention(q, kp, vp, table, L, scale=0.25,
                                           pages_per_step=2, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-2)


# ---- the walk: the kernel's own loop over a row's live windows


@pytest.mark.parametrize("dead", sorted(_walk.DEAD_ENTRIES))
@pytest.mark.parametrize("case", sorted(_walk.CASES))
@pytest.mark.parametrize("kind", ["exact", "int8"])
def test_the_walk_reads_live_pages_only(kind, case, dead):
    """Rows of length 0 and rows whose table names no page among live ones,
    every row dead, lengths on a window's boundary and one past it, a row
    full to the bucket; the dead entries the sentinel or any index past the
    arena, and NaN in every page no live entry names.  Live rows equal the
    fallback over the clean arena; a row that walks nothing gives zeros."""
    table, lengths, live, named = _walk.table_for(case, dead)
    rs = np.random.RandomState(1)
    kvh, rep, d = 2, 4, 16
    kp, vp = (rs.standard_normal((_walk.N_PAGES, kvh, _walk.PT, d))
              .astype(np.float32) for _ in range(2))
    q = jnp.asarray(rs.standard_normal((len(lengths), kvh * rep, d)),
                    jnp.float32)
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    kw = dict(scale=0.25, pages_per_step=_walk.PAGES_PER_STEP,
              interpret=True)
    if kind == "exact":
        want = _paged_decode_attention_xla(
            q, jnp.asarray(kp), jnp.asarray(vp), table, lengths, 0.25)
        got = flash_paged_decode_attention(
            q, jnp.asarray(_walk.poisoned(kp, named)),
            jnp.asarray(_walk.poisoned(vp, named)), table, lengths, **kw)
    else:
        (kq, ks), (vq, vs) = (kv_quantize(jnp.asarray(x), 2)
                              for x in (kp, vp))
        want = _paged_decode_attention_quant_xla(q, kq, vq, ks, vs, table,
                                                 lengths, 0.25)
        got = flash_paged_decode_quant_attention(
            q, *(jnp.asarray(_walk.poisoned(np.asarray(x), named, bad))
                 for x, bad in ((kq, 127), (vq, -127), (ks, np.nan),
                                (vs, np.nan))), table, lengths, **kw)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    assert not got[~live].any()


class TestDispatch:
    def test_auto_resolves_to_xla_off_tpu(self):
        q, kp, vp, table, _, _ = _paged_setup([17, 9])
        L = jnp.asarray([17, 9], jnp.int32)
        out = paged_decode_attention(q, kp, vp, table, L, backend="auto")
        ref = paged_decode_attention(q, kp, vp, table, L, backend="xla")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_scalar_length_broadcasts(self):
        q, kp, vp, table, _, _ = _paged_setup([9, 9])
        out = paged_decode_attention(q, kp, vp, table, 9, backend="xla")
        ref = paged_decode_attention(q, kp, vp, table,
                                     jnp.asarray([9, 9], jnp.int32),
                                     backend="xla")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_unknown_backend_raises(self):
        q, kp, vp, table, _, _ = _paged_setup([8])
        with pytest.raises(ValueError, match="paged decode attention"):
            paged_decode_attention(q, kp, vp, table, 8,
                                   backend="tensorrt")

    def test_contiguous_dispatcher_degrades_paged_to_auto(self):
        # EASYDIST_DECODE_ATTENTION=paged on a contiguous call site:
        # there is no table to chase, so it must fall through to auto
        b, h, T, d = 2, 4, 32, 16
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, h, T, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, h, T, d), jnp.float32)
        L = jnp.asarray([5, 30], jnp.int32)
        out = decode_attention(q, k, v, L, backend="paged")
        ref = decode_attention(q, k, v, L, backend="auto")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_jittable(self):
        q, kp, vp, table, _, _ = _paged_setup([17, 25])
        L = jnp.asarray([17, 25], jnp.int32)
        f = jax.jit(lambda *a: paged_decode_attention(*a, backend="xla"))
        out = f(q, kp, vp, table, L)
        ref = paged_decode_attention(q, kp, vp, table, L, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)


# ---- the lane-dense leaf (kv/arena.py): narrow heads `parts` positions to a
# 128-lane row.  The kernel takes it as it lies, the gather path through a
# reshape; both must be the plain leaf's attention.


def _plain_attention(q, kp, vp, table, lengths, scale):
    """Softmax attention a row at a time over the row's own pages, in
    numpy: no gather helper, no kernel."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    b, h, d = q.shape
    rep = h // kp.shape[1]
    out = np.zeros((b, h, d))
    for row in range(b):
        n = int(lengths[row])
        pages = np.asarray(table)[row, :-(-n // kp.shape[2])]
        for head in range(h):
            k = kp[pages, head // rep].reshape(-1, d)[:n]
            v = vp[pages, head // rep].reshape(-1, d)[:n]
            s = k @ q[row, head] * scale
            p = np.exp(s - s.max())
            out[row, head] = (p / p.sum()) @ v
    return out


@pytest.mark.parametrize("d,rep", [(64, 4), (32, 1), (16, 8)],
                         ids=["heads-of-64", "heads-of-32", "heads-of-16"])
def test_lane_dense_leaves_attend_as_plain_ones(d, rep):
    from easydist_tpu.kv.arena import lane_parts
    from easydist_tpu.ops import paged_decode_attention
    from easydist_tpu.runtime import spans

    q, kp, vp, table, lengths = _blocked_setup(_boundary_lengths(2), rep,
                                               d=d)
    parts = lane_parts(d, PT)
    assert parts == min(128 // d, PT)
    dense = [a.reshape(BNP, 2, PT // parts, parts * d) for a in (kp, vp)]
    want = _plain_attention(q, kp, vp, table, lengths, 0.25)
    spans.clear()
    kernel = flash_paged_decode_attention(q, *dense, table, lengths,
                                          scale=0.25, interpret=True)
    counted = [k for k in spans.snapshot()["counters"]
               if k.startswith("paged_attn_calls")]
    assert counted == [f"paged_attn_calls{{head_dim={d},kernel=decode,"
                       f"leaf=lane_dense,row_parts={parts}}}"]
    np.testing.assert_allclose(np.asarray(kernel), want, atol=1e-5)
    # the very kernel call a plain leaf makes (after `_whole_lanes`)
    np.testing.assert_array_equal(
        np.asarray(kernel), np.asarray(flash_paged_decode_attention(
            q, kp, vp, table, lengths, scale=0.25, interpret=True)))
    xla = paged_decode_attention(q, *dense, table, lengths, scale=0.25,
                                 backend="xla")
    np.testing.assert_array_equal(
        np.asarray(xla), np.asarray(_paged_decode_attention_xla(
            q, kp, vp, table, lengths, 0.25)))
    np.testing.assert_allclose(np.asarray(xla), want, atol=1e-5)
