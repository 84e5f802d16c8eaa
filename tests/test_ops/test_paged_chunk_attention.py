"""`flash_paged_chunk_attention` under the Pallas interpreter against the path
it replaces on a TPU, `_chunk_attention_xla` over `gather_pages`: a chunk of
queries reads its row's pages through the table and stops at the row's
extent.  Then what a model's layers cost the host (one kernel trace a
program, whatever the depth), and three tiny sessions whose greedy tokens
must not know which backend served their prefill."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu import config as edconfig
from easydist_tpu.ops import (flash_paged_chunk_attention, gather_pages,
                              paged_chunk_attention)

from . import _walk

fa = importlib.import_module("easydist_tpu.ops.flash_attention")

HEAD_DIM, KV_HEADS = 32, 2


def _arena(rng, n_pages, page_tokens, dtype):
    return tuple(jnp.asarray(rng.standard_normal(
        (n_pages, KV_HEADS, page_tokens, HEAD_DIM)), dtype) for _ in "kv")


def _case(group, chunk, page_tokens, starts, *, max_pages=6, dtype="bfloat16",
          unmapped=(), seed=0):
    """Row r's chunk starts at `starts[r]` (None: a row that holds no
    sequence: an all-sentinel table row at start 0, as the session builds
    it).  Every window a live row's extent touches is mapped to a page of
    its own, the others hold the sentinel; `unmapped` lists (row, window)
    pairs that hold it INSIDE the extent too."""
    rng = np.random.default_rng(seed)
    rows, n_pages = len(starts), len(starts) * max_pages + 3
    k, v = _arena(rng, n_pages, page_tokens, dtype)
    q = jnp.asarray(rng.standard_normal(
        (rows, group * KV_HEADS, chunk, HEAD_DIM)), dtype)
    table = np.full((rows, max_pages), n_pages, np.int32)
    perm = rng.permutation(n_pages)
    for r, start in enumerate(starts):
        if start is not None:
            used = -(-(start + chunk) // page_tokens)
            table[r, :used] = perm[r * max_pages:r * max_pages + used]
    for r, w in unmapped:
        table[r, w] = n_pages
    pos = np.asarray([[0 if s is None else s] for s in starts], np.int32) \
        + np.arange(chunk, dtype=np.int32)[None, :]
    live = np.asarray([s is not None for s in starts])
    return q, k, v, jnp.asarray(table), jnp.asarray(pos), live


CASES = {
    # chunk == page, as chunked prefill runs it: first chunk, a middle one,
    # the chunk that fills the bucket, and a row that holds no sequence
    "prefill-group1": dict(group=1, chunk=16, page_tokens=16,
                           starts=[0, 32, 80, None]),
    "prefill-group4": dict(group=4, chunk=16, page_tokens=16,
                           starts=[0, 32, 80, None]),
    "prefill-group8": dict(group=8, chunk=16, page_tokens=16,
                           starts=[80, None, 0, 48]),
    "prefill-float32": dict(group=4, chunk=16, page_tokens=16,
                            starts=[0, 64], dtype="float32"),
    # several pages a grid step (the Mistral cell's pages of 64 under
    # `_PAGED_STEP_TOKENS`): extents on both sides of a step's boundary
    "prefill-pages-of-8": dict(group=4, chunk=8, page_tokens=8, max_pages=12,
                               starts=[0, 24, 32, 88]),
    # chunk < page, as a verify step runs it: inside the first page,
    # straddling a boundary, ending at the bucket's last position
    "verify-group1": dict(group=1, chunk=5, page_tokens=16,
                          starts=[0, 3, 14, 91]),
    "verify-group4": dict(group=4, chunk=5, page_tokens=16,
                          starts=[7, 13, 30, None]),
    "verify-group8": dict(group=8, chunk=3, page_tokens=16,
                          starts=[15, 0, 93, 47]),
    # an unmapped window INSIDE the extent clips to a real page, as
    # `gather_pages` clips it
    "sentinel-inside": dict(group=4, chunk=16, page_tokens=16,
                            starts=[48, 80], unmapped=[(0, 1), (1, 4)]),
    "all-rows-dead": dict(group=4, chunk=16, page_tokens=16,
                          starts=[None, None]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_the_gather_path_on_real_rows(name):
    q, k, v, table, pos, live = _case(**CASES[name])
    want = fa._chunk_attention_xla(
        q, gather_pages(k, table, n_heads=q.shape[1]),
        gather_pages(v, table, n_heads=q.shape[1]), pos,
        1.0 / np.sqrt(HEAD_DIM))
    got = paged_chunk_attention(q, k, v, table, pos, backend="paged")
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # both accumulate in float32 and round once: a bf16 result may differ
    # by the rounding of its last place
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    # a row that holds no sequence reads nothing and gives zeros
    assert not got[~live].any()
    # the dispatcher's other arm is the gather path itself
    same = paged_chunk_attention(q, k, v, table, pos, backend="xla")
    np.testing.assert_array_equal(np.asarray(same, np.float32), want)


@pytest.mark.parametrize("dead", sorted(_walk.DEAD_ENTRIES))
@pytest.mark.parametrize("case", sorted(_walk.CASES))
def test_the_walk_reads_live_pages_only(case, dead):
    """The decode kernel's cases (`_walk.CASES`) for a chunk of 8 queries at
    each row's last positions, NaN in every page no live entry names: live
    rows equal the gather path over the clean arena, a row that walks
    nothing gives zeros."""
    chunk, group = 8, 4
    table, extents, live, named = _walk.table_for(case, dead,
                                                  min_length=chunk)
    rs = np.random.RandomState(2)
    k, v = (rs.standard_normal((_walk.N_PAGES, KV_HEADS, _walk.PT, HEAD_DIM))
            .astype(np.float32) for _ in range(2))
    q = jnp.asarray(rs.standard_normal(
        (len(extents), group * KV_HEADS, chunk, HEAD_DIM)), jnp.float32)
    table = jnp.asarray(table)
    pos = extents[:, None] - chunk + np.arange(chunk, dtype=np.int32)[None]
    want = fa._chunk_attention_xla(
        q, gather_pages(jnp.asarray(k), table, n_heads=q.shape[1]),
        gather_pages(jnp.asarray(v), table, n_heads=q.shape[1]),
        jnp.asarray(pos), 1.0 / np.sqrt(HEAD_DIM))
    got = flash_paged_chunk_attention(
        q, jnp.asarray(_walk.poisoned(k, named)),
        jnp.asarray(_walk.poisoned(v, named)), table, jnp.asarray(extents),
        pages_per_step=_walk.PAGES_PER_STEP, interpret=True)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    assert not got[~live].any()


@pytest.mark.parametrize("chunk,starts", [(16, [16, 48]), (5, [3, 30])],
                         ids=["prefill", "verify"])
def test_a_recycled_page_leaks_nothing_of_its_earlier_tenant(chunk, starts):
    """What an earlier tenant left beyond a row's extent — in the rest of its
    last page, and in the pages its table still names — never reaches the
    result: the property SERVE002 audits for the gather path."""
    q, k, v, table, pos, _ = _case(group=4, chunk=chunk, page_tokens=16,
                                   starts=starts)
    table = np.array(table)
    extents = np.asarray(pos)[:, -1] + 1
    stale_k, stale_v = np.array(k, np.float32), np.array(v, np.float32)
    (spare, *_) = set(range(k.shape[0])) - set(table.ravel().tolist())
    stale_k[spare], stale_v[spare] = 3e4, -3e4
    for r, extent in enumerate(extents):
        table[r, -(-extent // 16):] = spare     # mapped, not yet written
        last = table[r, (extent - 1) // 16]
        stale_k[last, :, extent % 16 or 16:] = 3e4
        stale_v[last, :, extent % 16 or 16:] = -3e4
    clean = flash_paged_chunk_attention(q, k, v, jnp.asarray(table),
                                        jnp.asarray(extents))
    stale = flash_paged_chunk_attention(
        q, jnp.asarray(stale_k, k.dtype), jnp.asarray(stale_v, v.dtype),
        jnp.asarray(table), jnp.asarray(extents))
    np.testing.assert_array_equal(np.asarray(stale, np.float32),
                                  np.asarray(clean, np.float32))


def test_the_knob_takes_the_decode_knobs_values(monkeypatch):
    q, k, v, table, pos, _ = _case(group=1, chunk=16, page_tokens=16,
                                   starts=[0])
    monkeypatch.setattr(edconfig, "prefill_attention_backend", "auto")
    auto = paged_chunk_attention(q, k, v, table, pos)   # off a TPU: "xla"
    np.testing.assert_array_equal(
        np.asarray(auto, np.float32), np.asarray(
            paged_chunk_attention(q, k, v, table, pos, backend="xla"),
            np.float32))
    for kernel in ("paged", "flash"):
        paged_chunk_attention(q, k, v, table, pos, backend=kernel)
    with pytest.raises(ValueError, match="auto|paged|flash|xla"):
        paged_chunk_attention(q, k, v, table, pos, backend="blocked")


# ------------------------------------------------- what a layer costs the host

# (rows, heads, kv_heads, chunk, page_tokens, max_pages, n_pages): the chunk
# programs of the three serving cells
CELLS = {"mistral": (4, 32, 8, 64, 64, 32, 576),
         "granite": (4, 32, 8, 256, 256, 16, 1024),
         "kexaone": (2, 64, 8, 256, 256, 32, 2048)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sixteen_layers_trace_the_kernel_once(cell):
    """A 16-layer chunk program holds 16 `pallas_call` equations of ONE
    kernel whose `jaxpr` and grid mapping are ONE object each: the body was
    traced once, and jax lowers equal equations once a module.  The body
    stays small (the first builder's had 20 top-level equations; the walk
    over live windows, with its copies, has 39)."""
    rows, h, kvh, c, pt, mp, n_pages = CELLS[cell]
    bf16, layers = jnp.bfloat16, 16
    pages = jax.ShapeDtypeStruct((n_pages, kvh, pt, 128), bf16)

    def program(qs, ks, vs, table, extents):
        return [flash_paged_chunk_attention(q, k, v, table, extents,
                                            interpret=False)
                for q, k, v in zip(qs, ks, vs)]

    fa._paged_call.cache_clear()
    closed = jax.make_jaxpr(program)(
        [jax.ShapeDtypeStruct((rows, h, c, 128), bf16)] * layers,
        [pages] * layers, [pages] * layers,
        jax.ShapeDtypeStruct((rows, mp), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32))
    calls = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == layers
    assert {e.params["name"] for e in calls} == {"paged_chunk"}
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    assert len({id(e.params["grid_mapping"]) for e in calls}) == 1
    assert fa._paged_call.cache_info().misses == 1
    assert len(calls[0].params["jaxpr"].eqns) <= 40
    # the arena leaves are passed ONCE each, after table, extents and q ...
    assert len(calls[0].invars) == 5
    # ... and no wider a window than the decode kernel's at the same pages
    slots = [v.aval.shape for v in calls[0].params["jaxpr"].invars
             if len(v.aval.shape) == 5]
    assert len(slots) == 2 and slots[0] == slots[1] and slots[0][0] == 2
    assert slots[0][1] <= fa._paged_step_shape(mp, (pages, pages))[1]


def test_the_decode_kernel_is_built_once_a_signature_too():
    pages = jax.ShapeDtypeStruct((48, 8, 64, 128), jnp.bfloat16)

    def program(qs, table, lengths):
        return [fa.flash_paged_decode_attention(q, pages_, pages_, table,
                                                lengths, interpret=False)
                for q, pages_ in qs]

    closed = jax.make_jaxpr(program)(
        [(jax.ShapeDtypeStruct((4, 32, 128), jnp.bfloat16), pages)] * 3,
        jax.ShapeDtypeStruct((4, 16), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32))
    calls = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1


# -------------------------------------------------------------- sessions


def _llama():
    from easydist_tpu.models import llama

    cfg = llama.LlamaConfig(vocab=96, seq=64, dim=32, heads=4, kv_heads=2,
                            layers=2, ffn_dim=64)
    return llama.decoder(cfg), llama.llama_init(cfg, jax.random.PRNGKey(1))


def _granite_hybrid():
    from easydist_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig.tiny()
    return gh.decoder(cfg), gh.granite_init(cfg, jax.random.PRNGKey(2))


def _exaone_moe():
    from easydist_tpu.models import exaone_moe as em

    cfg = em.ExaoneMoeConfig.tiny()
    return em.decoder(cfg), em.exaone_init(cfg, jax.random.PRNGKey(3))


@pytest.mark.parametrize("family", [_llama, _granite_hybrid, _exaone_moe],
                         ids=["llama", "granite_hybrid", "exaone_moe"])
def test_a_sessions_greedy_tokens_do_not_know_the_backend(family, monkeypatch):
    from easydist_tpu.serve import GenerationSession, ServeConfig

    model, params = family()
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 96, size=n).tolist(), m)
            for n, m in ((5, 4), (19, 6), (8, 3), (30, 5), (41, 7))]
    ids, kernels = {}, {}
    for backend in ("xla", "paged"):
        monkeypatch.setattr(edconfig, "prefill_attention_backend", backend)
        sess = GenerationSession(params, model=model, config=ServeConfig(
            decode_buckets=(64,), max_decode_slots=4,
            prefill_chunk=8, prefill_batch=2, enable_prefix_cache=False,
            speculate_k=0))
        futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
        sess.run_until_drained()
        ids[backend] = [fut.result(timeout=5)["ids"] for fut in futs]
        (chunk_c,) = (c for name, c in sess._paged_cs.items()
                      if name.startswith("chunk"))
        (result,) = chunk_c._cache.values()
        kernels[backend] = sorted(
            e.params["name"] for e in result.closed_jaxpr.jaxpr.eqns
            if e.primitive.name == "pallas_call")
        walked = sess.metrics.counter("prefill_pages_walked")
        bucket = sess.metrics.counter("prefill_pages_bucket")
        assert 0 < walked < bucket
        sess.close()
    assert "paged_chunk" in kernels["paged"]
    assert "paged_chunk" not in kernels["xla"]
    assert ids["paged"] == ids["xla"]


# ------------------------------ the step shapes the serving cells are held to

# cell -> ((heads, kv_heads, chunk, page_tokens, max_pages), what the
# kernels took BEFORE a group's query heads could be taken in blocks (PR 44's
# tree, computed there): the decode round's (KV heads a step, pages a
# window), the chunk call's, the query heads of a group a chunk step holds,
# and what one KV head's chunk step reckons to in MiB)
PINNED = {
    "mistral": ((32, 8, 64, 64, 32), (8, 4), (4, 4), 4, 1.0625),
    "granite": ((32, 8, 256, 256, 16), (8, 1), (1, 1), 4, 5.25),
    # over the 8 MiB budget, under the 16 MiB a kernel may scope: it has no
    # smaller shape to take and runs as it is
    "kexaone": ((64, 8, 256, 256, 32), (8, 1), (1, 1), 8, 9.75),
    "olmo": ((30, 30, 256, 256, 16), (10, 1), (3, 1), 1, 1.875),
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_the_cells_step_shapes_and_query_blocks_are_the_parents(cell):
    """Blocks of a group's query heads came for 20 heads on ONE KV head
    (`_query_head_block`); every shape served before keeps the step shape
    and the WHOLE group it had."""
    (h, kvh, c, pt, max_pages), decode, chunk, head_block, mib = PINNED[cell]
    pages = (jax.ShapeDtypeStruct((64, kvh, pt, 128), jnp.bfloat16),) * 2
    rows = fa._chunk_rows(c)
    assert fa._paged_step_shape(max_pages, pages) == decode
    assert fa._query_head_block(pages, h // kvh, rows) == head_block \
        == h // kvh
    assert fa._paged_step_shape(max_pages, pages, None,
                                head_block * rows) == chunk
    assert fa._paged_step_bytes(pages, 1, 1, (h // kvh) * rows) \
        == mib * 2 ** 20


def test_the_latent_cells_blocks_are_the_parents():
    """A.X-K1's chunk kernel: blocks of 2 of its 64 query heads against the
    one shared head (`_latent_head_block`, a rule of its own: another
    kernel's bytes), windows of 4 pages a decode round."""
    pages = (jax.ShapeDtypeStruct((2048, 1, 256, 640), jnp.bfloat16),)
    assert fa._latent_head_block(64, 256, 640, 512, 256, jnp.bfloat16) == 2
    assert fa._paged_step_shape(64, pages, fa._LATENT_STEP_TOKENS // 256) \
        == (1, 4)
    assert fa._paged_step_shape(64, pages, None, 2 * 256) == (1, 1)


# ---- the lane-dense leaf (kv/arena.py): heads of 32 four positions to a
# 128-lane row.  The chunk kernel takes it as it lies, the gather path
# through a reshape; both are the plain leaf's attention.


@pytest.mark.parametrize("name", ["prefill-group4", "prefill-float32",
                                  "verify-group4", "prefill-pages-of-8"])
def test_lane_dense_leaves_attend_as_plain_ones(name):
    from easydist_tpu.kv.arena import lane_parts

    q, k, v, table, pos, live = _case(**CASES[name])
    pt = k.shape[2]
    parts = lane_parts(HEAD_DIM, pt)
    assert parts == 4
    dense = [a.reshape(a.shape[0], KV_HEADS, pt // parts, 128)
             for a in (k, v)]
    got = paged_chunk_attention(q, *dense, table, pos, backend="paged")
    # the very call a plain leaf makes after `_whole_lanes`
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(paged_chunk_attention(q, k, v, table, pos,
                                         backend="paged"), np.float32))
    xla = paged_chunk_attention(q, *dense, table, pos, backend="xla")
    np.testing.assert_array_equal(
        np.asarray(xla, np.float32),
        np.asarray(paged_chunk_attention(q, k, v, table, pos,
                                         backend="xla"), np.float32))
    # and plain attention: each live row's real keys, a query at a time
    qf, kf, vf = (np.asarray(a, np.float64) for a in (q, k, v))
    rep = q.shape[1] // KV_HEADS
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    for r in np.flatnonzero(live):
        for i, p in enumerate(np.asarray(pos)[r]):
            pages = np.asarray(table)[r, :p // pt + 1]
            for head in range(q.shape[1]):
                keys = kf[pages, head // rep].reshape(-1, HEAD_DIM)[:p + 1]
                vals = vf[pages, head // rep].reshape(-1, HEAD_DIM)[:p + 1]
                s = keys @ qf[r, head, i] / np.sqrt(HEAD_DIM)
                w = np.exp(s - s.max())
                np.testing.assert_allclose(
                    np.asarray(got, np.float32)[r, head, i],
                    (w / w.sum()) @ vals, atol=tol, rtol=tol)
