"""Jamba through `models/decoder.py`'s one loop at a tiny size — four layers
[selective, selective, attention, selective], 64 channels of a 16-index
state, 4 query heads on ONE KV head — against the plain reference
(`chipbench/reference/`, float32, the recurrence a position at a time):
chunked prefill then decode through the pools, logits not tokens; padding
and dead rows; both kernels in the mixer's place; a session that serves
more requests than it has slots, with what it counts; what a session
refuses for a model with state; the paged chunk kernel at a group of 20
query heads on one KV head, in blocks of the group's heads and whole; and
the paged kernels cross-lowered for a TPU at `kv_heads=1`."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_jamba
from chipbench.reference import jamba as reference
from easydist_tpu.models import jamba
from easydist_tpu.models.decoder import Paged, State, chunk, decode
from easydist_tpu.ops import gather_pages, ssm
from easydist_tpu.serve import GenerationSession, ServeConfig

fa = importlib.import_module("easydist_tpu.ops.flash_attention")

SIZES = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=1,
    intermediate_size=48, num_hidden_layers=4, attn_layer_period=4,
    attn_layer_offset=2, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
    num_experts=1, sliding_window=None, vocab_size=96, rms_norm_eps=1e-6,
    tie_word_embeddings=True)
CFG = jamba.JambaConfig.tiny()
N_SLOTS, PT, N_PAGES, MAX_PAGES = 4, 8, 16, 4


@pytest.fixture(scope="module")
def params():
    return weights_jamba.jamba_params(SIZES, weights_jamba.seed_key(3),
                                      dtype=jnp.float32)


def _pools(dec):
    return {**Paged.init(dec, N_PAGES, PT), **State.init(dec, N_SLOTS)}


def _table(rows):
    tbl = np.full((len(rows), MAX_PAGES), N_PAGES, np.int32)
    for r, slot in enumerate(rows):
        if slot is not None:
            tbl[r] = slot * MAX_PAGES + np.arange(MAX_PAGES)
    return jnp.asarray(tbl)


def _prefill(dec, cache, params, prompt, slot):
    """Chunked prefill of one prompt into `slot`, a second row idle."""
    last = None
    for start in range(0, len(prompt), PT):
        toks = np.zeros((2, PT), np.int32)
        seg = prompt[start:start + PT]
        toks[0, :len(seg)] = seg
        pages, leaves = State.split(dec, cache)
        sl = jnp.asarray([slot, N_SLOTS], jnp.int32)
        starts = jnp.full((2,), start, jnp.int32)
        st = State(leaves, sl < N_SLOTS, sl, fresh=starts == 0)
        cache, logits = chunk(dec, Paged(pages, _table([slot, None])), params,
                              jnp.asarray(toks), starts,
                              jnp.asarray([len(prompt), 0]), state=st)
        last = np.asarray(logits[0])
    return cache, last


def _decode(dec, cache, params, tokens, positions, live):
    pages, leaves = State.split(dec, cache)
    alive = np.zeros((N_SLOTS,), bool)
    alive[list(live)] = True
    tbl = _table([i if i in live else None for i in range(N_SLOTS)])
    return decode(dec, Paged(pages, tbl), params, jnp.asarray(tokens),
                  jnp.asarray(positions),
                  state=State(leaves, jnp.asarray(alive)))


def _serve_logits(dec, params, prompt, n_new, slot=2, cache=None):
    cache, last = _prefill(dec, _pools(dec) if cache is None else cache,
                           params, prompt, slot)
    seq, got = list(prompt), [last]
    for _ in range(n_new):
        seq.append(int(np.argmax(got[-1])))
        toks, pos = np.zeros(N_SLOTS, np.int32), np.zeros(N_SLOTS, np.int32)
        toks[slot], pos[slot] = seq[-1], len(seq) - 1
        cache, logits = _decode(dec, cache, params, toks, pos, {slot})
        got.append(np.asarray(logits[slot]))
    return cache, seq, np.stack(got)


def test_the_state_is_stored_with_the_channels_on_the_lanes():
    dec = jamba.decoder(CFG)
    assert dec.kinds == ("state", "state", "attention", "state")
    assert dec.state_shapes["selective"][0] == (16, 64)
    # x alone, no B or C; flat, the three inputs side by side on the lanes
    assert dec.state_shapes["conv"][0] == (3 * 64,)
    full = jamba.decoder(jamba.JambaConfig())
    # [16, 5120]: two sublane tiles by forty lane tiles, nothing padded
    assert full.state_shapes["selective"] == ((16, 5120), jnp.float32)
    # [3 x 5120]: a slot a sublane, each input forty lane tiles, no padding
    assert full.state_shapes["conv"] == ((3 * 5120,), jnp.float32)
    assert (full.heads, full.kv_heads, full.head_dim) == (20, 1, 128)
    assert full.kinds.count("state") == 26 and full.kv_layers == 2
    assert [i for i, k in enumerate(full.kinds) if k == "attention"] \
        == [7, 21]
    shapes = jax.eval_shape(lambda k: jamba.jamba_init(jamba.JambaConfig(),
                                                       k),
                            jax.random.PRNGKey(0))
    # 26 x 104.16 M + 2 x 76.68 M + the tied 167.8 M: 3.029 B parameters
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 3_029_337_472
    assert {k: v.shape for k, v in shapes["blocks"][0].items()} == {
        k: v.shape for k, v in jax.eval_shape(
            lambda k: weights_jamba.jamba_params(
                dict(SIZES, hidden_size=2560, num_attention_heads=20,
                     intermediate_size=8192, mamba_dt_rank=160,
                     vocab_size=65536), k),
            jax.random.PRNGKey(0))["blocks"][0].items()}


def test_chunked_prefill_then_decode_equals_the_reference(params):
    """Logits, not tokens.  Both sides are float32 and walk the recurrence
    a position at a time; they differ in the order of a few sums (the conv
    over [tail | window], a paged softmax, the state [index, channel]
    against [channel, index]): 1e-4 of the logits' spread, where leaving a
    term out moves them by a good part of the spread itself."""
    dec = jamba.decoder(CFG)
    prompt = np.random.default_rng(0).integers(1, 96, size=21).tolist()
    _, seq, got = _serve_logits(dec, params, prompt, 6)
    want = np.asarray(reference.logits(params, SIZES,
                                       np.asarray(seq, np.int32)))
    want = want[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=1e-4 * want.std(), rtol=1e-3)
    assert (got.argmax(-1) == want.argmax(-1)).all()


@pytest.mark.parametrize("what", ["no_norm_on_dt_b_c", "the_decay_left_out",
                                  "no_skip", "the_conv_bias_left_out",
                                  "attention_on_layer_0"])
def test_the_reference_sees_each_term_of_the_layer(params, what):
    """What the comparison above has power over: the layer with a term
    changed moves the logits by a good part of their spread."""
    broken = dict(params, blocks=[dict(b) for b in params["blocks"]])
    sizes = dict(SIZES)
    if what == "attention_on_layer_0":      # assumption (a)'s other reading
        sizes["attn_layer_offset"] = 0
        broken["blocks"] = [broken["blocks"][i] for i in (2, 0, 1, 3)]
    for blk in broken["blocks"]:
        if "a_log" not in blk:
            continue
        if what == "no_norm_on_dt_b_c":
            # the plain Mamba-1 reading: a norm with no gain is a scale
            # the softplus and the products see
            for g in ("norm_dt", "norm_b", "norm_c"):
                blk[g] = 3.0 * jnp.ones_like(blk[g])
        if what == "the_decay_left_out":
            blk["a_log"] = jnp.full_like(blk["a_log"], -30.0)
        if what == "no_skip":
            blk["d_skip"] = jnp.zeros_like(blk["d_skip"])
        if what == "the_conv_bias_left_out":
            blk["conv_b"] = jnp.zeros_like(blk["conv_b"])
    toks = np.random.default_rng(1).integers(1, 96, size=40).astype(np.int32)
    sound = np.asarray(reference.logits(params, SIZES, toks))
    moved = np.asarray(reference.logits(broken, sizes, toks))
    assert np.abs(moved - sound)[8:].max() > 0.2 * sound.std()


def test_a_fresh_row_starts_from_zero_state_in_a_slot_that_was_used(params):
    dec = jamba.decoder(CFG)
    rng = np.random.default_rng(2)
    first, second = (rng.integers(1, 96, size=n).tolist() for n in (21, 13))
    cache, _, _ = _serve_logits(dec, params, first, 3)
    assert float(jnp.abs(cache["selective"][0][2]).max()) > 0  # left behind
    _, seq, got = _serve_logits(dec, params, second, 3, cache=cache)
    want = np.asarray(reference.logits(params, SIZES,
                                       np.asarray(seq, np.int32)))
    np.testing.assert_allclose(got, want[len(second) - 1:],
                               atol=1e-4 * want.std(), rtol=1e-3)


def _kernels(monkeypatch):
    """Both selective kernels under the interpreter, in the mixer's place."""
    for name in ("selective_chunk_scan", "selective_decode_update"):
        monkeypatch.setattr(ssm, name, functools.partial(
            getattr(ssm, name), backend="pallas", interpret=True))


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "pallas"])
def test_padded_positions_and_dead_rows_leave_the_carry_bit_identical(
        params, monkeypatch, kernels):
    if kernels:
        _kernels(monkeypatch)
    blk = params["blocks"][0]
    rng = np.random.default_rng(3)
    carry = {"conv": jnp.asarray(rng.normal(size=(3, 3 * CFG.d_inner)),
                                 jnp.float32),
             "selective": jnp.asarray(rng.normal(size=(3, 16, CFG.d_inner)),
                                      jnp.float32)}
    x = jnp.asarray(rng.normal(size=(3, PT, 32)), jnp.float32)
    lengths = jnp.asarray([PT, 3, 0])
    valid = jnp.arange(PT)[None, :] < lengths[:, None]
    _, after = jamba.selective_mixer(CFG, blk, x, carry, valid)
    for name in carry:                       # the row with nothing real
        np.testing.assert_array_equal(after[name][2], carry[name][2])
    # a row of 3 real positions: as if the window had ended there
    _, short = jamba.selective_mixer(CFG, blk, x[1:2, :3],
                                     {k: v[1:2] for k, v in carry.items()},
                                     jnp.ones((1, 3), bool))
    for name in carry:
        np.testing.assert_array_equal(after[name][1], short[name][0])
    # a decode round: the dead row's carry as it was
    _, after = jamba.selective_mixer(CFG, blk, x[:, 0], carry,
                                     jnp.asarray([True, False, True]))
    for name in carry:
        np.testing.assert_array_equal(after[name][1], carry[name][1])
        assert not np.array_equal(after[name][0], carry[name][0])


def test_both_kernels_in_the_mixers_place_give_the_same_logits(
        params, monkeypatch):
    dec = jamba.decoder(CFG)
    prompt = np.random.default_rng(4).integers(1, 96, size=19).tolist()
    _, _, want = _serve_logits(dec, params, prompt, 3)
    _kernels(monkeypatch)
    _, _, got = _serve_logits(dec, params, prompt, 3)
    np.testing.assert_allclose(got, want, atol=2e-5)


REFUSED = {   # what -> (the config that asks for it, the error names it)
    "the prefix trie": (dict(enable_prefix_cache=True), "prefix trie"),
    "speculation": (dict(speculate_k=2), "speculation"),
    "the host tier": (dict(enable_prefix_cache=True,
                           kv_host_tier_bytes=1 << 20), "host tier"),
    "the int8 arena": (dict(kv_quant_dtype="int8"), "int8 arena"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_model_with_selective_layers_refuses(params, what):
    base = dict(decode_buckets=(32,), max_decode_slots=2,
                prefill_chunk=8, enable_prefix_cache=False, speculate_k=0)
    asked, named = REFUSED[what]
    with pytest.raises(ValueError, match="state layers.*" + named):
        GenerationSession(params, model=jamba.decoder(CFG),
                          config=ServeConfig(**{**base, **asked}))
    GenerationSession(params, model=jamba.decoder(CFG),
                      config=ServeConfig(**base)).close()


def test_a_session_serves_more_requests_than_slots_and_counts_them(params):
    """Paged, two prefill rows, two slots reused by six requests: every
    served token is the argmax of the reference's full forward."""
    sess = GenerationSession(
        params, model=jamba.decoder(CFG), config=ServeConfig(
            kv_layout="paged", decode_buckets=(64,), max_decode_slots=2,
            prefill_chunk=PT, prefill_batch=2, enable_prefix_cache=False,
            speculate_k=0))
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 96, size=n).tolist(), m)
            for n, m in ((5, 4), (19, 6), (8, 3), (30, 5), (3, 7), (16, 9))]
    futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
    seen = set()
    while sess.step():
        gauges = sess.metrics.snapshot()["gauges"]
        if "selective_state_bytes" in gauges:
            seen.add(gauges["selective_state_bytes"])
            assert gauges["state_slots"] == 2
            assert gauges["state_slots_in_use"] <= 2
    for (prompt, _), fut in zip(reqs, futs):
        ids = fut.result(timeout=5)["ids"]
        want = np.asarray(reference.logits(
            params, SIZES, np.asarray(prompt + ids, np.int32)))
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(ids)]
        assert rows.argmax(-1).tolist() == ids
    # one [16, 64] float32 matrix a SLOT a selective layer, all run long
    assert seen == {2 * 3 * 16 * 64 * 4}
    counters = sess.metrics.snapshot()["counters"]
    # a request's first token comes from its last chunk call, the others
    # from decode rounds: each updated its row's state on three layers
    rounds = sum(m - 1 for _, m in reqs)
    assert counters["tokens_generated"] == rounds
    assert counters["selective_rows_updated"] == 3 * rounds
    assert counters["selective_scan_positions"] == 3 * sum(
        len(p) for p, _ in reqs)
    assert "delta_state_bytes" not in sess.metrics.snapshot()["gauges"]
    pool = next(iter(sess._pools.values()))
    assert pool.state.in_use == 0 == pool.pool.in_use
    sess.close()


# ------------------------ twenty query heads on one KV head (the chunk kernel)


def _mqa_case(group=20, chunk=16, pt=16, d=32, seed=0):
    rng = np.random.default_rng(seed)
    n_pages = 12
    k, v = (jnp.asarray(rng.standard_normal((n_pages, 1, pt, d)),
                        jnp.float32) for _ in "kv")
    q = jnp.asarray(rng.standard_normal((3, group, chunk, d)), jnp.float32)
    # a row three pages deep, a row in its first chunk, a row with none
    table = jnp.asarray([[3, 5, 7, n_pages], [1] + [n_pages] * 3,
                         [n_pages] * 4], jnp.int32)
    extents = jnp.asarray([40, chunk, 0], jnp.int32)
    pos = extents[:, None] - chunk + jnp.arange(chunk)[None]
    want = fa._chunk_attention_xla(
        q, gather_pages(k, table, n_heads=group),
        gather_pages(v, table, n_heads=group), pos, d ** -0.5)
    return q, k, v, table, extents, want


@pytest.mark.parametrize("head_block", [20, 10, 5, 4, 1])
def test_the_chunk_kernel_in_blocks_of_the_groups_heads_is_the_gather_path(
        head_block, monkeypatch):
    q, k, v, table, extents, want = _mqa_case()
    monkeypatch.setattr(fa, "_query_head_block", lambda *a: head_block)
    got = fa.flash_paged_chunk_attention(q, k, v, table, extents,
                                         interpret=True)
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[2]).any()


def test_blocks_of_query_heads_beside_several_kv_heads(monkeypatch):
    """Two KV heads of a group of 4, in blocks of 2: entry i of the q
    view's second axis attends KV head i // 2."""
    rng = np.random.default_rng(1)
    k, v = (jnp.asarray(rng.standard_normal((6, 2, 8, 32)), jnp.float32)
            for _ in "kv")
    q = jnp.asarray(rng.standard_normal((2, 8, 8, 32)), jnp.float32)
    table = jnp.asarray([[0, 2, 4], [5, 6, 6]], jnp.int32)
    extents = jnp.asarray([20, 8], jnp.int32)
    pos = extents[:, None] - 8 + jnp.arange(8)[None]
    want = fa._chunk_attention_xla(
        q, gather_pages(k, table, n_heads=8), gather_pages(v, table,
                                                           n_heads=8),
        pos, 32 ** -0.5)
    whole = fa.flash_paged_chunk_attention(q, k, v, table, extents,
                                           interpret=True)
    for head_block in (2, 1):
        monkeypatch.setattr(fa, "_query_head_block", lambda *a: head_block)
        got = fa.flash_paged_chunk_attention(q, k, v, table, extents,
                                             interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got, whole, atol=1e-6)


def test_a_group_of_twenty_takes_blocks_of_five_and_is_refused_whole():
    bf16 = jnp.bfloat16
    pages = (jax.ShapeDtypeStruct((2048, 1, 256, 128), bf16),) * 2
    # 20 x 256 query rows on ONE KV head: 23.25 MiB by the file's own
    # formula, over what a kernel may scope; blocks of 5 heads: 6.5 MiB
    assert fa._paged_step_bytes(pages, 1, 1, 20 * 256) == 24_379_392
    assert fa._query_head_block(pages, 20, 256) == 5
    assert fa._paged_step_bytes(pages, 1, 1, 5 * 256) == 6_684_672 \
        <= fa._PAGED_VMEM_BUDGET
    with pytest.raises(ValueError, match="5120 query rows.*may scope"):
        fa._paged_step_shape(16, pages, rows=20 * 256)
    assert fa._paged_step_shape(16, pages, rows=5 * 256) == (1, 1)
    # a decode round's 20 query rows are left out of the reckoning
    assert fa._paged_step_shape(16, pages) == (1, 1)


def _lower_for_tpu(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("chunk", [0, 256], ids=["decode", "chunk"])
def test_the_paged_kernels_lower_for_a_tpu_at_one_kv_head(chunk):
    """The cell's shapes: 128 slots / 2 prefill rows, 20 query heads on ONE
    KV head of 128, 16 pages of 256 a bucket, 2,048 arena pages."""
    bf16 = jnp.bfloat16
    rows = 2 if chunk else 128
    pages = jax.ShapeDtypeStruct((2048, 1, 256, 128), bf16)
    q = jax.ShapeDtypeStruct((rows, 20) + ((chunk,) if chunk else ())
                             + (128,), bf16)
    call = fa.flash_paged_chunk_attention if chunk \
        else fa.flash_paged_decode_attention
    text = _lower_for_tpu(
        lambda q, k, v, t, n: call(q, k, v, t, n, interpret=False),
        q, pages, pages, jax.ShapeDtypeStruct((rows, 16), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32)).as_text()
    (custom,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert custom.count("tensor<2048x1x256x128xbf16>") == 2
    if chunk:   # the q view: four blocks of five heads' 256 rows
        assert "tensor<2x4x1280x128xbf16>" in custom
