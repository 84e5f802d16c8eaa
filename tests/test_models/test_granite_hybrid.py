"""Granite 4.0-H through `models/decoder.py`'s one loop at a tiny size — a
three-layer period [mamba, attention, mamba], 8 experts top-2 of which 4
are held — against the plain reference (`chipbench/reference/`, float32,
sequential scan, dense experts): chunked prefill then decode through the
pools, the scan's indifference to chunking, padding and dead rows, slots,
the expert shares, and what a session refuses for a model with state."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_granite
from chipbench.reference import granite_hybrid as reference
from easydist_tpu.models import granite_hybrid as gh
from easydist_tpu.models.decoder import Paged, State, chunk, decode
from easydist_tpu.serve import GenerationSession, ServeConfig

SIZES = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=0.125, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
    mamba_chunk_size=8, router_experts=8, experts_held=[0, 4],
    num_local_experts=4, num_experts_per_tok=2, intermediate_size=16,
    shared_intermediate_size=24, layer_types=["mamba", "attention", "mamba"],
    num_hidden_layers=3, vocab_size=96, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5)
CFG = gh.GraniteHybridConfig(
    vocab=96, dim=32, layer_types=tuple(SIZES["layer_types"]), heads=4,
    kv_heads=2, head_dim=8, attention_multiplier=0.125, mamba_heads=4,
    mamba_head_dim=16, d_state=16, mamba_chunk=8, experts=8, top_k=2,
    experts_held=(0, 4), expert_dim=16, shared_dim=24, dtype="float32")
N_SLOTS, PT, N_PAGES, MAX_PAGES = 4, 8, 16, 4


@pytest.fixture(scope="module")
def params():
    return weights_granite.granite_params(SIZES, weights_granite.seed_key(3),
                                          dtype=jnp.float32)


def _pools(dec):
    return {**Paged.init(dec, N_PAGES, PT), **State.init(dec, N_SLOTS)}


def _table(rows):
    """Slot i owns pages 4i..4i+3; `rows` lists the slot of each row (None
    = no sequence)."""
    tbl = np.full((len(rows), MAX_PAGES), N_PAGES, np.int32)
    for r, slot in enumerate(rows):
        if slot is not None:
            tbl[r] = slot * MAX_PAGES + np.arange(MAX_PAGES)
    return jnp.asarray(tbl)


def _prefill(dec, cache, params, prompts, slots, c_len=PT):
    """Chunked prefill of `prompts` (row r into slot slots[r]); returns
    (cache, logits at each row's last position)."""
    n = max(len(p) for p in prompts)
    last = [None] * len(prompts)
    for start in range(0, n, c_len):
        toks = np.zeros((len(prompts), c_len), np.int32)
        row_slots = []
        for r, p in enumerate(prompts):
            seg = p[start:start + c_len]
            toks[r, :len(seg)] = seg
            row_slots.append(slots[r] if seg else None)
        pages, leaves = State.split(dec, cache)
        sl = jnp.asarray([N_SLOTS if s is None else s for s in row_slots],
                         jnp.int32)
        starts = jnp.full((len(prompts),), start, jnp.int32)
        st = State(leaves, sl < N_SLOTS, sl, fresh=starts == 0)
        cache, logits = chunk(dec, Paged(pages, _table(row_slots)), params,
                              jnp.asarray(toks), starts,
                              jnp.asarray([len(p) for p in prompts]),
                              state=st)
        for r, p in enumerate(prompts):
            if start < len(p) <= start + c_len:
                last[r] = np.asarray(logits[r])
    return cache, last


def _decode(dec, cache, params, tokens, positions, live):
    """One decode round over all N_SLOTS rows (`live`: the slots that are
    sequences)."""
    pages, leaves = State.split(dec, cache)
    alive = np.zeros((N_SLOTS,), bool)
    alive[list(live)] = True
    st = State(leaves, jnp.asarray(alive))
    tbl = _table([i if i in live else None for i in range(N_SLOTS)])
    return decode(dec, Paged(pages, tbl), params, jnp.asarray(tokens),
                  jnp.asarray(positions), state=st)


def _scan_kernel(monkeypatch):
    """The chunked scan's Pallas kernel under the interpreter, in the
    mixer's place (on the CPU the mixer takes the jnp form)."""
    from easydist_tpu.ops import ssm

    monkeypatch.setattr(ssm, "ssd_chunk_scan", functools.partial(
        ssm.ssd_chunk_scan, backend="pallas", interpret=True))
    return ssm


@pytest.mark.parametrize("scan", ["jnp", "pallas"])
def test_chunked_prefill_then_decode_equals_the_reference(params, scan,
                                                          monkeypatch):
    """Logits, not tokens.  Both sides are float32; they differ in the
    order of sums (SSD blocks against a sequential scan, grouped rows
    against dense experts, a paged softmax): 2e-5 of the logits' spread,
    where leaving a term out moves them by the spread itself.  With the
    scan's kernel interpreted too (float32 operands there, as the jnp
    form's are on the CPU)."""
    if scan == "pallas":
        _scan_kernel(monkeypatch)
    dec = gh.decoder(CFG)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 96, size=19).tolist()
    cache, last = _prefill(dec, _pools(dec), params, [prompt], [2])
    seq, got = list(prompt), [last[0]]
    for _ in range(5):
        seq.append(int(np.argmax(got[-1])))
        toks, pos = np.zeros(N_SLOTS, np.int32), np.zeros(N_SLOTS, np.int32)
        toks[2], pos[2] = seq[-1], len(seq) - 1
        cache, logits = _decode(dec, cache, params, toks, pos, {2})
        got.append(np.asarray(logits[2]))
    want = np.asarray(reference.logits(params, SIZES,
                                       np.asarray(seq, np.int32)))
    want = want[len(prompt) - 1:]
    spread = want.std()
    np.testing.assert_allclose(np.stack(got), want, atol=2e-5 * spread
                               + 1e-9, rtol=2e-4)
    assert (np.stack(got).argmax(-1) == want.argmax(-1)).all()


@pytest.mark.parametrize("c_len", [8, 16, 24])
def test_the_scan_gives_the_same_state_whatever_the_chunk(params, c_len):
    """Chunks of 8, 16 and the whole 24-token prompt (pages of that size)
    leave the same SSM state, conv tail and last logits."""
    global PT, MAX_PAGES
    dec = gh.decoder(dataclasses.replace(CFG, mamba_chunk=c_len))
    prompt = np.random.default_rng(1).integers(1, 96, size=24).tolist()
    keep = PT, MAX_PAGES
    try:
        PT, MAX_PAGES = c_len, 4
        got, last = _prefill(dec, _pools(dec), params, [prompt], [1], c_len)
        PT, MAX_PAGES = 8, 4
        want, last8 = _prefill(gh.decoder(CFG), _pools(dec), params,
                               [prompt], [1], 8)
    finally:
        PT, MAX_PAGES = keep
    for name in dec.state_shapes:
        for a, b in zip(got[name], want[name]):
            np.testing.assert_allclose(a[1], b[1], rtol=1e-4, atol=1e-6)
            assert not np.asarray(a[0]).any()      # another slot: untouched
    np.testing.assert_allclose(last[0], last8[0], rtol=1e-4, atol=1e-8)


@pytest.mark.parametrize("scan", ["jnp", "pallas"])
def test_padded_positions_and_dead_rows_leave_state_bit_identical(
        params, scan, monkeypatch):
    if scan == "pallas":
        _scan_kernel(monkeypatch)
    dec = gh.decoder(CFG)
    rng = np.random.default_rng(2)
    a, b = rng.integers(1, 96, size=11).tolist(), \
        rng.integers(1, 96, size=5).tolist()
    cache, _ = _prefill(dec, _pools(dec), params, [a, b], [0, 3])
    # the same prompts, the chunks' padding filled with other tokens
    toks = np.asarray([a[8:] + [7] * 5, [9] * 8], np.int32)
    first, _ = _prefill(dec, _pools(dec), params, [a[:8], b], [0, 3])
    pages, leaves = State.split(dec, first)
    sl = jnp.asarray([0, N_SLOTS], jnp.int32)     # row 1: no sequence now
    st = State(leaves, sl < N_SLOTS, sl, fresh=jnp.asarray([False, False]))
    other, _ = chunk(dec, Paged(pages, _table([0, None])), params,
                     jnp.asarray(toks), jnp.asarray([8, 8]),
                     jnp.asarray([len(a), len(b)]), state=st)
    for name in dec.state_shapes:
        for x, y in zip(cache[name], other[name]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # a decode round in which only slot 0 is live: the others' state, the
    # dead slot 3 (a finished prefill) included, is bit for bit as it was
    toks, pos = np.full(N_SLOTS, 5, np.int32), np.full(N_SLOTS, 3, np.int32)
    pos[0] = len(a)
    after, _ = _decode(dec, cache, params, toks, pos, {0})
    for name in dec.state_shapes:
        for x, y in zip(cache[name], after[name]):
            np.testing.assert_array_equal(np.asarray(x)[1:],
                                          np.asarray(y)[1:])
            assert (np.asarray(x)[0] != np.asarray(y)[0]).any()


def test_two_sequences_swapped_between_slots_give_swapped_results(params):
    dec = gh.decoder(CFG)
    rng = np.random.default_rng(3)
    a, b = rng.integers(1, 96, size=13).tolist(), \
        rng.integers(1, 96, size=6).tolist()

    def run(slot_a, slot_b):
        cache, last = _prefill(dec, _pools(dec), params, [a, b],
                               [slot_a, slot_b])
        toks, pos = np.zeros(N_SLOTS, np.int32), np.zeros(N_SLOTS, np.int32)
        toks[slot_a], pos[slot_a] = int(np.argmax(last[0])), len(a)
        toks[slot_b], pos[slot_b] = int(np.argmax(last[1])), len(b)
        _, logits = _decode(dec, cache, params, toks, pos, {slot_a, slot_b})
        return last, np.asarray(logits)

    last, logits = run(0, 2)
    last_s, logits_s = run(2, 0)
    np.testing.assert_array_equal(last[0], last_s[0])
    np.testing.assert_array_equal(last[1], last_s[1])
    np.testing.assert_array_equal(logits[0], logits_s[2])
    np.testing.assert_array_equal(logits[2], logits_s[0])
    assert not np.array_equal(logits[0], logits[2])


def test_the_expert_shares_add_up_to_the_uncut_layer(params):
    """Experts 0-3 and 4-7, each share routing over all 8, with the shared
    MLP (which every chip computes alike) counted once, against the
    reference's layer holding all 8."""
    full = dict(SIZES, experts_held=[0, 8], num_local_experts=8)
    blk = weights_granite.granite_params(
        full, weights_granite.seed_key(5), dtype=jnp.float32)["blocks"][0]
    u = jax.random.normal(jax.random.PRNGKey(1), (21, 32), jnp.float32)
    c = dict(reference.constants(full))
    with jax.default_matmul_precision("highest"):
        want = reference._moe(u, blk, c, False) + reference._glu(
            u, blk["shared_w1"], blk["shared_w2"], False)
    got = gh.shared_mlp(CFG, blk, u)
    counted = 0
    for first in (0, 4):
        share = dict(blk, w1=blk["w1"][first:first + 4],
                     w2=blk["w2"][first:first + 4])
        part, counters = gh.expert_ffn(
            dataclasses.replace(CFG, experts_held=(first, 4)), share, u)
        got = got + part
        counted += int(counters[0])
    assert counted == 21 * 2          # every pair went to exactly one share
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_rows_that_are_not_valid_are_routed_nowhere(params):
    blk = params["blocks"][0]
    u = jax.random.normal(jax.random.PRNGKey(2), (10, 32), jnp.float32)
    valid = jnp.arange(10) < 6
    part, counters = gh.expert_ffn(CFG, blk, u, valid)
    whole, every = gh.expert_ffn(CFG, blk, u)
    assert not np.asarray(part[6:]).any()
    np.testing.assert_array_equal(part[:6], whole[:6])
    assert 0 < int(counters[0]) < int(every[0]) <= 20
    assert int(counters[2]) <= int(counters[0])


@pytest.mark.parametrize("first", [0, 3], ids=["held-0-3", "held-3-6"])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("rows", [1, 5, 16, 64])
def test_the_expert_ffn_is_the_dense_sum_over_held_choices(params, rows,
                                                           masked, first):
    """Against the reference's layer, which runs every held expert densely
    over every token and knows no order of pairs: 1 and 5 rows (slices of
    the gathered products that are no whole tile), 16 and 64, some rows
    not valid, a held window that starts past expert 0."""
    cfg = dataclasses.replace(CFG, experts_held=(first, 4))
    blk = params["blocks"][0]
    u = jax.random.normal(jax.random.PRNGKey(10 + rows), (rows, 32),
                          jnp.float32)
    valid = np.arange(rows) % 3 != 1 if masked else np.ones(rows, bool)
    got, counters = gh.expert_ffn(cfg, blk, u,
                                  jnp.asarray(valid) if masked else None)
    c = dict(reference.constants(dict(SIZES, experts_held=[first, 4])))
    with jax.default_matmul_precision("highest"):
        want = np.where(valid[:, None], reference._moe(u, blk, c, False), 0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    if rows >= 16:      # top-2 of 8 with 4 held: some pairs land, some not
        assert 0 < int(counters[0]) < 2 * rows
    assert not np.asarray(got)[~valid].any()


def test_products_nothing_wrote_cannot_reach_the_output(params, monkeypatch):
    """The kernel leaves the rows of dead blocks UNWRITTEN: NaN there (put
    into the first product's fallback output, where the kernel would leave
    whatever was in memory) runs through the activation into the second
    product's operand and must not reach a token — the sum is made inside
    that product, over live blocks only
    (`tests/test_ops/test_ssm_and_grouped_matmul.py` poisons the places of
    LIVE blocks that hold no pair as well)."""
    from easydist_tpu.ops import grouped_matmul as gm

    blk = params["blocks"][0]
    u = jax.random.normal(jax.random.PRNGKey(7), (16, 32), jnp.float32)
    want, _ = gh.expert_ffn(CFG, blk, u)
    poisoned = []

    def unwritten(x, w, block_expert, live_blocks, tm):
        out = gm._gmm_xla(x, w, block_expert, live_blocks, tm)
        dead = jnp.repeat(jnp.arange(block_expert.shape[0]) >= live_blocks,
                          tm)
        poisoned.append(bool(dead[-1]))
        return jnp.where(dead[:, None], jnp.nan, out)

    monkeypatch.setattr(gm, "grouped_matmul", unwritten)
    got, counters = gh.expert_ffn(CFG, blk, u)
    assert poisoned == [True]              # the first product's dead rows
    assert int(counters[0]) < 2 * 16       # and some pair is held elsewhere
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)


REFUSED = {   # what -> (the config that asks for it, the error names it)
    "the prefix trie": (dict(enable_prefix_cache=True), "prefix trie"),
    "speculation": (dict(speculate_k=2), "speculation"),
    "the host tier": (dict(enable_prefix_cache=True,
                           kv_host_tier_bytes=1 << 20), "host tier"),
    "the int8 arena": (dict(kv_quant_dtype="int8"), "int8 arena"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_model_with_state_layers_refuses(params, what):
    base = dict(decode_buckets=(32,), max_decode_slots=2,
                prefill_chunk=8, enable_prefix_cache=False, speculate_k=0)
    asked, named = REFUSED[what]
    with pytest.raises(ValueError, match="state layers.*" + named):
        GenerationSession(params, model=gh.decoder(CFG),
                          config=ServeConfig(**{**base, **asked}))
    GenerationSession(params, model=gh.decoder(CFG),
                      config=ServeConfig(**base)).close()


def test_a_session_serves_it_and_the_ids_are_the_references(params):
    sess = GenerationSession(params, model=gh.decoder(CFG), config=ServeConfig(
        decode_buckets=(64,), max_decode_slots=N_SLOTS,
        prefill_chunk=PT, prefill_batch=2, enable_prefix_cache=False,
        speculate_k=0))
    rng = np.random.default_rng(4)
    reqs = [(rng.integers(1, 96, size=n).tolist(), m)
            for n, m in ((5, 4), (19, 6), (8, 3), (30, 5), (3, 7), (16, 9))]
    futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
    sess.run_until_drained()
    for (prompt, _), fut in zip(reqs, futs):
        ids = fut.result(timeout=5)["ids"]
        want = np.asarray(reference.logits(
            params, SIZES, np.asarray(prompt + ids, np.int32)))
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(ids)]
        assert rows.argmax(-1).tolist() == ids
    counters = sess.metrics.snapshot()["counters"]
    assert counters["moe_rounds"] > 0 and counters["moe_prefill_calls"] > 0
    assert 0 < counters["moe_pairs_routed"] <= 2 * 3 * \
        counters["tokens_generated"]
    sess.close()


def test_a_session_on_a_mesh_counts_the_scan_kernel_once_a_state_layer(
        params, monkeypatch):
    """The chunk program emitted for a mesh of two devices with the scan's
    kernel in the mixer's place: `pallas_calls{kernel=ssd_chunk_scan,
    row_shards=1}` reads one a state layer (the kernel's D has no row to
    split, so every device runs the call whole) and nothing for the decode
    program, the kernel was built ONCE for both layers (`_ssd_scan_call`),
    and the ids are still the reference's."""
    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.runtime import spans

    ssm = _scan_kernel(monkeypatch)
    ssm._ssd_scan_call.cache_clear()
    spans.clear()
    mesh = make_device_mesh((2,), ("tp",), devices=jax.devices()[:2])
    sess = GenerationSession(params, model=gh.decoder(CFG), mesh=mesh,
                             config=ServeConfig(
        decode_buckets=(64,), max_decode_slots=N_SLOTS, prefill_chunk=PT,
        prefill_batch=2, enable_prefix_cache=False, speculate_k=0))
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 96, size=n).tolist(), m)
            for n, m in ((19, 4), (8, 3), (30, 5))]
    futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
    sess.run_until_drained()
    for (prompt, _), fut in zip(reqs, futs):
        ids = fut.result(timeout=5)["ids"]
        want = np.asarray(reference.logits(
            params, SIZES, np.asarray(prompt + ids, np.int32)))
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(ids)]
        assert rows.argmax(-1).tolist() == ids
    sess.close()
    counted = {k: v for k, v in spans.snapshot()["counters"].items()
               if k.startswith("pallas_calls{kernel=ssd")}
    assert counted == {
        "pallas_calls{kernel=ssd_chunk_scan,row_shards=1}":
        CFG.layer_types.count("mamba")}
    assert ssm._ssd_scan_call.cache_info().misses == 1


# re-recorded where the conv's tail went flat (PR 46: the carry's "conv" is
# [b, (d_conv - 1) * conv_dim], so the text names another shape and other
# ops), on the CPU, by jax 0.9.0.  The digests before it (e5bf456767e73e43,
# 28ac03f4c7f95629) were taken at the commit before the conv moved to
# `ops/ssm.py::causal_conv_tail`; what carries over from them is below: the
# mixer's VALUES, bit for bit, against the tail in rows.
MIXER_SINCE_THE_TAIL_LAY_FLAT = {"window": "7952c6d50e2d3be3",
                                 "decode": "c82b289422434a25"}


@pytest.mark.parametrize("form", sorted(MIXER_SINCE_THE_TAIL_LAY_FLAT))
def test_the_mixer_lowers_to_what_it_did_before_the_conv_was_lifted(
        form, monkeypatch):
    """A migration proof, like `test_lowering_unchanged.py`: the mixer
    alone, over a window and over one position, (a) gives the bits it gave
    with the conv's tail as [b, d_conv - 1, conv_dim] — the body that was
    in `causal_conv_tail` then is frozen in `tests/test_ops/
    test_conv_tail.py` and put in its place here, fed the same tail in rows
    — and (b) lowers to the text recorded when the tail went flat."""
    import hashlib

    import jax
    import numpy as np

    from easydist_tpu.ops import ssm
    from tests.test_ops.test_conv_tail import _frozen

    cfg = gh.GraniteHybridConfig.tiny()
    blk = gh.granite_init(cfg, jax.random.PRNGKey(0))["blocks"][0]
    rng = np.random.default_rng(46)
    taps = cfg.d_conv - 1
    carry = {"conv": jnp.asarray(rng.normal(size=(2, taps * cfg.conv_dim)),
                                 jnp.float32),
             "ssm": jnp.asarray(rng.normal(size=(
                 2, cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state)),
                 jnp.float32)}
    if form == "window":
        u = jnp.asarray(rng.normal(size=(2, 8, cfg.dim)), jnp.float32)
        valid = jnp.arange(8)[None, :] < jnp.asarray([[8], [5]])
    else:
        u = jnp.asarray(rng.normal(size=(2, cfg.dim)), jnp.float32)
        valid = jnp.asarray([True, False])
    out, after = gh.mamba_mixer(cfg, blk, u, carry, valid)

    def in_rows(tail, x, w, bias, valid):
        conv, new = _frozen(tail.reshape(len(tail), taps, -1), x, w, bias,
                            valid)
        return conv, new.reshape(tail.shape)

    with monkeypatch.context() as mp:
        mp.setattr(ssm, "causal_conv_tail", in_rows)
        want, want_after = gh.mamba_mixer(cfg, blk, u, carry, valid)
    np.testing.assert_array_equal(out, want)
    for name in carry:
        np.testing.assert_array_equal(after[name], want_after[name])

    if jax.__version__ != "0.9.0":
        pytest.skip("digests recorded with jax 0.9.0")
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        (blk, u, carry, valid))
    text = jax.jit(lambda *a: gh.mamba_mixer(cfg, *a)).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == MIXER_SINCE_THE_TAIL_LAY_FLAT[form]
