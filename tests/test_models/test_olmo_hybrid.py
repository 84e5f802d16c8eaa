"""Olmo Hybrid through `models/decoder.py`'s one loop at a tiny size — four
layers [delta, delta, full, delta], 4 heads of an 8 x 64 state (two heads'
columns packed to 128 lanes) — against the plain reference
(`chipbench/reference/`, float32, the recurrence a position at a time):
chunked prefill then decode through the pools, logits not tokens; padding
and dead rows; the decode kernel in the mixer's place; a session that
serves more requests than it has slots, with what it counts; and what a
session refuses for a model with state."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_olmo
from chipbench.reference import olmo_hybrid as reference
from easydist_tpu.models import olmo_hybrid as oh
from easydist_tpu.models.decoder import Paged, State, chunk, decode
from easydist_tpu.ops import delta_rule
from easydist_tpu.serve import GenerationSession, ServeConfig

SIZES = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=48, num_hidden_layers=4,
    layer_types=["linear_attention", "linear_attention", "full_attention",
                 "linear_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=64, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, vocab_size=96, rms_norm_eps=1e-6,
    tie_word_embeddings=False, attention_bias=False,
    rope_parameters={"rope_theta": None})
CFG = oh.OlmoHybridConfig.tiny()
N_SLOTS, PT, N_PAGES, MAX_PAGES = 4, 8, 16, 4


@pytest.fixture(scope="module")
def params():
    return weights_olmo.olmo_params(SIZES, weights_olmo.seed_key(3),
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


def test_the_state_is_stored_two_heads_to_a_row_of_whole_lanes():
    dec = oh.decoder(CFG)
    assert dec.kinds == ("state", "state", "attention", "state")
    assert dec.state_shapes["delta"][0] == (2, 8, 128)
    # flat: the three inputs side by side on the lanes
    assert dec.state_shapes["conv"][0] == (3 * 4 * (2 * 8 + 64),)
    full = oh.decoder(oh.OlmoHybridConfig())
    assert full.state_shapes["delta"][0] == (15, 96, 384)
    assert full.state_shapes["conv"][0] == (3 * 11520,)
    assert (full.heads, full.kv_heads, full.head_dim) == (30, 30, 128)
    assert full.kinds.count("state") == 24 and full.kv_layers == 8


def test_chunked_prefill_then_decode_equals_the_reference(params):
    """Logits, not tokens.  Both sides are float32; they differ in the
    order of sums (blocks of 8 through a triangular inverse against a
    position at a time, a paged softmax): 1e-4 of the logits' spread, where
    leaving a term out moves them by the spread itself."""
    dec = oh.decoder(CFG)
    prompt = np.random.default_rng(0).integers(1, 96, size=21).tolist()
    _, seq, got = _serve_logits(dec, params, prompt, 6)
    want = np.asarray(reference.logits(params, SIZES,
                                       np.asarray(seq, np.int32)))
    want = want[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=1e-4 * want.std(), rtol=1e-3)
    assert (got.argmax(-1) == want.argmax(-1)).all()


@pytest.mark.parametrize("what", ["beta_is_one_sigmoid", "no_output_gate",
                                  "the_decay_left_out"])
def test_the_reference_sees_each_term_of_the_layer(params, what):
    """What the comparison above has power over: the layer with a term
    changed moves the logits by a good part of their spread."""
    broken = dict(params, blocks=[dict(b) for b in params["blocks"]])
    sizes = dict(SIZES)
    if what == "beta_is_one_sigmoid":
        sizes["linear_allow_neg_eigval"] = False
    for blk in broken["blocks"]:
        if "w_gate" in blk and what == "no_output_gate":
            blk["w_gate"] = jnp.zeros_like(blk["w_gate"])
        if "a_log" in blk and what == "the_decay_left_out":
            blk["a_log"] = jnp.full_like(blk["a_log"], -30.0)
    toks = np.random.default_rng(1).integers(1, 96, size=40).astype(np.int32)
    sound = np.asarray(reference.logits(params, SIZES, toks))
    moved = np.asarray(reference.logits(broken, sizes, toks))
    assert np.abs(moved - sound)[8:].max() > 0.2 * sound.std()


def test_a_fresh_row_starts_from_zero_state_in_a_slot_that_was_used(params):
    dec = oh.decoder(CFG)
    rng = np.random.default_rng(2)
    first, second = (rng.integers(1, 96, size=n).tolist() for n in (21, 13))
    cache, _, _ = _serve_logits(dec, params, first, 3)
    assert float(jnp.abs(cache["delta"][0][2]).max()) > 0    # left behind
    _, seq, got = _serve_logits(dec, params, second, 3, cache=cache)
    want = np.asarray(reference.logits(params, SIZES,
                                       np.asarray(seq, np.int32)))
    np.testing.assert_allclose(got, want[len(second) - 1:],
                               atol=1e-4 * want.std(), rtol=1e-3)


def test_padded_positions_and_dead_rows_leave_the_carry_bit_identical(
        params):
    blk = params["blocks"][0]
    rng = np.random.default_rng(3)
    carry = {"conv": jnp.asarray(rng.normal(size=(3, 3 * CFG.conv_dim)),
                                 jnp.float32),
             "delta": jnp.asarray(rng.normal(size=(3, 2, 8, 128)),
                                  jnp.float32)}
    x = jnp.asarray(rng.normal(size=(3, PT, 32)), jnp.float32)
    lengths = jnp.asarray([PT, 3, 0])
    valid = jnp.arange(PT)[None, :] < lengths[:, None]
    _, after = oh.gated_delta_mixer(CFG, blk, x, carry, valid)
    for name in carry:                       # the row with nothing real
        np.testing.assert_array_equal(after[name][2], carry[name][2])
    # a row of 3 real positions: as if the window had ended there
    _, short = oh.gated_delta_mixer(CFG, blk, x[1:2, :3],
                                    {k: v[1:2] for k, v in carry.items()},
                                    jnp.ones((1, 3), bool))
    np.testing.assert_array_equal(after["conv"][1], short["conv"][0])
    np.testing.assert_allclose(after["delta"][1], short["delta"][0],
                               atol=1e-5)
    # a decode round: the dead row's carry as it was
    _, after = oh.gated_delta_mixer(CFG, blk, x[:, 0], carry,
                                    jnp.asarray([True, False, True]))
    for name in carry:
        np.testing.assert_array_equal(after[name][1], carry[name][1])
        assert not np.array_equal(after[name][0], carry[name][0])


def test_the_decode_kernel_in_the_mixers_place_gives_the_same_round(
        params, monkeypatch):
    dec = oh.decoder(CFG)
    prompt = np.random.default_rng(4).integers(1, 96, size=11).tolist()
    _, _, want = _serve_logits(dec, params, prompt, 3)
    monkeypatch.setattr(delta_rule, "delta_decode_update", functools.partial(
        delta_rule.delta_decode_update, backend="pallas", interpret=True))
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
def test_a_model_with_delta_rule_layers_refuses(params, what):
    base = dict(decode_buckets=(32,), max_decode_slots=2,
                prefill_chunk=8, enable_prefix_cache=False, speculate_k=0)
    asked, named = REFUSED[what]
    with pytest.raises(ValueError, match="state layers.*" + named):
        GenerationSession(params, model=oh.decoder(CFG),
                          config=ServeConfig(**{**base, **asked}))
    GenerationSession(params, model=oh.decoder(CFG),
                      config=ServeConfig(**base)).close()


def test_a_session_serves_more_requests_than_slots_and_counts_them(params):
    sess = GenerationSession(params, model=oh.decoder(CFG), config=ServeConfig(
        decode_buckets=(64,), max_decode_slots=2,
        prefill_chunk=PT, prefill_batch=1, enable_prefix_cache=False,
        speculate_k=0))
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 96, size=n).tolist(), m)
            for n, m in ((5, 4), (19, 6), (8, 3), (30, 5), (3, 7), (16, 9))]
    futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
    seen = set()
    while sess.step():
        gauges = sess.metrics.snapshot()["gauges"]
        if "delta_state_bytes" in gauges:
            seen.add(gauges["delta_state_bytes"])
            assert gauges["state_slots"] == 2
            assert gauges["state_slots_in_use"] <= 2
    for (prompt, _), fut in zip(reqs, futs):
        ids = fut.result(timeout=5)["ids"]
        want = np.asarray(reference.logits(
            params, SIZES, np.asarray(prompt + ids, np.int32)))
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(ids)]
        assert rows.argmax(-1).tolist() == ids
    # one matrix a head a SLOT a layer, all run long
    assert seen == {2 * 3 * 4 * 8 * 64 * 4}
    counters = sess.metrics.snapshot()["counters"]
    assert counters["delta_rows_updated"] == 3 * counters["tokens_generated"]
    assert counters["delta_chunk_positions"] == 3 * sum(
        len(p) for p, _ in reqs)
    pool = next(iter(sess._pools.values()))
    assert pool.state.in_use == 0 == pool.pool.in_use
    sess.close()
