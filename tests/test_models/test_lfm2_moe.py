"""LFM2-MoE through `models/decoder.py`'s one loop at a tiny size — six
layers [conv, conv, attention, conv, attention, conv] (a LIST, no period),
the first two with a dense FFN and the other four with top-2-of-8 experts of
which four are held, 4 query heads on 2 KV heads of SIXTEEN over pages of
16 (so the arena's leaves are lane-dense, eight positions to a row) —
against the plain reference (`chipbench/reference/`, float32, one full
forward): chunked prefill then decode through the pools, logits not tokens;
a prompt that ends mid-chunk; padding and dead rows; the layer list and the
dense layers; the two shares of an expert layer adding up to the uncut
layer; a session that serves more requests than it has slots, with what it
counts; what a session refuses for a model with state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_lfm2
from chipbench.reference import lfm2_moe as reference
from easydist_tpu.kv.arena import plain_pages
from easydist_tpu.models import lfm2_moe
from easydist_tpu.models.decoder import Paged, State, chunk, decode
from easydist_tpu.serve import GenerationSession, ServeConfig

TYPES = ("conv", "conv", "full_attention", "conv", "full_attention", "conv")
SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=16, num_hidden_layers=6,
    layer_types=list(TYPES), num_dense_layers=2, num_experts=4,
    router_experts=8, experts_held=[0, 4], num_experts_per_tok=2,
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
    use_expert_bias=True, rope_theta=1e6, routed_scaling_factor=1,
    vocab_size=96)
CFG = lfm2_moe.Lfm2MoeConfig.tiny(dim=64)
N_SLOTS, PT, N_PAGES, MAX_PAGES = 4, 16, 16, 4


@pytest.fixture(scope="module")
def params():
    return weights_lfm2.lfm2_params(SIZES, weights_lfm2.seed_key(3),
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
        kv = Paged(pages, _table([slot, None]))
        cache, logits = chunk(dec, kv, params, jnp.asarray(toks), starts,
                              jnp.asarray([len(prompt), 0]), state=st)
        last = np.asarray(logits[0])
    return cache, last


def _decode(dec, cache, params, tokens, positions, live):
    pages, leaves = State.split(dec, cache)
    alive = np.zeros((N_SLOTS,), bool)
    alive[list(live)] = True
    tbl = _table([i if i in live else None for i in range(N_SLOTS)])
    return decode(dec, Paged(pages, tbl), params,
                  jnp.asarray(tokens), jnp.asarray(positions),
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


def test_the_layers_are_a_list_and_the_whole_carry_is_a_conv_tail():
    dec = lfm2_moe.decoder(CFG)
    assert dec.kinds == ("state", "state", "attention", "state",
                         "attention", "state")
    # ONE leaf a conv layer: the last two inputs of the conv, flat
    assert dec.state_shapes == {"shortconv": ((2 * 64,), jnp.float32)}
    assert dec.counts and dec.pair_slots == 2 * 4
    # heads of 16 over pages of 16: eight positions to a 128-lane row
    cache = jax.eval_shape(lambda: _pools(dec))
    assert cache["k"][0].shape == (N_PAGES, 2, 2, 128)
    assert cache["shortconv"][0].shape == (N_SLOTS, 128)
    full = lfm2_moe.decoder(lfm2_moe.Lfm2MoeConfig(experts_held=(0, 16)))
    assert (full.heads, full.kv_heads, full.head_dim) == (32, 8, 64)
    assert [i for i, k in enumerate(full.kinds) if k == "attention"] \
        == [2, 6, 10, 14, 18, 21]
    assert full.kv_layers == 6 and full.state_layers == 18
    assert full.state_shapes == {"shortconv": ((2 * 2048,), jnp.float32)}
    assert full.pair_slots == 4 * 22
    arena = jax.eval_shape(lambda: Paged.init(full, 8, 256))
    assert arena["k"][0].shape == (8, 8, 128, 128)     # lane-dense
    shapes = jax.eval_shape(
        lambda k: lfm2_moe.lfm2_init(
            lfm2_moe.Lfm2MoeConfig(experts_held=(0, 16)), k),
        jax.random.PRNGKey(0))
    # 22 x 16 experts of 11.01 M, two dense FFNs, 18 conv and 6 attention
    # mixers, the tied embedding, norms and routers: 4.464 B on the chip
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 4_464_393_664
    blocks = shapes["blocks"]
    assert all(("router" in b) == (i >= 2) for i, b in enumerate(blocks))
    assert blocks[0]["w1"].shape == (2048, 2 * 7168)
    assert blocks[2]["w1"].shape == (16, 2048, 2 * 1792)
    assert blocks[0]["w_in"].shape == (2048, 3 * 2048)
    assert blocks[0]["conv_w"].shape == (3, 2048)
    assert blocks[2]["wk"].shape == (2048, 8 * 64)
    assert blocks[2]["q_norm"].shape == (64,)


def test_chunked_prefill_then_decode_equals_the_reference(params):
    """Logits, not tokens.  Both sides are float32; they differ in the
    order of a few sums (the conv over [tail | window], a paged softmax, an
    expert's rows gathered into blocks): 1e-4 of the logits' spread, where
    leaving a term out moves them by a good part of the spread itself.  A
    prompt of 37 ends mid-chunk (16 + 16 + 5)."""
    dec = lfm2_moe.decoder(CFG)
    prompt = np.random.default_rng(0).integers(1, 96, size=37).tolist()
    _, seq, got = _serve_logits(dec, params, prompt, 6)
    want = np.asarray(reference.logits(params, SIZES,
                                       np.asarray(seq, np.int32)))
    want = want[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=1e-4 * want.std(), rtol=1e-3)


@pytest.mark.parametrize("n", [16, 17, 31, 32])
def test_a_prompt_may_end_anywhere_in_a_chunk(params, n):
    dec = lfm2_moe.decoder(CFG)
    prompt = np.random.default_rng(n).integers(1, 96, size=n).tolist()
    _, seq, got = _serve_logits(dec, params, prompt, 2)
    want = np.asarray(reference.logits(
        params, SIZES, np.asarray(seq, np.int32)))[n - 1:]
    np.testing.assert_allclose(got, want, atol=1e-4 * want.std(), rtol=1e-3)


@pytest.mark.parametrize("what", ["the_conv_left_out", "no_head_norms",
                                  "no_rope", "a_dense_layer_left_out",
                                  "the_bias_gates"])
def test_the_reference_sees_each_term_of_the_layer(params, what):
    """Each term the issue's equations name moves the reference's logits by
    a good part of their spread: a program that dropped it would not pass
    the comparison above."""
    toks = np.random.default_rng(1).integers(1, 96, size=24).astype(np.int32)
    sound = np.asarray(reference.logits(params, SIZES, toks))
    sizes, broken = dict(SIZES), jax.tree.map(lambda a: a, params)
    blocks = broken["blocks"]
    if what == "the_conv_left_out":       # only the current input's tap
        for b in blocks:
            if "conv_w" in b:
                b["conv_w"] = b["conv_w"].at[:-1].set(0.0)
    elif what == "no_head_norms":
        for b in blocks:
            if "q_norm" in b:
                b["q_norm"] = jnp.full_like(b["q_norm"], 3.0)
    elif what == "no_rope":
        sizes["rope_theta"] = 1e30        # every angle ~0
    elif what == "a_dense_layer_left_out":
        blocks[1] = dict(blocks[1], w2=blocks[1]["w2"] * 0.0)
    elif what == "the_bias_gates":
        for b in blocks:
            if "router_bias" in b:
                b["router_bias"] = b["router_bias"] + 0.5 * jnp.arange(8.0)
    moved = np.asarray(reference.logits(broken, sizes, toks))
    assert np.abs(moved - sound)[8:].max() > 0.1 * sound.std()


def test_a_fresh_row_starts_from_a_zero_tail_in_a_slot_that_was_used(params):
    dec = lfm2_moe.decoder(CFG)
    rng = np.random.default_rng(2)
    first, second = (rng.integers(1, 96, size=n).tolist() for n in (21, 13))
    cache, _, _ = _serve_logits(dec, params, first, 3)
    assert float(jnp.abs(cache["shortconv"][0][2]).max()) > 0  # left behind
    _, seq, got = _serve_logits(dec, params, second, 3, cache=cache)
    want = np.asarray(reference.logits(params, SIZES,
                                       np.asarray(seq, np.int32)))
    np.testing.assert_allclose(got, want[len(second) - 1:],
                               atol=1e-4 * want.std(), rtol=1e-3)


def test_padded_positions_and_dead_rows_leave_the_tail_bit_identical(params):
    blk = params["blocks"][0]
    rng = np.random.default_rng(3)
    carry = {"shortconv": jnp.asarray(rng.normal(size=(3, 2 * CFG.dim)),
                                      jnp.float32)}
    x = jnp.asarray(rng.normal(size=(3, PT, CFG.dim)), jnp.float32)
    lengths = jnp.asarray([PT, 3, 0])
    valid = jnp.arange(PT)[None, :] < lengths[:, None]
    _, after = lfm2_moe.shortconv_mixer(CFG, blk, x, carry, valid)
    # the row with nothing real
    np.testing.assert_array_equal(after["shortconv"][2],
                                  carry["shortconv"][2])
    # a row of 3 real positions: as if the window had ended there
    _, short = lfm2_moe.shortconv_mixer(
        CFG, blk, x[1:2, :3], {"shortconv": carry["shortconv"][1:2]},
        jnp.ones((1, 3), bool))
    np.testing.assert_array_equal(after["shortconv"][1],
                                  short["shortconv"][0])
    # a decode round: the dead row's tail as it was, the live rows' moved
    _, after = lfm2_moe.shortconv_mixer(CFG, blk, x[:, 0], carry,
                                        jnp.asarray([True, False, True]))
    np.testing.assert_array_equal(after["shortconv"][1],
                                  carry["shortconv"][1])
    assert not np.array_equal(after["shortconv"][0], carry["shortconv"][0])


def test_a_dead_row_of_a_round_touches_no_page_and_no_tail(params):
    dec = lfm2_moe.decoder(CFG)
    prompt = np.random.default_rng(6).integers(1, 96, size=20).tolist()
    cache, _ = _prefill(dec, _pools(dec), params, prompt, 1)
    toks, pos = np.full(N_SLOTS, 5, np.int32), np.full(N_SLOTS, 20, np.int32)
    after, _ = _decode(dec, cache, params, toks, pos, {1})
    for key in ("k", "v"):
        for old, new in zip(cache[key], after[key]):
            old, new = (np.asarray(plain_pages(a, dec.head_dim))
                        for a in (old, new))
            changed = np.flatnonzero(
                (old != new).reshape(N_PAGES, -1).any(axis=1))
            assert changed.tolist() == [1 * MAX_PAGES + 20 // PT]
    for old, new in zip(cache["shortconv"], after["shortconv"]):
        rows = np.flatnonzero((np.asarray(old) != np.asarray(new)).any(1))
        assert rows.tolist() == [1]


def test_the_two_shares_of_an_expert_layer_add_up_to_the_whole(params):
    """The share test: at 8 experts, the outputs of the shares [0, 4) and
    [4, 8) of one expert layer add up to the uncut reference's layer —
    nothing is computed alike on both chips of a pair, so nothing is
    counted once and nothing twice."""
    from easydist_tpu.models.experts import expert_ffn, sigmoid_route

    rng = np.random.default_rng(7)
    f = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    whole = dict(SIZES, num_experts=8, experts_held=[0, 8])
    blk = weights_lfm2.lfm2_params(whole, weights_lfm2.seed_key(9),
                                   dtype=jnp.float32)["blocks"][3]
    assert blk["w1"].shape[0] == 8
    c = dict(reference.constants(whole))
    want = np.asarray(reference._moe(f, blk, c, False))
    idx, gate = sigmoid_route(f, blk["router"], 2, 1.0, blk["router_bias"],
                              lfm2_moe.ROUTER_EPS)
    shares, pairs = [], 0
    for first in (0, 4):
        out, counters = expert_ffn(
            f, idx, gate, blk["w1"][first:first + 4],
            blk["w2"][first:first + 4], (first, 4), jnp.float32)
        shares.append(np.asarray(out))
        pairs += int(counters[0])
        # the reference's own share is the program's
        part = dict(blk, w1=blk["w1"][first:first + 4],
                    w2=blk["w2"][first:first + 4])
        np.testing.assert_allclose(
            out, reference._moe(f, part, dict(c, first=first), False),
            atol=1e-5)
    assert pairs == 40 * 2                   # every pair on one chip or the other
    assert all(np.abs(s).max() > 0.01 for s in shares)
    np.testing.assert_allclose(shares[0] + shares[1], want, atol=1e-5)


def test_the_router_chooses_by_score_plus_bias_and_gates_by_score(params):
    from easydist_tpu.models.experts import sigmoid_route

    blk = params["blocks"][2]
    f = jnp.asarray(np.random.default_rng(8).normal(size=(50, 64)),
                    jnp.float32)
    idx, gate = sigmoid_route(f, blk["router"], 2, 1.0, blk["router_bias"],
                              lfm2_moe.ROUTER_EPS)
    s = np.asarray(jax.nn.sigmoid(f @ blk["router"]))
    want = np.argsort(-(s + np.asarray(blk["router_bias"])), axis=1)[:, :2]
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want, 1))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(
        gate, chosen / (chosen.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    # the default normaliser is K-EXAONE's, unchanged
    _, default = sigmoid_route(f, blk["router"], 2, 1.0, blk["router_bias"])
    np.testing.assert_allclose(
        default, chosen / (chosen.sum(1, keepdims=True) + 1e-20), rtol=1e-6)


REFUSED = {   # what -> (the config that asks for it, the error names it)
    "the prefix trie": (dict(enable_prefix_cache=True), "prefix trie"),
    "speculation": (dict(speculate_k=2), "speculation"),
    "the host tier": (dict(enable_prefix_cache=True,
                           kv_host_tier_bytes=1 << 20), "host tier"),
    "the int8 arena": (dict(kv_quant_dtype="int8"), "int8 arena"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_model_with_conv_layers_refuses(params, what):
    base = dict(decode_buckets=(32,), max_decode_slots=2,
                prefill_chunk=16, enable_prefix_cache=False, speculate_k=0)
    asked, named = REFUSED[what]
    with pytest.raises(ValueError, match="state layers.*" + named):
        GenerationSession(params, model=lfm2_moe.decoder(CFG),
                          config=ServeConfig(**{**base, **asked}))
    GenerationSession(params, model=lfm2_moe.decoder(CFG),
                      config=ServeConfig(**base)).close()


def test_a_session_serves_more_requests_than_slots_and_counts_them(params):
    """Paged, two prefill rows, two slots reused by six requests: every
    served token is the argmax of the reference's full forward."""
    sess = GenerationSession(
        params, model=lfm2_moe.decoder(CFG), config=ServeConfig(
            decode_buckets=(64,), max_decode_slots=2, prefill_chunk=PT,
            prefill_batch=2, enable_prefix_cache=False, speculate_k=0))
    pool_arena = None
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 96, size=n).tolist(), m)
            for n, m in ((5, 4), (19, 6), (8, 3), (33, 5), (3, 7), (16, 9))]
    futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
    seen = set()
    while sess.step():
        gauges = sess.metrics.snapshot()["gauges"]
        if "shortconv_state_bytes" in gauges:
            seen.add(gauges["shortconv_state_bytes"])
            assert gauges["state_slots"] == 2
            assert gauges["state_slots_in_use"] <= 2
        pool_arena = next(iter(sess._pools.values())).arena
    for (prompt, _), fut in zip(reqs, futs):
        ids = fut.result(timeout=5)["ids"]
        want = np.asarray(reference.logits(
            params, SIZES, np.asarray(prompt + ids, np.int32)))
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(ids)]
        assert rows.argmax(-1).tolist() == ids
    # the session's arena is the lane-dense one
    assert pool_arena["k"][0].shape[2:] == (PT // 8, 128)
    # one [2 x 64] float32 tail a SLOT a conv layer, all run long
    assert seen == {2 * 4 * 2 * 64 * 4}
    snap = sess.metrics.snapshot()
    counters = snap["counters"]
    rounds = sum(m - 1 for _, m in reqs)
    assert counters["tokens_generated"] == rounds
    assert counters["shortconv_rows_updated"] == 4 * rounds
    assert counters["shortconv_chunk_positions"] == 4 * sum(
        len(p) for p, _ in reqs)
    # four expert layers x top 2: the slots a live row offers a round
    assert counters["moe_pair_slots"] \
        == 2 * 4 * 2 * counters["moe_rounds"]
    assert 0 < counters["moe_pairs_routed"] < counters["moe_pair_slots"]
    for other in ("delta_state_bytes", "selective_state_bytes"):
        assert other not in snap["gauges"]
    pool = next(iter(sess._pools.values()))
    assert pool.state.in_use == 0 == pool.pool.in_use
    sess.close()
