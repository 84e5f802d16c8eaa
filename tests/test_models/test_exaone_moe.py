"""K-EXAONE through `models/decoder.py`'s one loop at a tiny size — the
period [sliding, sliding, full, sliding] with a window of 8 (or 6: a ring
longer than its window), a dense FFN then 8 sigmoid-routed experts top-2 of
which 4 are held — against the plain reference (`chipbench/reference/`,
float32, one full forward under explicit masks, dense experts): chunked
prefill then decode through rings and pages, slots reused, the shares, the
selection bias, and what a session refuses for a model with rings."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_exaone
from chipbench.reference import exaone_moe as reference
from easydist_tpu.models import exaone_moe as em
from easydist_tpu.models.decoder import Paged, State, chunk, decode
from easydist_tpu.serve import GenerationSession, ServeConfig

SIZES = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=48, moe_intermediate_size=16, num_experts=4,
    router_experts=8, experts_held=[0, 4], num_experts_per_tok=2,
    num_shared_experts=1, n_group=1, topk_group=1, routed_scaling_factor=2.5,
    layer_types=["sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    sliding_window=8, sliding_windows=[8, 8, 0, 8], num_hidden_layers=4,
    vocab_size=96, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"})
CFG = em.ExaoneMoeConfig.tiny()
N_SLOTS, PT, N_PAGES, MAX_PAGES = 4, 8, 32, 8


def _sizes(window):
    return dict(SIZES, sliding_window=window,
                sliding_windows=[window, window, 0, window])


@pytest.fixture(scope="module")
def params():
    return weights_exaone.exaone_params(SIZES, weights_exaone.seed_key(3),
                                        dtype=jnp.float32)


def _pools(dec):
    return {**Paged.init(dec, N_PAGES, PT), **State.init(dec, N_SLOTS)}


def _table(rows):
    """Slot i owns pages 8i..8i+7; `rows` lists the slot of each row (None
    = no sequence)."""
    tbl = np.full((len(rows), MAX_PAGES), N_PAGES, np.int32)
    for r, slot in enumerate(rows):
        if slot is not None:
            tbl[r] = slot * MAX_PAGES + np.arange(MAX_PAGES)
    return jnp.asarray(tbl)


@functools.lru_cache(maxsize=None)
def _steps(dec):
    """The two steps as the session jits them: (chunk, decode)."""
    def chunk_step(cache, params, table, sl, toks, starts, lengths):
        pages, leaves = State.split(dec, cache)
        st = State(leaves, sl < N_SLOTS, sl, fresh=starts == 0)
        return chunk(dec, Paged(pages, table), params, toks, starts, lengths,
                     state=st)

    def decode_step(cache, params, table, alive, tokens, positions):
        pages, leaves = State.split(dec, cache)
        return decode(dec, Paged(pages, table), params, tokens, positions,
                      state=State(leaves, alive))

    return jax.jit(chunk_step), jax.jit(decode_step)


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
        sl = jnp.asarray([N_SLOTS if s is None else s for s in row_slots],
                         jnp.int32)
        cache, logits = _steps(dec)[0](
            cache, params, _table(row_slots), sl, jnp.asarray(toks),
            jnp.full((len(prompts),), start, jnp.int32),
            jnp.asarray([len(p) for p in prompts]))
        for r, p in enumerate(prompts):
            if start < len(p) <= start + c_len:
                last[r] = np.asarray(logits[r])
    return cache, last


def _decode(dec, cache, params, tokens, positions, live):
    """One decode round over all N_SLOTS rows (`live`: the slots that are
    sequences)."""
    alive = np.zeros((N_SLOTS,), bool)
    alive[list(live)] = True
    tbl = _table([i if i in live else None for i in range(N_SLOTS)])
    return _steps(dec)[1](cache, params, tbl, jnp.asarray(alive),
                          jnp.asarray(tokens), jnp.asarray(positions))


def _serve(dec, cache, params, prompts, slots, n_new):
    """Prefill the prompts together, then decode them together `n_new`
    greedy steps: ([tokens of each], [logits at each served position])."""
    cache, last = _prefill(dec, cache, params, prompts, slots)
    seqs = [list(p) for p in prompts]
    got = [[row] for row in last]
    for _ in range(n_new):
        toks, pos = np.zeros(N_SLOTS, np.int32), np.zeros(N_SLOTS, np.int32)
        for seq, slot, rows in zip(seqs, slots, got):
            seq.append(int(np.argmax(rows[-1])))
            toks[slot], pos[slot] = seq[-1], len(seq) - 1
        cache, logits = _decode(dec, cache, params, toks, pos, set(slots))
        for slot, rows in zip(slots, got):
            rows.append(np.asarray(logits[slot]))
    return cache, seqs, got


def _assert_is_the_reference(params, sizes, prompt, seq, got):
    """Logits, not tokens.  Both sides are float32; they differ in the
    order of sums (a ring in rotation and a paged softmax against one [T, T]
    softmax, grouped rows against dense experts): 2e-5 of the logits'
    spread, where a key too many or too few moves them by a tenth of it."""
    want = np.asarray(reference.logits(params, sizes,
                                       np.asarray(seq, np.int32)))
    want = want[len(prompt) - 1:]
    np.testing.assert_allclose(np.stack(got), want,
                               atol=2e-5 * want.std() + 1e-9, rtol=2e-4)
    assert (np.stack(got).argmax(-1) == want.argmax(-1)).all()


DECODERS = {w: em.decoder(dataclasses.replace(CFG, sliding_window=w))
            for w in (8, 6)}     # 6: a ring of 8 rows


@pytest.mark.parametrize("window", list(DECODERS))
@pytest.mark.parametrize("n_prompt", [5, 8, 16, 19, 29])
def test_chunked_prefill_then_decode_equals_the_reference(params, window,
                                                          n_prompt):
    """A prompt that ends inside its first chunk, on a chunk's boundary
    (one chunk, two) and past it, then decode until the rings have wrapped
    several times: every logit is the full forward's."""
    dec = DECODERS[window]
    prompt = np.random.default_rng(n_prompt).integers(
        1, 96, size=n_prompt).tolist()
    _, (seq,), (got,) = _serve(dec, _pools(dec), params, [prompt], [2], 30)
    assert len(seq) > n_prompt + 3 * 8
    _assert_is_the_reference(params, _sizes(window), prompt, seq, got)


def test_rows_of_unequal_length_share_a_batch(params):
    """Three prompts prefilled in one batch (the short ones' later chunks
    are dead rows) and decoded in one round each step."""
    dec = DECODERS[8]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 96, size=n).tolist() for n in (3, 21, 12)]
    _, seqs, got = _serve(dec, _pools(dec), params, prompts, [3, 0, 1], 12)
    for prompt, seq, rows in zip(prompts, seqs, got):
        _assert_is_the_reference(params, SIZES, prompt, seq, rows)


def test_a_reused_slot_does_not_see_its_earlier_tenant(params):
    """A long sequence fills slot 1's rings and pages; a second, short one
    takes the slot over.  Its logits are the reference's, bit for bit what
    they are in a slot nothing ever used."""
    dec = DECODERS[8]
    rng = np.random.default_rng(2)
    first = rng.integers(1, 96, size=27).tolist()
    second = rng.integers(1, 96, size=4).tolist()
    cache, _, _ = _serve(dec, _pools(dec), params, [first], [1], 10)
    assert all(np.asarray(leaf[1]).any() for leaf in cache["ring_k"])
    _, (seq,), (got,) = _serve(dec, cache, params, [second], [1], 6)
    _assert_is_the_reference(params, SIZES, second, seq, got)
    _, _, (clean,) = _serve(dec, _pools(dec), params, [second], [1], 6)
    np.testing.assert_array_equal(np.stack(got), np.stack(clean))


def test_a_dead_row_leaves_rings_and_pages_as_they_were(params):
    dec = DECODERS[8]
    prompt = np.random.default_rng(3).integers(1, 96, size=11).tolist()
    cache, _ = _prefill(dec, _pools(dec), params, [prompt], [2])
    before = jax.tree.map(np.asarray, cache)
    toks, pos = np.zeros(N_SLOTS, np.int32), np.zeros(N_SLOTS, np.int32)
    toks[0], pos[0] = 5, 3
    after, _ = _decode(dec, cache, params, toks, pos, {0})
    for key, leaves in before.items():
        for li, leaf in enumerate(leaves):
            if key.startswith("ring"):     # slot 0 wrote, slot 2 did not
                np.testing.assert_array_equal(np.asarray(after[key][li])[2],
                                              leaf[2])
                assert (np.asarray(after[key][li])[0] != leaf[0]).any()
            else:
                np.testing.assert_array_equal(
                    np.asarray(after[key][li])[2 * MAX_PAGES:],
                    leaf[2 * MAX_PAGES:])


# ------------------------------------------------------------ the experts


def _block(params, li=1):
    return dict(params["blocks"][li])


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Two chips hold 4 of the 8 experts each.  Their routed parts, plus
    the shared expert counted ONCE, are what the reference gives for the
    whole layer (8 held)."""
    key = weights_exaone.seed_key(11)
    whole_sizes = dict(SIZES, num_experts=8, experts_held=[0, 8])
    whole = weights_exaone.exaone_params(whole_sizes, key, dtype=jnp.float32)
    blk = _block(whole)
    u = jax.random.normal(jax.random.PRNGKey(5), (24, 32), jnp.float32)
    c = dict(reference.constants(whole_sizes))
    want = np.asarray(reference._moe(u, blk, c, False))

    idx, gate = em.route(CFG, blk, u)
    total, counted = np.zeros_like(want), 0
    for first in (0, 4):
        part, counters = em.expert_ffn(
            u, idx, gate, blk["w1"][first:first + 4],
            blk["w2"][first:first + 4], (first, 4), jnp.float32)
        total += np.asarray(part)
        counted += int(counters[0])
    assert counted == 24 * 2           # every choice landed on one chip
    total += np.asarray(em.glu(u, blk["shared_w1"], blk["shared_w2"],
                               jnp.float32))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)


def test_the_selection_bias_changes_choices_and_never_gates(params):
    blk = _block(params)
    u = jax.random.normal(jax.random.PRNGKey(6), (64, 32), jnp.float32)
    idx, gate = em.route(CFG, blk, u)
    unbiased, _ = em.route(CFG, dict(blk, router_bias=jnp.zeros((8,))), u)
    # the seed's bias (0.01 normal) flips some tokens' choices already ...
    assert (np.sort(idx, -1) != np.sort(unbiased, -1)).any()
    # ... and a bias that decides everything still weighs by the scores
    pushed = jnp.zeros((8,)).at[jnp.asarray([6, 7])].set(10.0)
    idx, gate = em.route(CFG, dict(blk, router_bias=pushed), u)
    assert (np.sort(idx, -1) == np.asarray([6, 7])).all()
    scores = np.asarray(jax.nn.sigmoid(u @ blk["router"]))
    chosen = np.take_along_axis(scores, np.asarray(idx), -1)
    np.testing.assert_allclose(
        gate, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gate).sum(-1), 2.5, rtol=1e-6)


def test_the_dense_layer_counts_nothing_and_the_others_every_pair(params):
    dec = em.decoder(CFG)
    blocks = dec.blocks(params)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 32), jnp.float32)
    valid = jnp.ones((2, 8), bool).at[1, 5:].set(False)
    _, none = dec.ffn(blocks[0], x, valid)
    assert none is None
    _, counters = dec.ffn(blocks[1], x, valid)
    idx, _ = em.route(CFG, blocks[1], x.reshape(-1, 32))
    held = np.asarray(idx < 4) & np.asarray(valid).reshape(-1, 1)
    assert int(counters[0]) == held.sum()


# ------------------------------------------------------------ the session


REFUSED = {   # what -> (the config that asks for it, the error names it)
    "the prefix trie and resume": (dict(enable_prefix_cache=True),
                                   "prefix trie.*resumed prefix"),
    "speculation": (dict(speculate_k=2), "speculation.*overwritten ring"),
    "the host tier": (dict(enable_prefix_cache=True,
                           kv_host_tier_bytes=1 << 20), "host tier"),
    "the int8 arena": (dict(kv_quant_dtype="int8"), "int8 arena"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_model_with_window_rings_refuses(params, what):
    base = dict(decode_buckets=(32,), max_decode_slots=2,
                prefill_chunk=8, enable_prefix_cache=False, speculate_k=0)
    asked, named = REFUSED[what]
    with pytest.raises(ValueError, match="window rings cannot be served "
                                         "with .*" + named + ".*; set "):
        GenerationSession(params, model=em.decoder(CFG),
                          config=ServeConfig(**{**base, **asked}))
    GenerationSession(params, model=em.decoder(CFG),
                      config=ServeConfig(**base)).close()


def test_a_session_serves_it_and_the_ids_are_the_references(params):
    sess = GenerationSession(params, model=em.decoder(CFG), config=ServeConfig(
        decode_buckets=(64,), max_decode_slots=N_SLOTS,
        prefill_chunk=PT, prefill_batch=2, enable_prefix_cache=False,
        speculate_k=0))
    rng = np.random.default_rng(4)
    reqs = [(rng.integers(1, 96, size=n).tolist(), m)
            for n, m in ((5, 4), (19, 6), (8, 3), (30, 25), (3, 7), (16, 9),
                         (24, 12), (41, 10))]
    futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
    ring_bytes = set()
    while not all(fut.done() for fut in futs):
        sess.step()
        gauges = sess.metrics.snapshot()["gauges"]
        if "window_ring_bytes" in gauges:
            ring_bytes.add(gauges["window_ring_bytes"])
    for (prompt, _), fut in zip(reqs, futs):
        ids = fut.result(timeout=5)["ids"]
        want = np.asarray(reference.logits(
            params, SIZES, np.asarray(prompt + ids, np.int32)))
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(ids)]
        assert ids == rows.argmax(-1).tolist()
    # three rings of 8 rows x 2 KV heads x 8 x float32, K and V, 4 slots:
    # one number from the first round to the last, whatever the lengths
    assert ring_bytes == {3 * 2 * N_SLOTS * 2 * 8 * 8 * 4}
    pool = next(iter(sess._pools.values()))
    assert pool.state.in_use == 0 == pool.pool.in_use
    assert sess.metrics.counter("moe_rounds") > 0
    assert sess.metrics.counter("moe_prefill_calls") > 0
    sess.close()
