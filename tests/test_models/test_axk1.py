"""A.X-K1 as `models/decoder.py` serves it, against the benchmark's plain
reference (`chipbench/reference/axk1.py`: the EXPANDED form, float32, no
cache): chunked prefill then decode through the latent adapter, the absorbed
attention against the expanded one for a single layer, the published
`rope_scaling`'s numbers, the sixteen shares of an expert layer, and a
`GenerationSession` on the tiny model — the trie off and on, a latent page
exported and imported, the expert counters without a `State`, the refusals,
one XLA compile a program."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_axk1
from chipbench.reference import axk1 as reference
from easydist_tpu.jaxfront import make_device_mesh
from easydist_tpu.models import axk1
from easydist_tpu.models.decoder import Latent, chunk, decode
from easydist_tpu.models.experts import expert_ffn, glu, sigmoid_route
from easydist_tpu.runtime import spans
from easydist_tpu.serve import GenerationSession, ServeConfig

SIZES = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
    n_routed_experts=4, router_experts=8, experts_held=[0, 4],
    num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=1,
    moe_layer_freq=1, topk_method="none", routed_scaling_factor=2.5,
    num_hidden_layers=3, vocab_size=96, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                  "mscale_all_dim": 1, "type": "yarn",
                  "original_max_position_embeddings": 16})
CFG = axk1.AxK1Config.tiny()
DEC = axk1.decoder(CFG)
PT, N_PAGES, MAX_PAGES = 8, 24, 8
SENTINEL = N_PAGES


@pytest.fixture(scope="module")
def params():
    return weights_axk1.axk1_params(SIZES, weights_axk1.seed_key(4),
                                    dtype=jnp.float32)


def _reference(params, tokens):
    return np.asarray(reference.logits(params, SIZES, jnp.asarray(tokens)))


def _tokens(seed, n):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         CFG.vocab))


def _serve(params, prompts, n_new, tables):
    """Prefill every row in chunks of a page (rows advance together, a row
    past its prompt keeps its last table row: its writes land past its
    length and nobody reads them), then `n_new` decode steps; returns each
    row's logits at its last prompt position and after each new token, the
    tokens fed being `feeds[r]`."""
    rows = len(prompts)
    arena = Latent.init(DEC, N_PAGES, PT)
    table = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray([max(len(p) - n_new, 0) for p in prompts],
                          jnp.int32)
    first = [None] * rows
    for start in range(0, int(lengths.max()), PT):
        toks = np.zeros((rows, PT), np.int32)
        live = np.asarray(lengths) > start
        for r, p in enumerate(prompts):
            seg = p[start:min(start + PT, int(lengths[r]))]
            toks[r, :len(seg)] = seg
        tbl = jnp.where(jnp.asarray(live)[:, None], table, SENTINEL)
        arena, lg = chunk(DEC, Latent(arena, tbl), params, jnp.asarray(toks),
                          jnp.full((rows,), start, jnp.int32),
                          jnp.maximum(lengths, 1))
        for r in range(rows):
            if live[r] and start + PT >= int(lengths[r]):
                first[r] = np.asarray(lg[r])
    out = [[f] for f in first]
    for i in range(n_new):
        pos = lengths + i
        tok = jnp.asarray([p[int(lengths[r]) + i] if lengths[r] else 0
                           for r, p in enumerate(prompts)], jnp.int32)
        tbl = jnp.where((lengths > 0)[:, None], table, SENTINEL)
        arena, lg = decode(DEC, Latent(arena, tbl), params, tok, pos)
        for r in range(rows):
            out[r].append(np.asarray(lg[r]))
    return out, arena


CASES = {
    "one_row_ends_mid_page": [29],
    "a_prompt_of_whole_pages": [24],
    "shorter_than_a_page": [11],
    "rows_of_unequal_length": [37, 13],
    "a_row_without_a_sequence": [21, 0],
}


@pytest.mark.parametrize("case", list(CASES))
def test_chunked_prefill_then_decode_equals_the_reference(params, case):
    """The timed path's two programs at a tiny size, float32: every logit
    they hand on is the reference's full forward's to 1e-4 — rows of
    unequal length in one batch, a prompt that ends mid-page, a row that
    holds no sequence (extent 0, every table entry the sentinel)."""
    n_new = 6
    totals = CASES[case]
    seqs = [_tokens(7 + r, n) if n else np.zeros((0,), np.int32)
            for r, n in enumerate(totals)]
    tables = [list(range(1 + r * MAX_PAGES, 1 + r * MAX_PAGES + MAX_PAGES))
              if n else [SENTINEL] * MAX_PAGES
              for r, n in enumerate(totals)]
    # unmapped windows past what a row needs are sentinels too
    for r, n in enumerate(totals):
        for j in range(-(-n // PT), MAX_PAGES):
            tables[r][j] = SENTINEL
    got, _ = _serve(params, [s.tolist() for s in seqs], n_new, tables)
    for r, seq in enumerate(seqs):
        if not len(seq):
            continue
        want = _reference(params, seq)
        n_prompt = len(seq) - n_new
        for i, lg in enumerate(got[r]):
            np.testing.assert_allclose(lg, want[n_prompt - 1 + i], atol=1e-4,
                                       rtol=1e-4, err_msg=f"row {r} +{i}")


def test_a_dead_row_writes_nothing(params):
    seq = _tokens(3, 20)
    tables = [[2, 5, 9] + [SENTINEL] * 5, [SENTINEL] * MAX_PAGES]
    _, arena = _serve(params, [seq.tolist(), []], 4, tables)
    for leaf in arena["latent"]:
        leaf = np.asarray(leaf)
        assert leaf.shape == (N_PAGES, PT, 128)      # 24 values, one tile
        assert not leaf[:, :, 24:].any()             # the padding stays 0
        touched = {p for p in range(N_PAGES) if leaf[p].any()}
        assert touched == {2, 5, 9}


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_absorbed_attention_is_the_expanded_one(params, step):
    """One layer: what `qkv` -> the latent adapter -> `attn_out` add to the
    stream is the reference's expanded attention (every head's keys and
    values re-made from the latent) of the same normed input."""
    blk = params["blocks"][1]
    t = 21
    x = jax.random.normal(jax.random.PRNGKey(2), (t, CFG.dim), jnp.float32)
    c = dict(reference.constants(SIZES))
    u = reference._rmsnorm(x, blk["norm_attn"], c["eps"])
    want = np.asarray(reference._attention(u, blk, c, False))

    arena = {"latent": (Latent.init(DEC, N_PAGES, PT)["latent"][0],)}
    table = jnp.asarray([[4, 1, 7] + [SENTINEL] * 5], jnp.int32)
    got = np.zeros_like(want)
    if step == "chunk":
        for start in range(0, t, PT):
            n = min(PT, t - start)
            xs = jnp.zeros((1, PT, CFG.dim)).at[0, :n].set(x[start:start + n])
            kv = Latent(arena, table)
            pos = kv.seek(jnp.asarray([start]), PT, aligned=True)
            q, row, _ = DEC.qkv(blk, xs, pos)
            kv.write(row)
            att = kv.attend(DEC, q, pos)                # [1, h, PT, kv_rank]
            out = DEC.attn_out(blk, jnp.zeros_like(xs),
                               att.transpose(0, 2, 1, 3).reshape(1, PT, -1))
            got[start:start + n] = np.asarray(out[0, :n])
            arena = kv.cache()
    else:
        for p in range(t):
            kv = Latent(arena, table)
            pos = kv.seek(jnp.asarray([p]))
            q, row, _ = DEC.qkv(blk, x[p:p + 1], pos)
            kv.write(row)
            att = kv.attend(DEC, q, pos)                # [1, h, kv_rank]
            got[p] = np.asarray(DEC.attn_out(
                blk, jnp.zeros((1, CFG.dim)), att.reshape(1, -1))[0])
            arena = kv.cache()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_the_published_rope_scaling_gives_these_numbers():
    cfg = axk1.AxK1Config()
    assert axk1.attention_scale(cfg) == pytest.approx(0.130861, abs=5e-7)
    m = 0.1 * np.log(32.0) + 1.0
    assert m == pytest.approx(1.34657, abs=5e-6)
    assert axk1.attention_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    inv_freq, factor = axk1.rope_frequencies(cfg)
    assert factor == 1.0                     # m(mscale) / m(mscale_all_dim)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    ratio = np.asarray(inv_freq) / plain
    np.testing.assert_allclose(ratio[:11], 1.0, rtol=1e-6)     # dims 0..10
    np.testing.assert_allclose(ratio[23:], 1 / 32.0, rtol=1e-6)  # 23..31
    assert (np.diff(ratio[10:24]) < 0).all()     # the ramp runs 10..23
    np.testing.assert_allclose(ratio[10:24],
                               1 - (31 / 32) * (np.arange(14) / 13.0),
                               rtol=1e-5)
    # and they are the reference's own, from the config's dict
    ref_freq, ref_factor, ref_scale = reference.yarn(dict(
        reference.constants(dict(
            SIZES, num_attention_heads=64, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rope_scaling=dict(SIZES["rope_scaling"],
                              original_max_position_embeddings=4096)))))
    np.testing.assert_allclose(np.asarray(ref_freq), np.asarray(inv_freq),
                               rtol=1e-6)
    assert ref_factor == 1.0
    assert ref_scale == pytest.approx(axk1.attention_scale(cfg))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold 2 of a layer's 32 experts each.  Their routed
    parts, plus the shared expert counted ONCE, are what the reference gives
    for the whole layer (32 held)."""
    whole = dict(SIZES, n_routed_experts=32, router_experts=32,
                 experts_held=[0, 32], num_experts_per_tok=4)
    blk = weights_axk1.axk1_params(whole, weights_axk1.seed_key(11),
                                   dtype=jnp.float32)["blocks"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (24, 32), jnp.float32)
    want = np.asarray(reference._moe(u, blk, dict(
        reference.constants(whole)), False))
    idx, gate = sigmoid_route(u, blk["router"], 4, 2.5)
    total, counted = np.zeros_like(want), 0
    for first in range(0, 32, 2):
        part, counters = expert_ffn(
            u, idx, gate, blk["w1"][first:first + 2],
            blk["w2"][first:first + 2], (first, 2), jnp.float32)
        total += np.asarray(part)
        counted += int(counters[0])
    assert counted == 24 * 4           # every choice landed on one chip
    total += np.asarray(glu(u, blk["shared_w1"], blk["shared_w2"],
                            jnp.float32))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)


# -------------------------------------------------------------- the session


def _session(params, **kw):
    base = dict(decode_buckets=(64,), max_decode_slots=4,
                prefill_chunk=8, prefill_batch=2, kv_arena_pages=40,
                enable_prefix_cache=False, speculate_k=0)
    base.update(kw)
    mesh = make_device_mesh((1,), ("d",), devices=jax.devices()[:1])
    return GenerationSession(params, model=DEC, config=ServeConfig(**base),
                             mesh=mesh)


def _prompts():
    rng = np.random.default_rng(0)
    shared = rng.integers(1, CFG.vocab, size=17).tolist()
    return [shared + rng.integers(1, CFG.vocab, size=n).tolist()
            for n in (3, 12, 7)] + [rng.integers(1, CFG.vocab,
                                                 size=5).tolist()]


def _greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(_reference(params, seq)[-1].argmax()))
    return seq[len(prompt):]


def _run(sess, prompts, n=6):
    """Two waves, so that the second finds the first's pages in the trie."""
    futs = [sess.submit(p, max_new_tokens=n) for p in prompts[:2]]
    sess.run_until_drained()
    futs += [sess.submit(p, max_new_tokens=n) for p in prompts[2:]]
    sess.run_until_drained()
    return [f.result(timeout=5)["ids"] for f in futs]


@pytest.fixture(scope="module")
def served(params):
    spans.clear()
    sess = _session(params)
    ids = _run(sess, _prompts())
    return ids, sess.metrics.snapshot(), spans.snapshot()["counters"], sess


def test_a_session_serves_it_and_the_ids_are_the_references(params, served):
    ids, snap, counters, _ = served
    for prompt, got in zip(_prompts(), ids):
        assert got == _greedy(params, prompt, 6)
    # one XLA compile a program, and no program but the two
    assert {k: v for k, v in counters.items()
            if k.startswith("xla_compiles")} == {
        "xla_compiles{fn=_prefill_chunk_paged}": 1,
        "xla_compiles{fn=_decode_paged}": 1}


def test_the_expert_counters_are_recorded_without_a_state(served):
    _, snap, _, sess = served
    c = snap["counters"]
    assert not DEC.per_sequence and DEC.counts
    assert c["moe_rounds"] == c["decode_steps"] > 0
    assert c["moe_prefill_calls"] == c["prefill_chunks"] > 0
    # 2 expert layers, top-2 of 8 with 4 held: some pairs, never all
    assert 0 < c["moe_pairs_routed"] < 2 * 2 * c["tokens_generated"]
    assert 0 < c["moe_prefill_pairs_routed"] < 2 * 2 * c[
        "prefill_tokens_real"]
    assert c["prefill_pages_walked"] < c["prefill_pages_bucket"]
    # every real prompt position sees itself and what is before it
    want = sum(n * (n + 1) // 2 for n in map(len, _prompts()))
    assert c["prefill_attn_pairs"] == want
    # one row a position a layer, stored in whole tiles, whatever the heads
    assert snap["gauges"]["latent_cache_bytes"] == 40 * 8 * 128 * 4 * 3


def test_the_pair_slots_a_program_offered_are_counted_on_the_host(served):
    """`moe_pair_slots` / `moe_prefill_pair_slots`: the rows of a program x
    top_k x its expert layers — the axis the parent's combine walked (PR
    43); `moe_pairs_routed` over it is the share for experts held HERE."""
    c = served[1]["counters"]
    assert DEC.pair_slots == 2 * 2        # top-2, two expert layers
    assert c["moe_pair_slots"] == c["moe_rounds"] * 4 * DEC.pair_slots  # slots
    assert c["moe_prefill_pair_slots"] \
        == c["prefill_tokens_padded"] * DEC.pair_slots
    assert 0 < c["moe_pairs_routed"] < c["moe_pair_slots"]
    assert 0 < c["moe_prefill_pairs_routed"] < c["moe_prefill_pair_slots"]


def test_the_trie_remaps_a_shared_prefix_and_changes_no_token(params, served):
    ids, _, _, _ = served
    sess = _session(params, enable_prefix_cache=True,
                    prefix_cache_bytes=1 << 20)
    assert _run(sess, _prompts()) == ids
    c = sess.metrics.snapshot()["counters"]
    assert c["prefix_tokens_reused"] == 16     # two whole pages, re-mapped
    assert c["prefill_tokens_real"] == sum(map(len, _prompts())) - 16


def test_speculation_commits_the_same_tokens(params, served):
    """The verify program comes with the chunk kernel: a k + 1 wide chunk of
    queries through `latent_chunk_attention`."""
    ids, _, _, _ = served
    assert _run(_session(params, speculate_k=2), _prompts()) == ids


def test_a_latent_page_round_trips_through_export_and_import(params, served):
    sess = _session(params)
    fut = sess.submit(list(range(1, 20)), max_new_tokens=2)
    sess.run_until_drained()
    assert len(fut.result(timeout=5)["ids"]) == 2
    pool = next(iter(sess._pools.values()))
    before = jax.tree.map(np.asarray, pool.arena)
    assert sorted(before) == ["latent"] and len(before["latent"]) == 3
    used = [p for p in range(pool.pool.n_pages) if before["latent"][0][p].any()]
    empty = [p for p in range(pool.pool.n_pages)
             if not any(leaf[p].any() for leaf in before["latent"])]
    src, dst = used[0], empty[0]
    page = sess._paged_c("export")(pool.arena, jnp.asarray(src, jnp.int32))
    assert page["latent"].shape == (3, 8, 128)   # [layers, page_tokens, width]
    np.testing.assert_array_equal(
        np.asarray(page["latent"]),
        np.stack([leaf[src] for leaf in before["latent"]]))
    pool.arena = sess._paged_c("import")(pool.arena, page,
                                         jnp.asarray(dst, jnp.int32))
    again = sess._paged_c("export")(pool.arena, jnp.asarray(dst, jnp.int32))
    np.testing.assert_array_equal(np.asarray(again["latent"]),
                                  np.asarray(page["latent"]))
    for li, leaf in enumerate(pool.arena["latent"]):
        np.testing.assert_array_equal(
            np.delete(np.asarray(leaf), dst, axis=0),
            np.delete(before["latent"][li], dst, axis=0))
    assert pool.page_bytes == pool.model_page_bytes == 3 * 8 * 128 * 4


REFUSED = {
    "the int8 arena": (dict(kv_quant_dtype="int8"), "a latent row has none"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_model_with_latent_attention_refuses(params, what):
    kw, reason = REFUSED[what]
    with pytest.raises(ValueError, match="latent attention cannot be "
                                         "served with") as e:
        _session(params, **kw)
    assert reason in str(e.value) and "; set " in str(e.value)


@pytest.mark.parametrize("devices", [1, 2])
def test_the_kernels_serve_it_too(params, served, monkeypatch, devices):
    """The same session with both latent kernels forced on (the Pallas
    interpreter here): the same tokens, ONE kernel built a signature for
    the three layers, and on a mesh of several devices each kernel is
    counted by its name where the programs are emitted, whole on every
    device (a latent leaf has no heads to split)."""
    from easydist_tpu import config as edconfig

    ids, _, _, _ = served
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(edconfig, "prefill_attention_backend", "paged")
    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    fa._paged_call.cache_clear()
    spans.clear()
    # another shape than `served`'s: the programs are traced again
    mesh = make_device_mesh((devices,), ("tp",),
                            devices=jax.devices()[:devices])
    sess = GenerationSession(params, model=DEC, mesh=mesh, config=ServeConfig(
        decode_buckets=(64,), max_decode_slots=3 + devices,
        prefill_chunk=8, prefill_batch=2, kv_arena_pages=40,
        enable_prefix_cache=False, speculate_k=0))
    assert _run(sess, _prompts()) == ids
    assert fa._paged_call.cache_info().misses == 2
    if devices > 1:
        counters = spans.snapshot()["counters"]
        assert counters["pallas_calls{kernel=latent_decode,row_shards=1}"] == 3
        assert counters["pallas_calls{kernel=latent_chunk,row_shards=1}"] == 3
