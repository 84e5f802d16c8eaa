"""The Olmo Hybrid additions of the benchmark: the plain reference against
an even plainer one written here (a loop over positions and heads, float64),
the seeded weights, the fp8 control, the configuration file against the
catalog's numbers, `kernel_costs_delta` against counts worked by hand, the
five new readers on a hand-made run, what `BENCHMARK.json` says of them, the
traffic, and the sample the check draws.  (The reference imports nothing of
the program; `tests/test_models/test_olmo_hybrid.py` holds the program to
it.)"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, kernel_costs_delta, weights_olmo
from chipbench.reference import olmo_hybrid as reference
from chipbench.runners import (serve_delta, serve_hybrid, serve_latent,
                               serve_window)

CELL = "serve-olmohybrid-longanswer-1chip"
AXK1 = "serve-axk1-longdoc-1chip"
MISTRAL = "serve-mistral7b-chat-1chip"
MINE = ("delta_decode_roofline", "delta_update_share_pct",
        "delta_decode_step_device_ms", "delta_prefill_chunk_device_ms",
        "delta_chunk_us_per_position")
LATENT_FIVE = ("latent_decode_roofline", "latent_chunk_roofline",
               "latent_attn_share_pct", "latent_decode_step_device_ms",
               "latent_prefill_chunk_device_ms")
BENCH = contract.load_benchmark()
TINY = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=48, num_hidden_layers=4,
    layer_types=["linear_attention", "linear_attention", "full_attention",
                 "linear_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, vocab_size=96, rms_norm_eps=1e-6,
    tie_word_embeddings=False, attention_bias=False,
    rope_parameters={"rope_theta": None})
with open(os.path.join(contract.ROOT, "chipbench", "configs",
                       "olmo-hybrid-7b.json")) as f:
    SIZES = json.load(f)


def _reader(name):
    path = os.path.join(contract.ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(contract.ROOT, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def params():
    return weights_olmo.olmo_params(TINY, weights_olmo.seed_key(4),
                                    dtype=jnp.float32)


def test_the_same_seed_makes_the_same_weights_and_the_tree_the_model_reads():
    a, b, c = (weights_olmo.olmo_params(
        TINY, weights_olmo.seed_key(s), dtype=jnp.float32)
        for s in (7, 7, 2 ** 31 + 5))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a["wte"], c["wte"])
    delta, full = a["blocks"][0], a["blocks"][2]
    assert "w_qkv" not in full and "wq" not in delta
    assert delta["w_qkv"].shape == (32, 4 * (8 + 8 + 16))
    assert delta["conv_w"].shape == (4, 128) and "conv_b" not in delta
    assert delta["w_ab"].shape == (32, 8) and delta["a_log"].shape == (4,)
    assert delta["a_log"].dtype == delta["dt_bias"].dtype == jnp.float32
    assert delta["w_gate"].shape == (32, 64)
    assert delta["norm_gate"].shape == (16,)
    assert delta["w_out"].shape == (64, 32)
    assert full["wq"].shape == full["wo"].shape == (32, 32)
    assert full["q_norm"].shape == full["k_norm"].shape == (32,)  # whole
    for blk in (delta, full):
        assert blk["w1"].shape == (32, 96) and blk["w2"].shape == (48, 32)
    assert a["head"].shape == a["wte"].shape == (96, 32)
    assert not np.array_equal(a["head"], a["wte"])          # untied
    # the decay's own part: exp(a_log) in 1..16, softplus(dt_bias) 1e-3..1e-1
    assert 1.0 <= float(jnp.exp(delta["a_log"]).min()) \
        and float(jnp.exp(delta["a_log"]).max()) <= 16.0
    dt = jax.nn.softplus(delta["dt_bias"])
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 1e-1 + 1e-6
    for bad in (dict(linear_num_key_heads=2), dict(tie_word_embeddings=True),
                dict(rope_parameters={"rope_theta": 500000.0}),
                dict(num_attention_heads=5)):
        with pytest.raises(ValueError, match="sizes disagree"):
            weights_olmo.dims(dict(TINY, **bad))


def _by_position(params, sizes, tokens, beta_max=2.0, gate=True, q_scale=True):
    """ISSUE 41's equations a position and a head at a time, in float64
    numpy: nothing of `reference` but its weights' layout."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    h, dk, dv = 4, 8, 16
    n_q, hd, eps = 4, 8, sizes["rms_norm_eps"]

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def unit(x):
        return x / np.sqrt((x * x).sum() + eps)

    def silu(x):
        return x / (1 + np.exp(-x))

    x = p["wte"][np.asarray(tokens)]
    t = len(x)
    for kind, blk in zip(sizes["layer_types"], p["blocks"]):
        out = np.zeros((t, 32))
        if kind == "full_attention":
            q = norm(x @ blk["wq"], blk["q_norm"]).reshape(t, n_q, hd)
            k = norm(x @ blk["wk"], blk["k_norm"]).reshape(t, n_q, hd)
            v = (x @ blk["wv"]).reshape(t, n_q, hd)
            att = np.zeros((t, n_q, hd))
            for i in range(t):
                for head in range(n_q):
                    s = k[:i + 1, head] @ q[i, head] * hd ** -0.5
                    w = np.exp(s - s.max())
                    att[i, head] = (w / w.sum()) @ v[:i + 1, head]
            out = att.reshape(t, -1) @ blk["wo"]
        else:
            u = x @ blk["w_qkv"]
            ab = x @ blk["w_ab"]
            gates = silu(x @ blk["w_gate"]).reshape(t, h, dv)
            state = np.zeros((h, dv, dk))
            y = np.zeros((t, h, dv))
            for i in range(t):
                c = sum(blk["conv_w"][j] * u[i - 3 + j] for j in range(4)
                        if i - 3 + j >= 0)
                c = silu(c)
                alpha = np.exp(-np.exp(blk["a_log"]) * np.log1p(
                    np.exp(ab[i, :h] + blk["dt_bias"])))
                beta = beta_max / (1 + np.exp(-ab[i, h:]))
                for head in range(h):
                    q = unit(c[head * dk:(head + 1) * dk]) \
                        * (dk ** -0.5 if q_scale else 1.0)
                    k = unit(c[h * dk + head * dk:h * dk + (head + 1) * dk])
                    v = c[2 * h * dk + head * dv:2 * h * dk + (head + 1) * dv]
                    s = alpha[head] * state[head]
                    s = s @ (np.eye(dk) - beta[head] * np.outer(k, k)) \
                        + beta[head] * np.outer(v, k)
                    state[head] = s
                    o = norm(s @ q, blk["norm_gate"])
                    y[i, head] = o * gates[i, head] if gate else o
            out = y.reshape(t, -1) @ blk["w_out"]
        x = x + norm(out, blk["norm_attn"])
        ab = x @ blk["w1"]
        x = x + norm((silu(ab[:, :48]) * ab[:, 48:]) @ blk["w2"],
                     blk["norm_ffn"])
    return norm(x, p["norm_f"]) @ p["head"].T


TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (27,), 1, 96))


def test_the_reference_is_the_equations_a_position_at_a_time(params):
    got = np.asarray(reference.logits(params, TINY, TOKENS))
    want = _by_position(params, TINY, TOKENS)
    assert 0.5 < want.std() < 2.0             # a spread a token is read from
    np.testing.assert_allclose(got, want, atol=2e-4 * want.std(), rtol=2e-3)
    rows = np.asarray(reference.logits(params, TINY, TOKENS, rows=[3, 26]))
    np.testing.assert_allclose(rows, got[[3, 26]], atol=1e-6)


@pytest.mark.parametrize("what", [dict(beta_max=1.0), dict(gate=False),
                                  dict(q_scale=False)],
                         ids=["beta_one_sigmoid", "no_output_gate",
                              "q_not_scaled"])
def test_each_assumption_is_seen_by_the_logits(params, what):
    got = np.asarray(reference.logits(params, TINY, TOKENS))
    other = _by_position(params, TINY, TOKENS, **what)
    assert np.abs(got - other)[4:].max() > 0.05 * got.std()


def test_the_fp8_control_moves_the_logits_and_bf16_barely_does(params):
    sound = np.asarray(reference.logits(params, TINY, TOKENS))
    low = np.asarray(reference.logits(params, TINY, TOKENS, quant=True))
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    bf16 = np.asarray(reference.logits(half, TINY, TOKENS))
    assert np.abs(low - sound).mean() > 3 * np.abs(bf16 - sound).mean() > 0


def test_the_bf16_recurrence_control_is_the_stated_precision_with_the_state_a_step_below(params):
    """The second control: matmul operands in bfloat16 as the configuration
    states them, and the conv, the decay, beta and the state kept in
    bfloat16 where it states float32 — a state leaf of half the bytes.  It
    moves the logits by less than fp8 operands do and by more than the
    stated precision alone does; on a model without delta-rule layers it IS
    the stated precision."""
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    sound = np.asarray(reference.logits(half, TINY, TOKENS))
    fp8 = np.asarray(reference.logits(half, TINY, TOKENS,
                                      quant="fp8_operands"))
    low = np.asarray(reference.logits(half, TINY, TOKENS,
                                      quant="bf16_recurrence"))
    np.testing.assert_array_equal(
        fp8, reference.logits(half, TINY, TOKENS, quant=True))
    assert np.abs(fp8 - sound).mean() > np.abs(low - sound).mean() > 1e-4
    # the matmuls' operands alone, rounded as the program rounds them
    stated = dict(TINY, layer_types=["full_attention"] * 4)
    full = dict(half, blocks=[half["blocks"][2]] * 4)
    operands = np.abs(np.asarray(reference.logits(
        full, stated, TOKENS, quant="bf16_recurrence"))
        - np.asarray(reference.logits(full, stated, TOKENS))).mean()
    assert 0 < operands < np.abs(low - sound).mean()
    with pytest.raises(ValueError, match="no such control"):
        reference.logits(params, TINY, TOKENS, quant="fp4")


def test_the_config_file_is_the_catalogs_with_the_cut_written_out():
    d = weights_olmo.dims(SIZES)
    assert (d["hidden"], d["q"], d["kv"], d["hd"]) == (3840, 30, 30, 128)
    assert (d["heads"], d["dk"], d["dv"], d["taps"]) == (30, 96, 192, 4)
    assert (d["ffn"], d["vocab"]) == (11008, 100352)
    assert d["kinds"] == ("linear_attention",) * 3 + ("full_attention",) \
        + d["kinds"][4:] and len(d["kinds"]) == 16
    assert d["kinds"].count("full_attention") == 4
    assert SIZES["linear_allow_neg_eigval"] is True
    assert SIZES["rope_parameters"] == {"rope_theta": None}
    assert SIZES["published"]["num_hidden_layers"] == 32
    entry = next(c for c in BENCH["configs"] if c["name"] == "olmo-hybrid-7b")
    assert set(SIZES["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types"}
    assert entry["source"] == SIZES["source"]
    assert all(any(a.startswith(f"({x})") for a in SIZES["assumed"])
               for x in "abcdef")
    assert all("other reading" in a for a in SIZES["assumed"]
               if a[:3] in ("(a)", "(b)", "(c)", "(d)", "(e)", "(f)"))
    for word in ("two pipeline stages", "sixteen", "stage 0"):
        assert word in SIZES["deployment"], word
    # the catalog's row, key for key, but for the two that are reduced
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["source_url"] == SIZES["source"]
        for key, value in row["config"].items():
            if key not in SIZES["reduced"]:
                assert SIZES[key] == value, key
        assert SIZES["layer_types"] == row["config"]["layer_types"][:16]
    # bf16 bytes of what the file describes: 8.20 GB of weights
    ffn = 3 * 3840 * 11008
    delta = 3840 * 11520 + 4 * 11520 + 3840 * 60 + 60 + 3840 * 5760 + 192 \
        + 5760 * 3840 + ffn + 2 * 3840
    full = 4 * 3840 * 3840 + 2 * 3840 + ffn + 2 * 3840
    assert 215.4e6 < delta < 215.6e6 and 185.7e6 < full < 185.9e6
    total = 12 * delta + 4 * full + 2 * 100352 * 3840 + 3840
    assert 8.19e9 < 2 * total < 8.21e9
    shapes = jax.eval_shape(lambda k: weights_olmo.olmo_params(SIZES, k),
                            jax.random.PRNGKey(0))
    # a_log and dt_bias are float32: 30 x 2 x 12 values at four bytes
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) \
        == 2 * total + 2 * 12 * 60


def test_kernel_costs_delta_by_hand():
    s = SIZES
    assert kernel_costs_delta.state_layers(s) == 12
    # one sequence, one layer: 30 heads of a 96 x 192 float32 matrix
    assert kernel_costs_delta.state_bytes(s) == 30 * 96 * 192 * 4 == 2_211_840
    assert kernel_costs_delta.conv_tail_bytes(s) == 3 * 11520 * 4
    # the cell's pool: 40 slots, twelve layers
    assert kernel_costs_delta.stored_state_bytes(40, s) == 1_061_683_200
    # a round of 28 live rows updates 28 x 12 states: each read and written,
    # and q, k (96), v, o (192), the decay and beta a head beside it
    rows = 28 * 12
    assert kernel_costs_delta.update_bytes(rows, s) \
        == rows * (2 * 2_211_840 + 30 * (2 * 96 + 2 * 192 + 2) * 4)
    assert kernel_costs_delta.update_flops(rows, s) \
        == 7 * rows * 30 * 96 * 192
    # 0.9 FLOP a byte: far under the v5e's ridge (240), so bytes bind
    ratio = kernel_costs_delta.update_flops(rows, s) \
        / kernel_costs_delta.update_bytes(rows, s)
    assert 0.8 < ratio < 0.9
    # 1.51 GB a round at 28 live: 1.8 ms at 819 GB/s
    assert 1.50e9 < kernel_costs_delta.update_bytes(rows, s) < 1.52e9


def _hand_made_run():
    """Two rounds and one chunk call: the update 1 ms a round (twelve calls
    stand in one event) of 10 ms busy; the paged attention kernels' bfloat16
    results are none of its, nor is a float32 kernel the chunk program
    runs."""
    ms = 1_000_000
    ops = [
        ["%_decode_paged_state.1 custom-call tpu_custom_call "
         "f32[40,15,96,384]", 0, 1 * ms],
        ["%_decode_paged_state.2 custom-call tpu_custom_call "
         "bf16[40,30,1,128]", 1 * ms, 1 * ms],
        ["%_prefill_chunk_paged_state.3 custom-call tpu_custom_call "
         "bf16[1,30,256,128]", 4 * ms, 2 * ms],
        ["%_prefill_chunk_paged_state.4 custom-call tpu_custom_call "
         "f32[40,15,96,384]", 6 * ms, 1 * ms],
        ["%_decode_paged_state.1 custom-call tpu_custom_call "
         "f32[40,15,96,384]", 8 * ms, 1 * ms],
        ["%fusion.7 fusion", 9 * ms, 1 * ms],
    ]
    modules = [["jit__decode_paged_state(1)", 0, 3 * ms],
               ["jit__prefill_chunk_paged_state(2)", 4 * ms, 4 * ms],
               ["jit__decode_paged_state(1)", 8 * ms, 2 * ms]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    counted = {"prefill_chunks": 1, "delta_rows_updated": 2 * 28 * 12,
               "delta_chunk_positions": 12 * 200, "tokens_generated": 56}
    return {"trace": {"trace": trace, "window_s": 0.02, "counted": counted,
                      "decode_calls": [40_000, 41_000]},
            "busy": {"busy_s": 0.008, "per_chip_s": [0.008]},
            "sizes": SIZES, "device_kind": "TPU v5 lite", "chips": 1,
            "cell": {"serve_config": {"max_decode_slots": 40}},
            "serve": {"arena_pages": 288}}


def test_the_five_readers_on_a_hand_made_run():
    run = _hand_made_run()
    # two rounds of 28 live rows: 2 x 1.51 GB at 819 GB/s over the update's
    # 2 ms INSIDE the decode program (the chunk program's float32 kernel is
    # not its)
    least = kernel_costs_delta.update_bytes(2 * 28 * 12, SIZES) / 819e9
    assert _reader("delta_decode_roofline").read(run) \
        == pytest.approx(100 * least / 0.002)
    assert _reader("delta_update_share_pct").read(run) \
        == pytest.approx(100 * 0.002 / 0.008)
    assert _reader("delta_decode_step_device_ms").read(run) \
        == pytest.approx(2.5)
    assert _reader("delta_prefill_chunk_device_ms").read(run) \
        == pytest.approx(4.0)
    # the one traced call carried 12 x 200 counted positions: 200 real ones
    # of the row's 256 through the 4 ms of the whole program
    assert _reader("delta_chunk_us_per_position").read(run) \
        == pytest.approx(4000 / 200)
    # 1.51 GB in 1 ms would be 184 % of the peak: the contract refuses it,
    # the reader hides nothing
    assert _reader("delta_decode_roofline").read(run) > 105


def test_the_unlisted_reading_of_the_paged_kernel_at_one_query_row_a_head():
    """`mha_paged_decode_roofline`: the bytes of the traced rounds' live
    tokens on the MINE full layers, 30 KV heads of 128, over the bfloat16
    kernel's time inside the decode program — not the chunk program's
    kernel, not the update."""
    from chipbench import kernel_costs

    run = _hand_made_run()
    least = sum(kernel_costs.paged_decode_bytes(live, 40, 30, 30, 128, 2)
                for live in (40_000, 41_000)) / 819e9
    # 81,000 live tokens x 15,360 B a full layer: 1.24 GB, 6.1 ms over four
    assert 4 * least == pytest.approx(4 * 81_000 * 15_360 / 819e9, rel=1e-3)
    assert _reader("mha_paged_decode_roofline").read(run) \
        == pytest.approx(100 * 4 * least / 0.001)
    assert _reader("mha_paged_decode_roofline").read({"chips": 1}) is None
    ops = run["trace"]["trace"]["planes"][0]["lines"][0]
    ops["events"] = [e for e in ops["events"] if "_decode_paged_state.2 "
                     not in e[0]]
    assert _reader("mha_paged_decode_roofline").read(run) is None


def test_a_reader_that_finds_nothing_returns_none():
    for name in MINE:
        assert _reader(name).read({"chips": 1}) is None
        assert _reader(name).read({"serve": {}, "trace": None}) is None
    run = _hand_made_run()
    run["trace"]["counted"]["delta_rows_updated"] = 0
    assert _reader("delta_decode_roofline").read(run) is None
    del run["trace"]["counted"]["delta_rows_updated"]     # a program
    assert _reader("delta_decode_roofline").read(run) is None   # without it
    ops = run["trace"]["trace"]["planes"][0]["lines"][0]
    ops["events"] = [e for e in ops["events"] if "_decode_paged_state.1 "
                     not in e[0]]
    assert _reader("delta_update_share_pct").read(run) is None
    run["trace"]["trace"]["planes"][0]["lines"][1]["events"] = []
    assert _reader("delta_decode_step_device_ms").read(run) is None
    assert _reader("delta_prefill_chunk_device_ms").read(run) is None
    assert _reader("delta_chunk_us_per_position").read(run) is None
    run = _hand_made_run()
    del run["trace"]["counted"]["delta_chunk_positions"]   # a program
    assert _reader("delta_chunk_us_per_position").read(run) is None  # without


def _in_order(names, wanted):
    """`wanted` are all among `names`, in that relative order."""
    at = [names.index(n) for n in wanted]
    return at == sorted(at)


def test_the_five_are_listed_for_this_cell_alone_and_nothing_before_them_moved():
    """By MEMBERSHIP and relative order, never by position from the end: a
    later PR appends a cell, its name and its entries after these, and this
    test has nothing to say against that."""
    names = [m["name"] for m in BENCH["per_layer"]]
    # PR 39's five, then this PR's, each set in its own order
    assert _in_order(names, LATENT_FIVE + MINE)
    for name in MINE + LATENT_FIVE:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL if name in MINE else AXK1]
        assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} \
            == _reader(name).META
        assert entry["moves"] == "token_gap_p95_ms"
    cells = [w["name"] for w in BENCH["workloads"]]
    assert _in_order(cells, (MISTRAL, AXK1, CELL))
    (mine,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert mine["chips"] == 1 and mine["config"] == "olmo-hybrid-7b"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(cells) // 4)
    # the twins' namesakes stay the Mistral cell's, one cell each
    for name in ("decode_step_device_ms", "prefill_chunk_device_ms",
                 "session_host_ms_per_step", "paged_decode_roofline"):
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [MISTRAL]
    # PR 36's seven keep their three cells: both runners log them instead
    three = [w["name"] for w in BENCH["workloads"][:4] if w["chips"] == 1]
    seven = ("session_empty_pct", "decode_gap_host_ms", "prefill_gap_host_ms",
             "step_caller_ms", "decode_launch_readback_ms", "serve_compile_s",
             "serve_xla_compiles")
    for name in seven:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == three
        assert name in serve_delta.UNLISTED and name in serve_latent.UNLISTED
    for runner in (serve_latent, serve_delta):
        assert "session_host_ms_per_step" in runner.UNLISTED
    # the Granite cell's pool share and this PR's reading of the paged
    # decode kernel at one query row a KV head ship unlisted
    for name in ("state_pool_use_pct", "mha_paged_decode_roofline"):
        assert name in serve_delta.UNLISTED and name not in names
        assert _reader(name).META["moves"] == "token_gap_p95_ms"
    # and no listed reader shares a name with one that ships unlisted
    assert not set(MINE) & (set(serve_hybrid.UNLISTED)
                            | set(serve_window.UNLISTED)
                            | set(serve_latent.UNLISTED)
                            | set(serve_delta.UNLISTED))
    for name in ("token_gap_p95_ms", "admit_wait_mean_ms", "ttft_p90_ms",
                 "kv_arena_use_pct", "device_idle_pct.chat"):
        entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                     if m["name"] == name)
        assert _in_order(entry["workloads"], (MISTRAL, AXK1, CELL))


def test_the_traffic_is_the_issues():
    mix = _json("traffic", "long-answer.json")
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.7, "min": 128, "max": 2560}
    assert mix["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 64, "max": 1536}
    assert mix["shared_prefix"] is None
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["ramp"]["seconds"] == 5 and mix["tail_s"] == 20
    assert mix["drain_s"] == 120
    cell = _json("cells", CELL + ".json")
    sc = cell["serve_config"]
    assert sc["decode_buckets"] == [4096] and sc["max_decode_slots"] == 40
    assert sc["prefill_chunk"] == 256 and sc["kv_arena_pages"] == 288
    # the session the issue names: ONE prefill row, one chunk call a step
    assert (sc["prefill_batch"], sc["prefill_chunks_per_step"]) == (1, 1)
    assert not sc["enable_prefix_cache"] and not sc["speculate_k"]
    # every prompt fits its bucket with its longest output
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 4096
    # seven pages a slot: the arena runs out with the slots
    assert sc["kv_arena_pages"] // sc["max_decode_slots"] == 7
    # 0.8 of the knee swept with this session (PERF.md section 4)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(0.8 * KNEE)
    assert cell["check"]["requests"] == 6 and cell["check"]["rows"] == 768
    assert cell["check"]["long_requests"] == 2
    assert cell["check"]["longer_than"] == 2048 == 8 * sc["prefill_chunk"]


KNEE = 2.0      # requests/s: the highest rate the sweep sustained


def test_one_order_of_arrivals_every_seed_and_the_ids_the_seeds():
    """The mix's `order_seed` is the generator's own draw under that seed:
    the lengths, the due times and the outputs of every run are its, the
    ids are the run's seed's (`serve_latent.arrival_trace`, which this
    runner calls)."""
    from chipbench import traffic_gen

    mix = _json("traffic", "long-answer.json")
    assert mix["order_seed"] == 4121000033
    seeds = (2 ** 31 + 41, 7)
    a, b = (serve_delta.arrival_trace(mix, s, 50.0, 100352) for s in seeds)
    drawn = traffic_gen.serve_schedule(mix, mix["order_seed"], 50.0, 100352)

    def shape(schedule):
        return [(r["due_s"], len(r["prompt"]), r["max_new"], r["phase"])
                for r in schedule["requests"]]

    assert shape(a) == shape(b) == shape(drawn)
    assert sum(r["phase"] == "window" for r in a["requests"]) == 80
    assert sum(r["phase"] == "live" for r in a["requests"]) == 25
    assert [r["prompt"] for r in a["requests"]] \
        != [r["prompt"] for r in b["requests"]]
    assert all(1 <= t < 100352 for r in a["requests"] for t in r["prompt"])


def test_the_numbers_compared_are_the_ones_the_cell_limits():
    numbers = serve_delta._numbers([0.0] * 195 + [0.1, 0.2, 0.3, 0.4, 1.0])
    cell = _json("cells", CELL + ".json")
    for check in (cell["check"], cell["rehearse"]["cell"]["check"]):
        assert set(check["limits"]) == set(numbers)


# what the chip read (PERF.md section 4's table; my chip runs, PR 41): the
# LARGEST of 26 sound runs, and the SMALLEST a control read — fp8 operands
# over eleven runs, the stated precision with the recurrence in bfloat16 over
# three
READINGS = {"deficit_max": (0.0910, {"fp8": 1.5415, "bf16": 0.1085}),
            "deficit_mean": (0.000952, {"fp8": 0.2199, "bf16": 0.002036}),
            "not_first_choice_pct": (5.56, {"fp8": 59.49, "bf16": 7.41})}


def test_each_limit_lies_between_its_readings_and_one_fails_a_bf16_state():
    limits = _json("cells", CELL + ".json")["check"]["limits"]
    for name, (sound, control) in READINGS.items():
        # room on both sides of every limit against the fp8 control
        assert 1.4 * sound < limits[name] < control["fp8"] / 3, name
    # a state kept in bfloat16 (half the update's bytes) is told from a
    # sound run by the mean alone, with room on both sides; it passes the
    # other two, which is why the mean's limit is where it is
    sound, control = READINGS["deficit_mean"]
    assert 1.4 * sound < limits["deficit_mean"] < control["bf16"] / 1.4
    assert limits["deficit_max"] > READINGS["deficit_max"][1]["bf16"]


def test_the_sample_holds_two_requests_past_eight_chunk_boundaries():
    finished = [{"req": {"prompt": [1] * n}, "ids": [2] * m}
                for n, m in ((300, 500), (900, 1400), (250, 200), (2500, 1000),
                             (800, 300), (1200, 1300), (400, 100),
                             (500, 600))]
    logged = []
    spec = {"requests": 6, "long_requests": 2, "longer_than": 2048}
    sample = serve_delta.sample_requests(finished, 5, spec, logged.append)
    sizes = [len(r["req"]["prompt"]) + len(r["ids"]) for r in sample]
    assert len(sample) == 6 and len({id(r) for r in sample}) == 6
    assert sizes[0] == 3500 and sizes[1] > 2048         # the two long ones
    assert "2 from 3 finished requests longer than 2048" in logged[0]
