"""The Jamba2 additions of the benchmark and its serving cell.

The plain reference against an even plainer one written here (a loop over
positions, float64), the seeded weights, both controls, the configuration
file against the catalog's numbers, `kernel_costs_selective` against counts
worked by hand, the five new readers on a hand-made run, what
`BENCHMARK.json` says of them, the traffic, the limits between their chip
readings; then the cell end to end under `--rehearse` (its tiny twin on the
CPU: three selective layers and an attention layer of 4 heads on one KV
head): the last line is the contract's and a traced one carries the five
readers, read from the cell's own recorded trace; the recurrence broken
underneath turns `correct` false; a program without the model fails at
once.  (The reference imports nothing of the program;
`tests/test_models/test_jamba.py` holds the program to it.)"""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, kernel_costs_selective, weights_jamba
from chipbench.reference import jamba as reference
from chipbench.runners import (serve_delta, serve_hybrid, serve_latent,
                               serve_selective, serve_window)

from ._rehearse import CELLS, last_line, run_cell

CELL = "serve-jamba2-burstchat-1chip"
OLMO = "serve-olmohybrid-longanswer-1chip"
MISTRAL = "serve-mistral7b-chat-1chip"
MINE = ("selective_decode_roofline", "selective_scan_roofline",
        "selective_share_pct", "selective_decode_step_device_ms",
        "selective_prefill_chunk_device_ms")
DELTA_FIVE = ("delta_decode_roofline", "delta_update_share_pct",
              "delta_decode_step_device_ms", "delta_prefill_chunk_device_ms",
              "delta_chunk_us_per_position")
BENCH = contract.load_benchmark()
TINY = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=1,
    intermediate_size=48, num_hidden_layers=4, attn_layer_period=4,
    attn_layer_offset=2, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
    num_experts=1, sliding_window=None, vocab_size=96, rms_norm_eps=1e-6,
    tie_word_embeddings=True)
with open(os.path.join(contract.ROOT, "chipbench", "configs",
                       "ai21-jamba2-3b.json")) as f:
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
    return weights_jamba.jamba_params(TINY, weights_jamba.seed_key(4),
                                      dtype=jnp.float32)


def test_the_same_seed_makes_the_same_weights_and_the_tree_the_model_reads():
    a, b, c = (weights_jamba.jamba_params(
        TINY, weights_jamba.seed_key(s), dtype=jnp.float32)
        for s in (7, 7, 2 ** 31 + 5))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a["wte"], c["wte"])
    mixer, full = a["blocks"][0], a["blocks"][2]
    assert "w_in" not in full and "wq" not in mixer
    assert mixer["w_in"].shape == (32, 128)              # x~ | z
    assert mixer["conv_w"].shape == (4, 64) and mixer["conv_b"].shape == (64,)
    assert mixer["w_x"].shape == (64, 8 + 16 + 16)       # dt~ | B | C
    assert (mixer["norm_dt"].shape, mixer["norm_b"].shape,
            mixer["norm_c"].shape) == ((8,), (16,), (16,))
    assert mixer["w_dt"].shape == (8, 64) and mixer["w_out"].shape == (64, 32)
    # the channels on the lanes, as the state is stored
    assert mixer["a_log"].shape == (16, 64) and mixer["d_skip"].shape == (64,)
    assert mixer["a_log"].dtype == mixer["dt_bias"].dtype \
        == mixer["d_skip"].dtype == jnp.float32
    assert full["wq"].shape == full["wo"].shape == (32, 32)
    assert full["wk"].shape == full["wv"].shape == (32, 8)     # ONE head
    for blk in (mixer, full):
        assert blk["w1"].shape == (32, 96) and blk["w2"].shape == (48, 32)
    assert sorted(a) == ["blocks", "norm_f", "wte"]            # tied
    # assumption (c): A = -(n + 1), softplus(dt_bias) in 1e-3..1e-1, D = 1
    np.testing.assert_allclose(jnp.exp(mixer["a_log"][:, 5]),
                               np.arange(1, 17), rtol=1e-6)
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 1e-1 + 1e-6
    assert float(mixer["d_skip"].min()) == float(mixer["d_skip"].max()) == 1
    for bad in (dict(tie_word_embeddings=False), dict(num_experts=16),
                dict(mamba_conv_bias=False), dict(mamba_proj_bias=True),
                dict(num_attention_heads=5), dict(sliding_window=4096)):
        with pytest.raises(ValueError, match="sizes disagree"):
            weights_jamba.dims(dict(TINY, **bad))


def _by_position(params, sizes, tokens, norms=True, offset=None):
    """ISSUE 45's equations a position at a time, in float64 numpy, the
    state h [channels, index]: nothing of `reference` but its weights'
    layout."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    e, n, r, n_q, hd, eps = 64, 16, 8, 4, 8, sizes["rms_norm_eps"]
    offset = sizes["attn_layer_offset"] if offset is None else offset

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def silu(x):
        return x / (1 + np.exp(-x))

    x = p["wte"][np.asarray(tokens)]
    t = len(x)
    for i, blk in enumerate(p["blocks"]):
        u = norm(x, blk["norm_in"])
        if i % sizes["attn_layer_period"] == offset:
            q = (u @ blk["wq"]).reshape(t, n_q, hd)
            k, v = u @ blk["wk"], u @ blk["wv"]
            att = np.zeros((t, n_q, hd))
            for at in range(t):
                for head in range(n_q):
                    s = k[:at + 1] @ q[at, head] * hd ** -0.5
                    w = np.exp(s - s.max())
                    att[at, head] = (w / w.sum()) @ v[:at + 1]
            out = att.reshape(t, -1) @ blk["wo"]
        else:
            xz = u @ blk["w_in"]
            pre, z = xz[:, :e], xz[:, e:]
            a = -np.exp(blk["a_log"]).T                    # [e, n]
            h = np.zeros((e, n))
            y = np.zeros((t, e))
            for at in range(t):
                c = sum(blk["conv_w"][j] * pre[at - 3 + j] for j in range(4)
                        if at - 3 + j >= 0) + blk["conv_b"]
                xc = silu(c)
                proj = xc @ blk["w_x"]
                dt, b, cc = proj[:r], proj[r:r + n], proj[r + n:]
                if norms:
                    dt, b, cc = (norm(dt, blk["norm_dt"]),
                                 norm(b, blk["norm_b"]),
                                 norm(cc, blk["norm_c"]))
                dt = np.log1p(np.exp(dt @ blk["w_dt"] + blk["dt_bias"]))
                h = np.exp(dt[:, None] * a) * h + (dt * xc)[:, None] * b
                y[at] = h @ cc + blk["d_skip"] * xc
            out = (y * silu(z)) @ blk["w_out"]
        x = x + out
        ab = norm(x, blk["norm_ff"]) @ blk["w1"]
        x = x + (silu(ab[:, :48]) * ab[:, 48:]) @ blk["w2"]
    return norm(x, p["norm_f"]) @ p["wte"].T


TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (27,), 1, 96))


def test_the_reference_is_the_equations_a_position_at_a_time(params):
    got = np.asarray(reference.logits(params, TINY, TOKENS))
    want = _by_position(params, TINY, TOKENS)
    # the tied embedding at 0.02: a spread of 0.02 sqrt(hidden)
    assert 0.05 < want.std() < 0.3
    np.testing.assert_allclose(got, want, atol=2e-4 * want.std(), rtol=2e-3)
    rows = np.asarray(reference.logits(params, TINY, TOKENS, rows=[3, 26]))
    np.testing.assert_allclose(rows, got[[3, 26]], atol=1e-6)
    assert reference.kinds(TINY) == ("mamba", "mamba", "attention", "mamba")
    assert [i for i, k in enumerate(reference.kinds(SIZES))
            if k == "attention"] == [7, 21]


@pytest.mark.parametrize("what", [dict(norms=False), dict(offset=0)],
                         ids=["plain_mamba1_no_norms", "attention_first"])
def test_each_assumption_is_seen_by_the_logits(params, what):
    got = np.asarray(reference.logits(params, TINY, TOKENS))
    if "offset" in what:    # the other order: the same blocks, moved
        moved = dict(params, blocks=[params["blocks"][i]
                                     for i in (2, 0, 1, 3)])
        other = _by_position(moved, TINY, TOKENS, **what)
    else:
        other = _by_position(params, TINY, TOKENS, **what)
    assert np.abs(got - other)[4:].max() > 0.05 * got.std()


def test_the_controls_move_the_logits_each_by_its_own_measure(params):
    """fp8 operands move them most; the stated precision (bfloat16
    operands) with the conv, dt, the decay and the state kept in bfloat16
    moves them by less than that and by more than the stated precision
    alone, which on a model without selective layers it IS."""
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    sound = np.asarray(reference.logits(half, TINY, TOKENS))
    fp8 = np.asarray(reference.logits(half, TINY, TOKENS,
                                      quant="fp8_operands"))
    low = np.asarray(reference.logits(half, TINY, TOKENS,
                                      quant="bf16_recurrence"))
    np.testing.assert_array_equal(
        fp8, reference.logits(half, TINY, TOKENS, quant=True))
    assert np.abs(fp8 - sound).mean() > np.abs(low - sound).mean() > 1e-5
    stated = dict(TINY, attn_layer_period=1, attn_layer_offset=0)
    full = dict(half, blocks=[half["blocks"][2]] * 4)
    operands = np.abs(np.asarray(reference.logits(
        full, stated, TOKENS, quant="bf16_recurrence"))
        - np.asarray(reference.logits(full, stated, TOKENS))).mean()
    assert 0 < operands < np.abs(low - sound).mean()
    with pytest.raises(ValueError, match="no such control"):
        reference.logits(params, TINY, TOKENS, quant="fp4")


def test_the_config_file_is_the_catalogs_with_nothing_cut():
    d = weights_jamba.dims(SIZES)
    assert (d["hidden"], d["q"], d["kv"], d["hd"]) == (2560, 20, 1, 128)
    assert (d["inner"], d["state"], d["rank"], d["taps"]) \
        == (5120, 16, 160, 4)
    assert (d["ffn"], d["vocab"]) == (8192, 65536)
    assert len(d["kinds"]) == 28 and [
        i for i, k in enumerate(d["kinds"]) if k == "attention"] == [7, 21]
    entry = next(c for c in BENCH["configs"] if c["name"] == "ai21-jamba2-3b")
    assert SIZES["reduced"] == {} and entry["reduced"] == []
    assert entry["source"] == SIZES["source"]
    assert entry["file"] == "chipbench/configs/ai21-jamba2-3b.json"
    assert all(any(a.startswith(f"({x})") for a in SIZES["assumed"])
               for x in "abcde")
    assert all("other reading" in a for a in SIZES["assumed"]
               if a[:3] in ("(a)", "(b)", "(c)", "(d)", "(e)"))
    for word in ("one v5e chip", "WHOLE", "10.1 MB", "1,024 B"):
        assert word in SIZES["deployment"], word
    # the catalog's row, key for key
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "AI21-Jamba2-3B")
        assert row["source_url"] == SIZES["source"]
        for key, value in row["config"].items():
            assert SIZES[key] == value, key
    # bf16 bytes of what the file describes
    ffn = 3 * 2560 * 8192
    mixer = 2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 192 \
        + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560
    selective = mixer + ffn + 2 * 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128 + ffn + 2 * 2560
    assert 41.2e6 < mixer < 41.3e6
    assert 104.1e6 < selective < 104.2e6 and 76.6e6 < attention < 76.7e6
    total = 26 * selective + 2 * attention + 65536 * 2560 + 2560
    assert total == 3_029_337_472
    shapes = jax.eval_shape(lambda k: weights_jamba.jamba_params(SIZES, k),
                            jax.random.PRNGKey(0))
    # a_log, dt_bias and d_skip are float32: two more bytes each a value
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) \
        == 2 * total + 2 * 26 * (5120 * 16 + 2 * 5120)


def test_kernel_costs_selective_by_hand():
    s, k = SIZES, kernel_costs_selective
    assert k.state_layers(s) == 26 and k.inner(s) == 5120
    # one sequence, one layer: 5,120 channels x 16 indices of float32
    assert k.state_bytes(s) == 5120 * 16 * 4 == 327_680
    assert k.conv_tail_bytes(s) == 3 * 5120 * 4 == 61_440
    # a sequence: 26 x (327,680 + 61,440) = 10.1 MB whatever its length
    assert 26 * (k.state_bytes(s) + k.conv_tail_bytes(s)) == 10_117_120
    # the cell's pool: 128 slots, 26 layers
    assert k.stored_state_bytes(128, s) == 1_090_519_040
    # x, dt and y rows of 5,120 float32 and the B and C rows of 16
    assert k.position_bytes(s) == (3 * 5120 + 32) * 4 == 61_568
    # a round of 80 live rows updates 80 x 26 states: each read and written
    rows = 80 * 26
    assert k.update_bytes(rows, s) == rows * (2 * 327_680 + 61_568)
    assert 1.49e9 < k.update_bytes(rows, s) < 1.50e9      # 1.8 ms at 819 GB/s
    assert k.update_flops(rows, s) == 7 * rows * 5120 * 16
    # 0.8 FLOP a byte: far under the v5e's ridge (240), so bytes bind
    assert 0.7 < k.update_flops(rows, s) / k.update_bytes(rows, s) < 0.9
    # a chunk call of two rows, 400 real positions of its 512: 26 scans
    positions = 400 * 26
    assert k.scan_bytes(positions, 1, 2, s) \
        == positions * 61_568 + 2 * 26 * 2 * 327_680
    assert k.state_elements(positions, s) == positions * 81_920
    # 6.0 G operations over 0.67 GB: vector work binds, not bytes
    assert 8 < k.update_flops(positions, s) / k.scan_bytes(positions, 1, 2,
                                                           s) < 10


def _hand_made_run():
    """Two rounds and one chunk call: the update 1 ms a round (26 calls
    stand in one event) and the scan 3 ms of 10 ms busy; the paged
    attention kernels' bfloat16 results are none of theirs."""
    ms = 1_000_000
    ops = [
        ["%_decode_paged_state.1 custom-call tpu_custom_call "
         "f32[128,16,5120]", 0, 1 * ms],
        ["%_decode_paged_state.2 custom-call tpu_custom_call "
         "bf16[128,1,20,128]", 1 * ms, 1 * ms],
        ["%_prefill_chunk_paged_state.3 custom-call tpu_custom_call "
         "bf16[2,4,1280,128]", 4 * ms, 1 * ms],
        ["%_prefill_chunk_paged_state.4 custom-call tpu_custom_call "
         "f32[2,256,5120]", 5 * ms, 3 * ms],
        ["%_decode_paged_state.1 custom-call tpu_custom_call "
         "f32[128,16,5120]", 8 * ms, 1 * ms],
        ["%fusion.7 fusion", 9 * ms, 1 * ms],
    ]
    modules = [["jit__decode_paged_state(1)", 0, 3 * ms],
               ["jit__prefill_chunk_paged_state(2)", 4 * ms, 4 * ms],
               ["jit__decode_paged_state(1)", 8 * ms, 2 * ms]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    counted = {"prefill_chunks": 1, "selective_rows_updated": 2 * 80 * 26,
               "selective_scan_positions": 26 * 400, "tokens_generated": 160}
    return {"trace": {"trace": trace, "window_s": 0.02, "counted": counted,
                      "decode_calls": [40_000, 41_000]},
            "busy": {"busy_s": 0.008, "per_chip_s": [0.008]},
            "sizes": SIZES, "device_kind": "TPU v5 lite", "chips": 1,
            "cell": {"serve_config": {"max_decode_slots": 128,
                                      "prefill_batch": 2}},
            "serve": {"arena_pages": 2048}}


def test_the_five_readers_on_a_hand_made_run():
    run, k = _hand_made_run(), kernel_costs_selective
    # two rounds of 80 live rows: 2 x 1.49 GB at 819 GB/s over the update's
    # 2 ms INSIDE the decode program (the chunk program's float32 kernel is
    # the scan, not it)
    least = k.update_bytes(2 * 80 * 26, SIZES) / 819e9
    assert _reader("selective_decode_roofline").read(run) \
        == pytest.approx(100 * least / 0.002)
    # the scan's BYTES over its 3 ms inside the chunk program
    least = k.scan_bytes(26 * 400, 1, 2, SIZES) / 819e9
    assert _reader("selective_scan_roofline").read(run) \
        == pytest.approx(100 * least / 0.003)
    assert _reader("selective_scan_roofline").read(run) < 30    # reads low
    assert _reader("selective_share_pct").read(run) \
        == pytest.approx(100 * (0.002 + 0.003) / 0.008)
    assert _reader("selective_decode_step_device_ms").read(run) \
        == pytest.approx(2.5)
    assert _reader("selective_prefill_chunk_device_ms").read(run) \
        == pytest.approx(4.0)
    # 1.49 GB in 1 ms would be 182 % of the peak: the contract refuses it,
    # the reader hides nothing
    assert _reader("selective_decode_roofline").read(run) > 105


def test_the_unlisted_reading_of_the_paged_kernel_at_twenty_rows_on_one_head():
    """`mqa_paged_decode_roofline`: the bytes of the traced rounds' live
    tokens on the TWO attention layers, one KV head of 128 under 20 query
    heads, over the bfloat16 kernel's time inside the decode program — not
    the chunk program's kernel, not the update."""
    from chipbench import kernel_costs

    run = _hand_made_run()
    least = sum(kernel_costs.paged_decode_bytes(live, 128, 20, 1, 128, 2)
                for live in (40_000, 41_000)) / 819e9
    # 81,000 live tokens x 512 B a layer, q and o beside: ~42 MB a layer
    assert 81_000 * 512 / 819e9 < least < 1.2 * 81_000 * 512 / 819e9
    assert _reader("mqa_paged_decode_roofline").read(run) \
        == pytest.approx(100 * 2 * least / 0.001)
    assert _reader("mqa_paged_decode_roofline").read({"chips": 1}) is None
    ops = run["trace"]["trace"]["planes"][0]["lines"][0]
    ops["events"] = [e for e in ops["events"] if "_decode_paged_state.2 "
                     not in e[0]]
    assert _reader("mqa_paged_decode_roofline").read(run) is None


def test_a_reader_that_finds_nothing_returns_none():
    for name in MINE:
        assert _reader(name).read({"chips": 1}) is None
        assert _reader(name).read({"serve": {}, "trace": None}) is None
    for counter, name in (("selective_rows_updated",
                           "selective_decode_roofline"),
                          ("selective_scan_positions",
                           "selective_scan_roofline")):
        run = _hand_made_run()
        run["trace"]["counted"][counter] = 0
        assert _reader(name).read(run) is None
        del run["trace"]["counted"][counter]      # a program without it:
        assert _reader(name).read(run) is None    # the parent
    # a program without the kernels (the parent's trace holds no 3-D
    # float32 Mosaic call): the three that read them fall silent
    run = _hand_made_run()
    ops = run["trace"]["trace"]["planes"][0]["lines"][0]
    ops["events"] = [e for e in ops["events"] if " f32[" not in e[0]]
    for name in MINE[:3]:
        assert _reader(name).read(run) is None
    run["trace"]["trace"]["planes"][0]["lines"][1]["events"] = []
    assert _reader("selective_decode_step_device_ms").read(run) is None
    assert _reader("selective_prefill_chunk_device_ms").read(run) is None
    # the olmo cell's own float32 kernel is 4-D: none of this cell's
    run = _hand_made_run()
    for e in run["trace"]["trace"]["planes"][0]["lines"][0]["events"]:
        e[0] = e[0].replace("f32[128,16,5120]", "f32[40,15,96,384]")
    assert _reader("selective_decode_roofline").read(run) is None


def _in_order(names, wanted):
    """`wanted` are all among `names`, in that relative order."""
    at = [names.index(n) for n in wanted]
    return at == sorted(at)


def test_the_five_are_listed_for_this_cell_alone_and_nothing_before_them_moved():
    """By MEMBERSHIP and relative order, never by position from the end: a
    later PR appends a cell, its name and its entries after these, and this
    test has nothing to say against that."""
    names = [m["name"] for m in BENCH["per_layer"]]
    # PR 41's five, then this PR's, each set in its own order
    assert _in_order(names, DELTA_FIVE + MINE)
    for name in MINE + DELTA_FIVE:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL if name in MINE else OLMO]
        assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} \
            == _reader(name).META
        assert entry["moves"] == "token_gap_p95_ms"
    better = {m["name"]: m["better"] for m in BENCH["per_layer"]}
    assert [better[n] for n in MINE] == ["higher", "higher", "lower",
                                         "lower", "lower"]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert _in_order(cells, (MISTRAL, OLMO, CELL))
    (mine,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert mine["chips"] == 1 and mine["config"] == "ai21-jamba2-3b"
    assert mine["traffic"] == "burst-chat"
    assert len(cells) >= 7
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1 \
        <= max(1, len(cells) // 4)
    # the twins' namesakes stay the Mistral cell's, one cell each
    for name in ("decode_step_device_ms", "prefill_chunk_device_ms",
                 "session_host_ms_per_step", "paged_decode_roofline"):
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [MISTRAL]
    # PR 36's seven keep their three cells: the runners log them instead
    seven = ("session_empty_pct", "decode_gap_host_ms", "prefill_gap_host_ms",
             "step_caller_ms", "decode_launch_readback_ms", "serve_compile_s",
             "serve_xla_compiles")
    for name in seven:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]
        assert name in serve_selective.UNLISTED
    assert "session_host_ms_per_step" in serve_selective.UNLISTED
    # the Granite cell's pool share and this PR's reading of the paged
    # decode kernel at twenty query rows on one KV head ship unlisted
    for name in ("state_pool_use_pct", "mqa_paged_decode_roofline"):
        assert name in serve_selective.UNLISTED and name not in names
        assert _reader(name).META["moves"] == "token_gap_p95_ms"
    # and no listed reader shares a name with one that ships unlisted
    assert not set(MINE) & (set(serve_hybrid.UNLISTED)
                            | set(serve_window.UNLISTED)
                            | set(serve_latent.UNLISTED)
                            | set(serve_delta.UNLISTED)
                            | set(serve_selective.UNLISTED))
    for name in ("token_gap_p95_ms", "admit_wait_mean_ms", "ttft_p90_ms",
                 "kv_arena_use_pct", "device_idle_pct.chat"):
        entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                     if m["name"] == name)
        assert _in_order(entry["workloads"], (MISTRAL, OLMO, CELL))
    # the runner is serve_delta's run with this file's family alone
    assert serve_selective.serve_family is serve_delta.serve_family
    assert serve_selective.JAMBA.reference == "jamba"
    assert [q for _, q in serve_selective.JAMBA.controls] \
        == ["fp8_operands", "bf16_recurrence"]


KNEE = 13.0     # requests/s: the highest rate the sweep sustained


def test_the_traffic_is_the_issues():
    mix = _json("traffic", "burst-chat.json")
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 16, "max": 512}
    assert mix["shared_prefix"] is None
    assert mix["arrivals"]["process"] == "gamma"
    assert mix["arrivals"]["cv"] == 2.0
    assert mix["ramp"]["seconds"] == 5 and mix["tail_s"] == 20
    assert mix["drain_s"] == 30
    cell = _json("cells", CELL + ".json")
    # the window's requests end ~12.5 s after it closes; closing a device
    # trace holds the host for seconds a traced second (PERF.md section
    # 4): one second of ~34 rounds and ~13 chunk calls keeps a traced run's
    # drain inside the issue's 30 s
    assert cell["trace_s"] == 1.0
    sc = cell["serve_config"]
    assert sc["decode_buckets"] == [4096] and sc["max_decode_slots"] == 128
    assert sc["prefill_chunk"] == 256 and sc["kv_arena_pages"] == 2048
    assert (sc["prefill_batch"], sc["prefill_chunks_per_step"]) == (2, 1)
    assert not sc["enable_prefix_cache"] and not sc["speculate_k"]
    # every prompt fits its bucket with its longest output
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 4096
    # sixteen pages a slot: every slot can hold the longest sequence
    assert sc["kv_arena_pages"] // sc["max_decode_slots"] == 16
    # 0.8 of the knee swept with this session (PERF.md section 4)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(0.8 * KNEE)
    # four times the issue's 8 and 2: a sample of eight moved deficit_mean
    # by a quarter between sound seeds (PERF.md section 4)
    assert cell["check"]["requests"] == 32 and cell["check"]["rows"] == 768
    assert cell["check"]["long_requests"] == 8
    assert cell["check"]["longer_than"] == 1024 == 4 * sc["prefill_chunk"]
    assert cell["runner"] == "serve_selective"


def test_one_order_of_arrivals_every_seed_and_the_ids_the_seeds():
    from chipbench import traffic_gen

    mix = _json("traffic", "burst-chat.json")
    seeds = (2 ** 31 + 41, 7)
    a, b = (serve_delta.arrival_trace(mix, s, 50.0, 65536) for s in seeds)
    drawn = traffic_gen.serve_schedule(mix, mix["order_seed"], 50.0, 65536)

    def shape(schedule):
        return [(r["due_s"], len(r["prompt"]), r["max_new"], r["phase"])
                for r in schedule["requests"]]

    assert shape(a) == shape(b) == shape(drawn)
    rate = mix["arrivals"]["rate_per_s"]
    assert sum(r["phase"] == "window" for r in a["requests"]) \
        == round(50 * rate)
    assert sum(r["phase"] == "live" for r in a["requests"]) \
        == mix["ramp"]["live"]
    assert [r["prompt"] for r in a["requests"]] \
        != [r["prompt"] for r in b["requests"]]
    assert all(1 <= t < 65536 for r in a["requests"] for t in r["prompt"])
    # bursts: gamma gaps with cv 2 (a due time lies mid-gap and the
    # generator's stratified quantiles stop short of the tail: the due
    # times' gaps read 1.37 as drawn, where Poisson's read 0.69) — a third
    # of the arrivals come within a fifth of a mean gap of the one before,
    # where Poisson's 6 % would
    due = np.asarray([r["due_s"] for r in a["requests"]
                      if r["phase"] == "window"])
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() > 1.3
    assert (gaps < 0.2 / rate).mean() > 0.3


def test_the_numbers_compared_are_the_ones_the_cell_limits():
    numbers = serve_delta._numbers([0.0] * 195 + [0.1, 0.2, 0.3, 0.4, 1.0])
    cell = _json("cells", CELL + ".json")
    for check in (cell["check"], cell["rehearse"]["cell"]["check"]):
        assert set(check["limits"]) == set(numbers)


# what the chip read at 32 sampled requests, 8 of them long (PERF.md section
# 4's table; my chip runs, PR 45, second session): the LARGEST of the sound
# runs (twenty runs, 4,305-5,870 served tokens each; the first session's
# fourteen runs of 8 requests, a quarter of the tokens, read up to 0.2063 /
# 0.00609 / 12.82) and the SMALLEST each control read over its four runs
READINGS = {"deficit_max": (0.2503, {"fp8": 3.3689, "bf16": 0.3826}),
            "deficit_mean": (0.005554, {"fp8": 0.8879, "bf16": 0.01122}),
            "not_first_choice_pct": (12.16, {"fp8": 88.45, "bf16": 16.52})}


def test_each_limit_lies_between_its_readings_and_all_three_fail_a_bf16_state():
    limits = _json("cells", CELL + ".json")["check"]["limits"]
    for name, (sound, control) in READINGS.items():
        # room on both sides of every limit against the fp8 control
        assert 1.15 * sound < limits[name] < control["fp8"] / 3, name
    # a state kept in bfloat16 is told from a sound run by the mean — its
    # smallest reading 2.0x the sound runs' largest, the limit 1.39x over
    # the one and 1.46x under the other — and by the share of tokens that
    # are not the reference's first choice (1.15x and 1.18x)
    sound, control = READINGS["deficit_mean"]
    assert control["bf16"] > 2 * sound
    assert 1.35 * sound < limits["deficit_mean"] < control["bf16"] / 1.35
    sound, control = READINGS["not_first_choice_pct"]
    assert 1.15 * sound < limits["not_first_choice_pct"] \
        < control["bf16"] / 1.15
    # the max over ~5,400 tokens: 1.4x over the sound runs' largest; the
    # bf16 state's smallest reading is 1.09x over it, which decides nothing
    sound, control = READINGS["deficit_max"]
    assert 1.39 * sound < limits["deficit_max"] < control["bf16"]


def test_the_sample_holds_two_requests_past_four_chunk_boundaries():
    finished = [{"req": {"prompt": [1] * n}, "ids": [2] * m}
                for n, m in ((300, 100), (900, 400), (250, 200), (1500, 300),
                             (800, 100), (1200, 130), (400, 100), (500, 60),
                             (64, 30), (100, 200))]
    logged = []
    spec = {"requests": 8, "long_requests": 2, "longer_than": 1024}
    sample = serve_delta.sample_requests(finished, 5, spec, logged.append)
    sizes = [len(r["req"]["prompt"]) + len(r["ids"]) for r in sample]
    assert len(sample) == 8 and len({id(r) for r in sample}) == 8
    assert sizes[0] == 1800 and sizes[1] > 1024         # the two long ones
    assert "2 from 3 finished requests longer than 1024" in logged[0]


# ------------------------------------------------ the cell under --rehearse

ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 41), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0 and obj["device"]["platform"] == "cpu"
    assert "correct: deficit_max" in err and "limit" in err
    # the sample holds a request with two chunk boundaries behind it
    sampled = re.search(r"sample of (\d+) from (\d+) finished requests "
                        r"longer than 32 tokens", err)
    assert sampled and int(sampled.group(2)) >= 1
    # one number all run long, and the one the shapes give
    held = re.search(r"selective_state_bytes over the run: \[(\d+)\] \(the "
                     r"shapes give (\d+):", err)
    assert held and held.group(1) == held.group(2) \
        == str(4 * 3 * 16 * 128 * 4)
    assert set(obj["metrics"]) >= {"setup_s", "token_gap_p95_ms"}
    logged = dict(re.findall(r"not reported: (\S+) = (\S+)", err))
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        for name in ("kv_arena_use_pct", "device_idle_pct.chat",
                     "admit_wait_mean_ms", "ttft_p90_ms"):
            assert obj["metrics"][name]["value"] >= 0, name
        for name in MINE:     # none of them None: the recording is the
            assert obj["metrics"][name]["value"] > 0, name   # cell's own
        for name in MINE[:3]:
            assert obj["metrics"][name]["value"] <= 100.0, name
        assert set(serve_selective.UNLISTED) <= set(logged)
        assert float(logged["state_pool_use_pct"]) > 0
        assert float(logged["serve_xla_compiles"]) == 2.0
        assert float(logged["selective_scan_elements_per_s"]) > 0
        assert "decode_step_device_ms" not in obj["metrics"]
    else:
        assert not set(MINE) & set(obj["metrics"])
        # the host's readers take the span recorder alone: every run logs
        # them, and nothing that needs a device trace
        assert set(serve_selective.UNLISTED) & set(logged) \
            == set(serve_selective.HOST)
        assert float(logged["session_host_ms_per_step"]) > 0
        assert re.search(r"decode program: in flight \S+ ms over the run",
                         err)


BREAK = """
from easydist_tpu.ops import ssm
from chipbench import run
{patch}
run.main()
"""
BROKEN = {
    # the decode round decays by half of dt: every state drifts from the
    # first generated token on
    "half_the_step_in_the_decode_round": """
sound = ssm.selective_decode_update
ssm.selective_decode_update = lambda state, x, dt, *a, **kw: \\
    sound(state, x, 0.5 * dt, *a, **kw)
""",
    # the chunk scan forgets nothing and adds nothing: what a prompt leaves
    # in the state is wrong before the first round
    "the_state_left_out_of_the_chunk_scan": """
sound = ssm.selective_chunk_scan
ssm.selective_chunk_scan = lambda x, dt, *a, **kw: \\
    sound(x, 0.0 * dt, *a, **kw)
""",
}


@pytest.mark.parametrize("what", list(BROKEN))
def test_the_recurrence_broken_underneath_is_not_correct(what):
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=BREAK.format(patch=BROKEN[what]))
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    assert obj["correct"] is False
    assert "OVER THE LIMIT" in err


def test_the_fp8_control_is_not_correct_by_the_cells_own_limits_and_bf16_is_read():
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse", "--control")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    sound, control = obj["check"]["numbers"], obj["check"]["control"]
    assert control["deficit_mean"] > 3 * sound["deficit_mean"]
    assert control["deficit_mean"] > 0
    assert obj["correct"] is True and control["correct"] is False
    assert re.search(r"control \(fp8 operands\) correct: \S+ = \S+"
                     r"  limit \S+  OVER THE LIMIT", err)
    # the second control — the conv, dt, the decay and the state in
    # bfloat16 — is read against the same limits and reported beside it
    # (what it reads at the real size is in PERF.md section 4's table)
    state = control["bf16_recurrence"]
    assert set(state) == set(sound) | {"correct"}
    assert 0 <= state["deficit_mean"] < control["deficit_mean"]
    assert isinstance(state["correct"], bool)
    assert re.search(r"control \(bf16 recurrence\) correct: deficit_mean = ",
                     err)


def test_a_program_without_the_model_fails_at_once(tmp_path):
    """What the driver's check of the new cell on the parent commit sees:
    the benchmark's files laid over a program that lacks the model end in
    a nonzero exit before any weight is made."""
    import shutil

    shutil.copy(contract.ROOT + "/BENCHMARK.json", tmp_path)
    shutil.copytree(contract.ROOT + "/chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(contract.ROOT + "/easydist_tpu",
                    tmp_path / "easydist_tpu",
                    ignore=shutil.ignore_patterns("__pycache__", "jamba.py"))
    init = tmp_path / "easydist_tpu" / "models" / "__init__.py"
    init.write_text(init.read_text().replace("from . import jamba, ",
                                             "from . import "))
    assert not os.path.exists(tmp_path / "easydist_tpu" / "models"
                              / "jamba.py")
    assert os.path.exists(tmp_path / "chipbench" / "reference" / "jamba.py")
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            cwd=str(tmp_path))
    assert rc != 0 and out == ""
    assert "jamba" in err and "weights on the device" not in err
