"""The LFM2-8B-A1B additions of the benchmark and its serving cell.

The seeded weights, the plain reference against an even plainer one written
here (a loop over positions, float64), both controls, the configuration
file against the catalog's numbers and the byte count, `kernel_costs_shortconv`
against counts worked by hand, the five new readers on a hand-made run and
on the cell's recorded trace, what `BENCHMARK.json` says of them, the
traffic; then the cell end to end under `--rehearse` (its tiny twin on the
CPU: conv conv attn conv conv attn, one dense layer, top 2 of 8 experts with
4 held, heads of 16 on a lane-dense arena): the last line is the contract's
and a traced one carries the five readers; the timed path broken underneath
turns `correct` false; a program without the model fails at once.  (The
reference imports nothing of the program; `tests/test_models/
test_lfm2_moe.py` holds the program to it.)"""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, kernel_costs_shortconv, weights_lfm2
from chipbench.reference import lfm2_moe as reference
from chipbench.runners import (serve_delta, serve_hybrid, serve_latent,
                               serve_selective, serve_shortconv,
                               serve_window)

from ._rehearse import CELLS, last_line, run_cell

CELL = "serve-lfm2moe-crowdchat-1chip"
JAMBA = "serve-jamba2-burstchat-1chip"
MISTRAL = "serve-mistral7b-chat-1chip"
MINE = ("shortconv_expert_roofline", "shortconv_expert_share_pct",
        "shortconv_attn_decode_roofline", "shortconv_decode_step_device_ms",
        "shortconv_prefill_chunk_device_ms")
SELECTIVE_FIVE = ("selective_decode_roofline", "selective_scan_roofline",
                  "selective_share_pct", "selective_decode_step_device_ms",
                  "selective_prefill_chunk_device_ms")
BENCH = contract.load_benchmark()
TYPES = ["conv", "conv", "full_attention", "conv", "full_attention", "conv"]
TINY = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=16, num_hidden_layers=6,
    layer_types=TYPES, num_dense_layers=2, num_experts=4, router_experts=8,
    experts_held=[0, 4], num_experts_per_tok=2, conv_L_cache=3,
    conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
    use_expert_bias=True, rope_theta=1e6, routed_scaling_factor=1,
    vocab_size=96)
with open(os.path.join(contract.ROOT, "chipbench", "configs",
                       "lfm2-8b-a1b.json")) as f:
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
    return weights_lfm2.lfm2_params(TINY, weights_lfm2.seed_key(11),
                                    dtype=jnp.float32)


def test_the_same_seed_makes_the_same_weights_and_the_tree_the_model_reads():
    from easydist_tpu.models import lfm2_moe

    a = weights_lfm2.lfm2_params(TINY, weights_lfm2.seed_key(2 ** 31 + 7))
    b = weights_lfm2.lfm2_params(TINY, weights_lfm2.seed_key(2 ** 31 + 7))
    c = weights_lfm2.lfm2_params(TINY, weights_lfm2.seed_key(2 ** 31 + 8))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a),
                                                    jax.tree.leaves(b)))
    assert not np.array_equal(a["wte"], c["wte"])
    assert a["wte"].dtype == jnp.bfloat16
    assert a["blocks"][2]["router_bias"].dtype == jnp.float32
    cfg = serve_shortconv.model_config(dict(TINY))
    mine = jax.eval_shape(lambda k: lfm2_moe.lfm2_init(cfg, k),
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: x.shape, a) \
        == jax.tree.map(lambda x: x.shape, mine)
    # the list of layers, the dense layers and the held experts
    kinds = [("w_in" in b, "router" in b) for b in a["blocks"]]
    assert kinds == [(True, False), (True, False), (False, True),
                     (True, True), (False, True), (True, True)]
    assert a["blocks"][3]["w1"].shape == (4, 32, 32)
    assert a["blocks"][3]["router"].shape == (32, 8)


@pytest.mark.parametrize("wrong", [
    dict(conv_bias=True), dict(tie_word_embeddings=False),
    dict(norm_topk_prob=False), dict(num_shared_experts=1),
    dict(num_experts=8), dict(layer_types=TYPES[:5]),
    dict(use_expert_bias=False)])
def test_sizes_that_disagree_with_what_is_built_are_refused(wrong):
    with pytest.raises(ValueError, match="disagree with what is built"):
        weights_lfm2.dims({**TINY, **wrong})


def _by_position(params, sizes, tokens):
    """The issue's equations a position at a time, float64 numpy: the conv
    from the two inputs carried, attention over the keys so far, every
    chosen held expert a dense SwiGLU."""
    f64 = lambda a: np.asarray(a, np.float64)   # noqa: E731
    hd = sizes["hidden_size"] // sizes["num_attention_heads"]
    n_q, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    eps, top_k = sizes["norm_eps"], sizes["num_experts_per_tok"]
    first, held = sizes["experts_held"]
    dim = sizes["hidden_size"]

    def rms(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * f64(g)

    def silu(x):
        return x / (1 + np.exp(-x))

    def glu(x, w1, w2):
        ab = x @ f64(w1)
        half = ab.shape[-1] // 2
        return (silu(ab[:half]) * ab[half:]) @ f64(w2)

    def rope(x, p):                          # [heads, hd]
        half = hd // 2
        ang = p / sizes["rope_theta"] ** (np.arange(half) / half)
        x1, x2 = x[:, :half], x[:, half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    tails = [np.zeros((2, dim)) for _ in params["blocks"]]
    keys = [[] for _ in params["blocks"]]
    vals = [[] for _ in params["blocks"]]
    out = []
    for p, tok in enumerate(tokens):
        h = f64(params["wte"])[tok]
        for li, (kind, blk) in enumerate(zip(sizes["layer_types"],
                                             params["blocks"])):
            u = rms(h, blk["norm_op"])
            if kind == "conv":
                bcx = u @ f64(blk["w_in"])
                z = bcx[:dim] * bcx[2 * dim:]
                w = f64(blk["conv_w"])
                conv = w[2] * z + w[1] * tails[li][1] + w[0] * tails[li][0]
                tails[li] = np.stack([tails[li][1], z])
                h = h + (bcx[dim:2 * dim] * conv) @ f64(blk["w_out"])
            else:
                q = rope(rms((u @ f64(blk["wq"])).reshape(n_q, hd),
                             blk["q_norm"]), p)
                k = rope(rms((u @ f64(blk["wk"])).reshape(n_kv, hd),
                             blk["k_norm"]), p)
                keys[li].append(k)
                vals[li].append((u @ f64(blk["wv"])).reshape(n_kv, hd))
                ks, vs = np.stack(keys[li]), np.stack(vals[li])
                att = np.zeros((n_q, hd))
                for head in range(n_q):
                    s = ks[:, head // (n_q // n_kv)] @ q[head] / np.sqrt(hd)
                    w = np.exp(s - s.max())
                    att[head] = (w / w.sum()) @ vs[:, head // (n_q // n_kv)]
                h = h + att.reshape(-1) @ f64(blk["wo"])
            f = rms(h, blk["norm_ffn"])
            if "router" not in blk:
                h = h + glu(f, blk["w1"], blk["w2"])
                continue
            s = 1 / (1 + np.exp(-(f @ f64(blk["router"]))))
            idx = np.argsort(-(s + f64(blk["router_bias"])))[:top_k]
            total = s[idx].sum() + 1e-6
            for e in idx:
                if first <= e < first + held:
                    h = h + sizes["routed_scaling_factor"] * s[e] / total \
                        * glu(f, blk["w1"][e - first], blk["w2"][e - first])
        out.append(rms(h, params["norm_f"]) @ f64(params["wte"]).T)
    return np.stack(out)


TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (27,), 1, 96))


def test_the_reference_is_the_equations_a_position_at_a_time(params):
    want = _by_position(params, TINY, TOKENS)
    got = np.asarray(reference.logits(params, TINY, TOKENS))
    np.testing.assert_allclose(got, want, atol=2e-5 * want.std() + 1e-6,
                               rtol=1e-4)
    rows = np.asarray(reference.logits(params, TINY, TOKENS, rows=[3, 26]))
    np.testing.assert_array_equal(rows, got[[3, 26]])


def test_the_absent_experts_part_is_left_out(params):
    """With all 8 experts held the logits move: what the absent half adds
    is really left out at `experts_held` [0, 4)."""
    whole = dict(TINY, num_experts=8, experts_held=[0, 8])
    full = weights_lfm2.lfm2_params(whole, weights_lfm2.seed_key(11),
                                    dtype=jnp.float32)
    a = np.asarray(reference.logits(full, whole, TOKENS))
    b = _by_position(full, whole, TOKENS)
    np.testing.assert_allclose(a, b, atol=2e-5 * b.std() + 1e-6, rtol=1e-4)
    cut = dict(full, blocks=[
        dict(blk, w1=blk["w1"][:4], w2=blk["w2"][:4]) if "router" in blk
        else blk for blk in full["blocks"]])
    c = np.asarray(reference.logits(cut, TINY, TOKENS))
    assert np.abs(c - a).max() > 0.1 * a.std()


def test_the_controls_move_the_logits_each_by_its_own_measure(params):
    sound = np.asarray(reference.logits(params, TINY, TOKENS))
    fp8 = np.asarray(reference.logits(params, TINY, TOKENS,
                                      quant="fp8_operands"))
    assert np.array_equal(fp8, np.asarray(reference.logits(
        params, TINY, TOKENS, quant=True)))
    router = np.asarray(reference.logits(params, TINY, TOKENS,
                                         quant="bf16_router"))
    spread = sound.std()
    assert 0.01 * spread < np.abs(fp8 - sound).max()
    # the router's control rounds operands to bfloat16 too: it moves the
    # logits, and less than fp8 does on the whole
    assert 0 < np.abs(router - sound).mean() < np.abs(fp8 - sound).mean()
    with pytest.raises(ValueError, match="no such control"):
        reference.logits(params, TINY, TOKENS, quant="int4")


def test_bfloat16_scores_choose_other_experts_on_near_ties():
    """The second control's one step down: scores rounded to 8 bits of
    mantissa tie where float32 ones do not, and `top_k` then takes another
    expert."""
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.normal(size=(4000, 32)), jnp.float32)
    blk = {"router": jnp.asarray(rng.normal(size=(32, 32)) / 32 ** 0.5,
                                 jnp.float32),
           "router_bias": jnp.zeros((32,), jnp.float32)}
    s = jax.nn.sigmoid(f @ blk["router"])
    _, sound = jax.lax.top_k(s, 4)
    _, low = jax.lax.top_k(reference._bf16(s), 4)
    flipped = (np.sort(sound, 1) != np.sort(low, 1)).any(1).mean()
    assert 0.01 < flipped < 0.5


def test_the_config_file_is_the_catalogs_with_the_experts_cut_to_a_share():
    d = weights_lfm2.dims(SIZES)
    assert (d["hidden"], d["q"], d["kv"], d["hd"]) == (2048, 32, 8, 64)
    assert (d["dense"], d["expert"], d["taps"]) == (7168, 1792, 3)
    assert (d["experts"], d["first"], d["held"], d["top_k"]) \
        == (32, 0, 16, 4)
    assert d["vocab"] == 65536 and d["dense_layers"] == 2
    assert len(d["kinds"]) == 24 and [
        i for i, k in enumerate(d["kinds"]) if k == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2-8b-a1b")
    assert list(SIZES["reduced"]) == ["num_experts"] == entry["reduced"]
    assert SIZES["published"] == {"num_experts": 32}
    assert SIZES["router_experts"] == 32 and SIZES["num_experts"] == 16
    assert entry["source"] == SIZES["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "chipbench/configs/lfm2-8b-a1b.json"
    assert all(any(a.startswith(f"({x})") for a in SIZES["assumed"])
               for x in "abcdefg")
    for word in ("v5e-2", "16 of 32 experts", "ONE chip of the pair",
                 "4,464,393,664", "8.93 GB", "12 KB", "288 KB"):
        assert word in SIZES["deployment"], word
    # the catalog's row, key for key, but the one key that is reduced
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert row["source_url"] == SIZES["source"]
        for key, value in row["config"].items():
            if key == "num_experts":
                assert value == SIZES["published"][key] == 32
            else:
                assert SIZES[key] == value, key
    # the issue's arithmetic, reckoned again
    expert = 3 * 2048 * 1792
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert expert == 11_010_048 and 16.7e6 < conv < 16.8e6
    assert 10.4e6 < attention < 10.5e6
    total = 22 * (16 * expert + 2048 * 32 + 32) + 2 * 3 * 2048 * 7168 \
        + 18 * conv + 6 * attention + 65536 * 2048 + 24 * 2 * 2048 + 2048
    assert total == 4_464_393_664
    shapes = jax.eval_shape(lambda k: weights_lfm2.lfm2_params(SIZES, k),
                            jax.random.PRNGKey(0))
    # the selection bias is float32: two more bytes each a value
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) \
        == 2 * total + 2 * 22 * 32
    assert 8.92e9 < 2 * total < 8.94e9
    assert total + 22 * 16 * expert == 8_339_930_560      # the whole model


def test_kernel_costs_shortconv_by_hand():
    k = kernel_costs_shortconv
    assert k.layers(SIZES, "conv") == 18
    assert k.layers(SIZES, "full_attention") == 6
    assert k.expert_layers(SIZES) == 22 and k.head_dim(SIZES) == 64
    # an expert hit is 22.0 MB of bfloat16 weights, a routed pair 22.0 MFLOP
    assert k.expert_params(SIZES) == 3 * 2048 * 1792 == 11_010_048
    assert k.expert_ffn_flops(1, SIZES) == 22_020_096.0
    assert k.expert_ffn_bytes(0, 1, SIZES) == 22_020_096.0
    # a pair's rows beside the weights: 2 x 2,048 + 3 x 1,792 values
    assert k.expert_ffn_bytes(1, 0, SIZES) == 2 * (4096 + 5376)
    # a round of 256 live rows: 512 pairs on 16 experts, 22 layers
    assert k.expert_ffn_bytes(512 * 22, 16 * 22, SIZES) \
        == 2 * (352 * 11_010_048 + 11264 * 9472)
    # a live token on an attention layer: 2,048 B of K and V
    assert k.kv_token_bytes(SIZES) == 2048
    # a conv tail: 2 x 2,048 float32 = 16 KB a slot a layer; 256 slots 74 MB
    assert k.conv_tail_bytes(SIZES) == 16384
    assert k.stored_state_bytes(256, SIZES) == 256 * 18 * 16384 == 75_497_472
    tiny = _json("cells", CELL + ".json")["rehearse"]["sizes"]
    tiny = {**SIZES, **tiny}
    assert k.stored_state_bytes(4, tiny) == 4 * 4 * 2 * 64 * 4


def _hand_made_run():
    """Two rounds and one chunk call: the expert products 1 ms a round and
    2 ms a chunk call, the paged decode kernel 0.5 ms a round, 10 ms busy;
    the chunk program's attention kernel is not the decode kernel's."""
    ms = 1_000_000
    ops = [
        ["%_decode_paged_state.1 custom-call tpu_custom_call bf16[512,3584]",
         0, 1 * ms],
        ["%_decode_paged_state.2 custom-call tpu_custom_call "
         "bf16[256,8,4,64]", 1 * ms, ms // 2],
        ["%_prefill_chunk_paged_state.3 custom-call tpu_custom_call "
         "bf16[4,8,1024,64]", 4 * ms, 1 * ms],
        ["%_prefill_chunk_paged_state.4 custom-call tpu_custom_call "
         "bf16[2048,3584]", 5 * ms, 2 * ms],
        ["%_decode_paged_state.1 custom-call tpu_custom_call bf16[512,3584]",
         8 * ms, 1 * ms],
        ["%_decode_paged_state.2 custom-call tpu_custom_call "
         "bf16[256,8,4,64]", 9 * ms, ms // 2],
        ["%fusion.7 fusion", 9 * ms + ms // 2, ms // 2],
    ]
    modules = [["jit__decode_paged_state(1)", 0, 3 * ms],
               ["jit__prefill_chunk_paged_state(2)", 4 * ms, 4 * ms],
               ["jit__decode_paged_state(1)", 8 * ms, 2 * ms]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    counted = {"prefill_chunks": 1, "tokens_generated": 300,
               "moe_pairs_routed": 2 * 300 * 22, "moe_experts_hit": 2 * 352,
               "moe_prefill_pairs_routed": 2000 * 22,
               "moe_prefill_experts_hit": 352}
    return {"trace": {"trace": trace, "window_s": 0.02, "counted": counted,
                      "decode_calls": [100_000, 101_000]},
            "busy": {"busy_s": 0.008, "per_chip_s": [0.008]},
            "sizes": SIZES, "device_kind": "TPU v5 lite", "chips": 1,
            "cell": {"serve_config": {"max_decode_slots": 256,
                                      "prefill_batch": 4}},
            "serve": {"arena_pages": 1536}}


def test_the_five_readers_on_a_hand_made_run():
    run, k = _hand_made_run(), kernel_costs_shortconv
    # rounds bound by bytes (704 hits x 22 MB) and the chunk call too: at
    # 2,000 pairs on 16 experts a layer an expert sees 125 rows, 125 FLOPs
    # a byte of its weights, under the chip's 240 (44,000 pairs x 22 MFLOP
    # are 4.9 ms, 352 hits x 22 MB 10.5) — over the 4 ms of 2-D bfloat16
    # Mosaic calls
    rounds = k.expert_ffn_bytes(13200, 704, SIZES) / 819e9
    call = k.expert_ffn_bytes(44000, 352, SIZES) / 819e9
    assert call > k.expert_ffn_flops(44000, SIZES) / 197e12 > call / 2.2
    assert _reader("shortconv_expert_roofline").read(run) \
        == pytest.approx(100 * (rounds + call) / 0.004)
    assert _reader("shortconv_expert_share_pct").read(run) \
        == pytest.approx(100 * 0.004 / 0.008)
    # 201,000 live tokens x 2,048 B on six layers, q and o of 256 rows,
    # over the decode kernel's 1 ms INSIDE the decode program
    least = 6 * (201_000 * 2048 + 2 * 2 * 256 * 32 * 64 * 2) / 819e9
    assert _reader("shortconv_attn_decode_roofline").read(run) \
        == pytest.approx(100 * least / 0.001)
    assert _reader("shortconv_decode_step_device_ms").read(run) \
        == pytest.approx(2.5)
    assert _reader("shortconv_prefill_chunk_device_ms").read(run) \
        == pytest.approx(4.0)
    # 2.5 GB in 1 ms would be over the peak: the contract refuses it, the
    # reader hides nothing
    assert _reader("shortconv_attn_decode_roofline").read(run) > 105


def test_a_reader_that_finds_nothing_returns_none():
    """A program without the spans, the counters or the kernels (the
    parent commit under this PR's benchmark files): every reader returns
    None and does not raise."""
    run = _hand_made_run()
    bare = dict(run, trace=None)
    for name in MINE:
        assert _reader(name).read(bare) is None, name
    # a trace without the kernels or the programs
    empty = dict(run, trace=dict(run["trace"], trace={"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["%fusion.1 fusion", 0, 10]]},
            {"name": "XLA Modules", "events": []}]}]}))
    for name in MINE:
        assert _reader(name).read(empty) is None, name
    # the kernels, but a program that counted no experts
    uncounted = dict(run, trace=dict(run["trace"], counted={
        "prefill_chunks": 1}))
    assert _reader("shortconv_expert_roofline").read(uncounted) is None


def _in_order(names, wanted):
    """`wanted` are all among `names`, in that relative order."""
    at = [names.index(n) for n in wanted]
    return at == sorted(at)


def test_the_five_are_listed_for_this_cell_alone_and_nothing_before_them_moved():
    """By MEMBERSHIP and relative order, never by position from the end: a
    later PR appends a cell, its name and its entries after these."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert _in_order(names, SELECTIVE_FIVE + MINE)
    for name in MINE + SELECTIVE_FIVE:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL if name in MINE else JAMBA]
        assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} \
            == _reader(name).META
        assert entry["moves"] == "token_gap_p95_ms"
    better = {m["name"]: m["better"] for m in BENCH["per_layer"]}
    assert [better[n] for n in MINE] == ["higher", "lower", "higher",
                                         "lower", "lower"]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert _in_order(cells, (MISTRAL, JAMBA, CELL))
    (mine,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert mine["chips"] == 1 and mine["config"] == "lfm2-8b-a1b"
    assert mine["traffic"] == "crowd-chat" and len(mine["why"]) <= 200
    assert len(cells) >= 8
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1 \
        <= max(1, len(cells) // 4)
    for name in ("decode_step_device_ms", "prefill_chunk_device_ms",
                 "session_host_ms_per_step", "paged_decode_roofline"):
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [MISTRAL]
    seven = ("session_empty_pct", "decode_gap_host_ms", "prefill_gap_host_ms",
             "step_caller_ms", "decode_launch_readback_ms", "serve_compile_s",
             "serve_xla_compiles")
    for name in seven:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]
        assert name in serve_shortconv.UNLISTED
    for name in ("session_host_ms_per_step", "state_pool_use_pct",
                 "expert_load_max_over_mean"):
        assert name in serve_shortconv.UNLISTED
        assert _reader(name).META["moves"] == "token_gap_p95_ms"
    assert not set(MINE) & (set(serve_hybrid.UNLISTED)
                            | set(serve_window.UNLISTED)
                            | set(serve_latent.UNLISTED)
                            | set(serve_delta.UNLISTED)
                            | set(serve_selective.UNLISTED)
                            | set(serve_shortconv.UNLISTED))
    for name in ("token_gap_p95_ms", "admit_wait_mean_ms", "ttft_p90_ms",
                 "kv_arena_use_pct", "device_idle_pct.chat"):
        entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                     if m["name"] == name)
        assert _in_order(entry["workloads"], (MISTRAL, JAMBA, CELL))
    # the runner is serve_delta's run with this file's family alone
    assert serve_shortconv.serve_family is serve_delta.serve_family
    assert serve_shortconv.LFM2.reference == "lfm2_moe"
    assert [q for _, q in serve_shortconv.LFM2.controls] \
        == ["fp8_operands", "bf16_router"]
    for name in ("shortconv_rows_updated", "shortconv_chunk_positions",
                 "moe_pairs_routed", "moe_prefill_experts_hit",
                 "prefill_attn_pairs", "decode_pages_walked"):
        assert name in serve_shortconv.LFM2.counters


KNEE = 14.0     # requests/s: the highest rate the sweep sustained


def test_the_traffic_is_the_issues():
    mix = _json("traffic", "crowd-chat.json")
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.9, "min": 32, "max": 3072}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 32, "max": 1024}
    assert mix["shared_prefix"] is None
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["ramp"]["seconds"] == 5 and mix["tail_s"] == 20
    assert mix["drain_s"] == 120
    # 0.8 of the knee swept with this session (PERF.md section 4), and the
    # knee it is 0.8 of is in the file's own words
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(0.8 * KNEE)
    assert "0.8 of the knee" in mix["what"] and "14/s" in mix["what"]
    # the steady live count at that rate
    assert 120 <= mix["ramp"]["live"] <= 200
    cell = _json("cells", CELL + ".json")
    assert cell["trace_s"] == 1.0
    sc = cell["serve_config"]
    assert sc["decode_buckets"] == [4096] and sc["max_decode_slots"] == 256
    assert sc["prefill_chunk"] == 256
    assert (sc["prefill_batch"], sc["prefill_chunks_per_step"]) == (4, 1)
    assert not sc["enable_prefix_cache"] and not sc["speculate_k"]
    # every prompt fits its bucket with its longest output
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 4096
    # sized so that the fullest round of the window reads 60-85 % of it
    # (901 pages of the replayed order at 1,536: PERF.md section 4)
    assert sc["kv_arena_pages"] == 1152
    assert 0.60 <= 901 / sc["kv_arena_pages"] <= 0.85
    assert cell["check"]["requests"] == 8 and cell["check"]["rows"] == 1024
    assert cell["check"]["long_requests"] == 2
    assert cell["check"]["longer_than"] == 1536 == 6 * sc["prefill_chunk"]
    assert cell["runner"] == "serve_shortconv"
    assert cell["recorded_trace"] == "serve-shortconv-1chip.json.gz"
    # the rehearsal's twin is the issue's
    tiny = cell["rehearse"]["sizes"]
    assert tiny["layer_types"] == ["conv", "conv", "full_attention"] * 2
    assert (tiny["hidden_size"], tiny["num_attention_heads"],
            tiny["num_key_value_heads"]) == (64, 4, 2)
    assert (tiny["router_experts"], tiny["experts_held"],
            tiny["num_experts_per_tok"], tiny["num_dense_layers"],
            tiny["vocab_size"]) == (8, [0, 4], 2, 1, 256)


def test_one_order_of_arrivals_every_seed_and_the_ids_the_seeds():
    from chipbench import traffic_gen

    mix = _json("traffic", "crowd-chat.json")
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
    # token ids uniform over all 65,536 (0 is kept out, as in every cell)
    ids = np.concatenate([r["prompt"] for r in a["requests"]])
    assert ids.min() >= 1 and ids.max() < 65536 and ids.max() > 65000
    lengths = [len(r["prompt"]) for r in a["requests"]
               if r["phase"] == "window"]
    assert min(lengths) >= 32 and max(lengths) <= 3072
    assert 330 < np.median(lengths) < 440


def test_the_numbers_compared_are_the_ones_the_cell_limits():
    numbers = serve_delta._numbers([0.0] * 195 + [0.1, 0.2, 0.3, 0.4, 1.0])
    cell = _json("cells", CELL + ".json")
    for check in (cell["check"], cell["rehearse"]["cell"]["check"]):
        assert set(check["limits"]) == set(numbers)


# what the chip read at 8 sampled requests, 2 of them long (PERF.md section
# 4's table; my chip runs, PR 48): the LARGEST of twenty sound runs and what
# each control read.  The second control — the router's scores rounded to
# bfloat16 — reads as a sound run: at 22 expert layers with half of the
# chosen pairs held, the stream's own bfloat16 noise flips more fourth
# choices than its rounding does (PERF.md section 6), and no limit on
# served tokens can tell it.
READINGS = {"deficit_max": (2.048, {"fp8": 3.486, "bf16_router": 1.358}),
            "deficit_mean": (0.0840, {"fp8": 1.139, "bf16_router": 0.0711}),
            "not_first_choice_pct": (38.55, {"fp8": 94.17,
                                             "bf16_router": 35.76})}


def test_each_limit_lies_between_its_readings_and_fp8_fails_every_one():
    limits = _json("cells", CELL + ".json")["check"]["limits"]
    for name, (sound, control) in READINGS.items():
        assert sound < limits[name] < control["fp8"], name
    # room on both sides where the readings leave it: the mean and the
    # share of tokens that are not the reference's first choice
    for name in ("deficit_mean", "not_first_choice_pct"):
        sound, control = READINGS[name]
        assert 1.5 * sound < limits[name] < control["fp8"] / 1.5, name
    # the widest gap guards a plainly wrong token: 1.4x over the sound
    # runs' largest, and decides nothing between precisions
    assert 1.4 * READINGS["deficit_max"][0] < limits["deficit_max"]
    # the router's control is INSIDE the sound runs' range on all three
    for name, (sound, control) in READINGS.items():
        assert control["bf16_router"] < sound, name


# ------------------------------------------------ the cell under --rehearse

ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 48), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0 and obj["device"]["platform"] == "cpu"
    assert "correct: deficit_max" in err and "limit" in err
    # one number all run long, and the one the shapes give: a tail a slot a
    # conv layer and nothing beside it
    held = re.search(r"shortconv_state_bytes over the run: \[(\d+)\] \(the "
                     r"shapes give (\d+):", err)
    assert held and held.group(1) == held.group(2) \
        == str(4 * 4 * 2 * 64 * 4)
    assert set(obj["metrics"]) >= {"setup_s", "token_gap_p95_ms"}
    logged = dict(re.findall(r"not reported: (\S+) = (\S+)", err))
    counted = re.search(r"counters in the window (\{.*\})", err)
    window = json.loads(counted.group(1).replace("'", '"'))
    assert window["shortconv_rows_updated"] \
        == 4 * window["tokens_generated"] > 0
    assert window["shortconv_chunk_positions"] > 0
    assert 0 < window["moe_pairs_routed"] < window["moe_pair_slots"]
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        for name in ("kv_arena_use_pct", "device_idle_pct.chat",
                     "admit_wait_mean_ms", "ttft_p90_ms"):
            assert obj["metrics"][name]["value"] >= 0, name
        for name in MINE:     # none of them None: the recording is the
            assert obj["metrics"][name]["value"] > 0, name   # cell's own
        assert set(serve_shortconv.UNLISTED) <= set(logged)
        assert float(logged["state_pool_use_pct"]) > 0
        assert float(logged["serve_xla_compiles"]) == 2.0
        assert float(logged["expert_load_max_over_mean"]) >= 1.0
        assert "decode_step_device_ms" not in obj["metrics"]
    else:
        assert not set(MINE) & set(obj["metrics"])
        assert set(serve_shortconv.UNLISTED) & set(logged) \
            == set(serve_selective.HOST)
        assert float(logged["session_host_ms_per_step"]) > 0


def test_the_recorded_trace_is_the_cells_own_and_the_five_read_it():
    """The reduced trace taken of this cell on the chip: both programs by
    name, the expert products, the paged kernels inside each — and no copy
    of a K or V leaf anywhere in it."""
    from chipbench import programs, trace_reduce

    trace = trace_reduce.load_recorded(os.path.join(
        contract.ROOT, "chipbench", "recorded",
        _json("cells", CELL + ".json")["recorded_trace"]))
    assert trace["device_kind"] == "TPU v5 lite"
    names = {n for n, _, _ in programs.module_events(trace)}
    assert any("jit__decode_paged_state" in n for n in names)
    assert any("jit__prefill_chunk_paged_state" in n for n in names)
    ops = [n for n, _, _ in trace_reduce.op_events(
        trace_reduce.device_planes(trace)[0])]
    assert any(re.search(r"tpu_custom_call bf16\[\d+,8,\d+,64\]", n)
               for n in ops)
    # the arena's leaves are [pages, 8, 128, 128] bfloat16: no op of the
    # trace gives one (a copy, a transpose, a pad of a whole leaf would)
    assert not [n for n in ops if re.search(r"bf16\[\d+,8,128,128\]", n)
                and not n.startswith("%scatter") and "scatter" not in n]


BREAK = """
from easydist_tpu.ops import ssm
from easydist_tpu.models import experts
from chipbench import run
{patch}
run.main()
"""
BROKEN = {
    # the conv forgets its tail: every conv layer is wrong from a
    # sequence's second position on
    "the_tail_left_out_of_the_conv": """
import jax.numpy as jnp
sound = ssm.causal_conv_tail
ssm.causal_conv_tail = lambda tail, *a, **kw: \\
    sound(jnp.zeros_like(tail), *a, **kw)
""",
    # the bias gates: a chosen expert is weighed by score + bias
    "the_router_takes_the_first_experts": """
import jax.numpy as jnp
sound = experts.sigmoid_route
def broken(u, router, top_k, scale, bias=None, eps=1e-20):
    idx, gate = sound(u, router, top_k, scale, bias, eps)
    return jnp.zeros_like(idx) + jnp.arange(top_k), gate
experts.sigmoid_route = broken
import easydist_tpu.models.lfm2_moe as m
m.sigmoid_route = broken
""",
}


@pytest.mark.parametrize("what", list(BROKEN))
def test_the_timed_path_broken_underneath_is_not_correct(what):
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=BREAK.format(patch=BROKEN[what]))
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    assert obj["correct"] is False
    assert "OVER THE LIMIT" in err


def test_the_fp8_control_is_not_correct_by_the_cells_own_limits_and_the_router_is_read():
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse", "--control")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    sound, control = obj["check"]["numbers"], obj["check"]["control"]
    assert control["deficit_mean"] > 3 * sound["deficit_mean"]
    assert obj["correct"] is True and control["correct"] is False
    assert re.search(r"control \(fp8 operands\) correct: \S+ = \S+"
                     r"  limit \S+  OVER THE LIMIT", err)
    router = control["bf16_router"]
    assert set(router) == set(sound) | {"correct"}
    assert 0 <= router["deficit_mean"] < control["deficit_mean"]
    assert isinstance(router["correct"], bool)
    assert re.search(r"control \(bf16 router scores\) correct: "
                     r"deficit_mean = ", err)


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
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  "lfm2_moe.py"))
    init = tmp_path / "easydist_tpu" / "models" / "__init__.py"
    init.write_text(init.read_text().replace(", lfm2_moe", ""))
    assert not os.path.exists(tmp_path / "easydist_tpu" / "models"
                              / "lfm2_moe.py")
    assert os.path.exists(tmp_path / "chipbench" / "reference"
                          / "lfm2_moe.py")
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            cwd=str(tmp_path))
    assert rc != 0 and out == ""
    assert "lfm2_moe" in err and "weights on the device" not in err
