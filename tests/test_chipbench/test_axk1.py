"""The A.X-K1 additions of the benchmark: the plain (expanded) reference
against an even plainer one written here (a loop over positions and heads,
float64), the scale and the YaRN blend seen by the logits, the seeded
weights, the fp8 control, the configuration file against the catalog's
numbers, `kernel_costs_latent` against counts worked by hand, the five new
readers on a hand-made run, what `BENCHMARK.json` says of them, and the
sample the check draws.  (The reference imports nothing of the program;
`tests/test_models/test_axk1.py` holds the program to it.)"""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, kernel_costs_latent, weights_axk1
from chipbench.reference import axk1 as reference
from chipbench.runners import serve_hybrid, serve_latent, serve_window

CELL = "serve-axk1-longdoc-1chip"
MISTRAL = "serve-mistral7b-chat-1chip"
FIVE = ("latent_decode_roofline", "latent_chunk_roofline",
        "latent_attn_share_pct", "latent_decode_step_device_ms",
        "latent_prefill_chunk_device_ms")
BENCH = contract.load_benchmark()
TINY = dict(
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
with open(os.path.join(contract.ROOT, "chipbench", "configs",
                       "a.x-k1.json")) as f:
    SIZES = json.load(f)


def _reader(name):
    path = os.path.join(contract.ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return weights_axk1.axk1_params(TINY, weights_axk1.seed_key(4),
                                    dtype=jnp.float32)


def test_the_same_seed_makes_the_same_weights_and_the_tree_the_model_reads():
    a, b, c = (weights_axk1.axk1_params(
        TINY, weights_axk1.seed_key(s), dtype=jnp.float32)
        for s in (7, 7, 2 ** 31 + 5))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a["wte"], c["wte"])
    dense, sparse = a["blocks"][0], a["blocks"][1]
    assert dense["w1"].shape == (32, 96) and dense["w2"].shape == (48, 32)
    assert "router" not in dense and "router" in a["blocks"][2]
    assert sparse["router"].shape == (32, 8)
    assert sparse["w1"].shape == (4, 32, 32)
    assert sparse["shared_w2"].shape == (16, 32)
    assert sparse["w_dq"].shape == (32, 24) and sparse["q_norm"].shape == (24,)
    assert sparse["w_uq"].shape == (24, 4 * 16)
    assert sparse["w_dkv"].shape == (32, 16 + 8)      # latent | rotary key
    assert sparse["kv_norm"].shape == (16,)
    assert sparse["w_ukv"].shape == (16, 4 * 16) and sparse["wo"].shape \
        == (32, 32)
    assert a["head"].shape == a["wte"].shape == (96, 32)
    assert not np.array_equal(a["head"], a["wte"])          # untied
    for bad in (dict(n_routed_experts=8), dict(topk_method="noaux_tc"),
                dict(first_k_dense_replace=3), dict(n_shared_experts=2)):
        with pytest.raises(ValueError, match="sizes disagree"):
            weights_axk1.dims(dict(TINY, **bad))


def _by_position(params, sizes, tokens, m_squared=True, blend=True):
    """ISSUE 39's equations a position and a head at a time, in float64
    numpy: nothing of `reference` but its weights' layout."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    h, nope, rope, vd = 4, 8, 8, 8
    rank, eps = sizes["kv_lora_rank"], sizes["rms_norm_eps"]
    rs = sizes["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    scale = (nope + rope) ** -0.5 * (m * m if m_squared else 1.0)
    half = rope // 2
    f = 10000.0 ** (-np.arange(half) / half)

    def turns_dim(turns):
        return rope * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi)) / (2 * math.log(1e4))

    low = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rs["beta_slow"])), rope - 1)
    keep = 1 - np.clip((np.arange(half) - low) / max(high - low, 0.001), 0, 1)
    inv = f / rs["factor"] * (1 - keep) + f * keep if blend else f

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def silu(x):
        return x / (1 + np.exp(-x))

    def glu(u, w1, w2):
        ab = u @ w1
        return (silu(ab[:ab.size // 2]) * ab[ab.size // 2:]) @ w2

    def rot(x, pos):
        ang = pos * inv
        x1, x2 = x[:half], x[half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)])

    x = p["wte"][np.asarray(tokens)]
    for blk in p["blocks"]:
        u = norm(x, blk["norm_attn"])
        c_q = norm(u @ blk["w_dq"], blk["q_norm"])
        q = (c_q @ blk["w_uq"]).reshape(-1, h, nope + rope)
        ckr = u @ blk["w_dkv"]
        c = norm(ckr[:, :rank], blk["kv_norm"])
        kv = (c @ blk["w_ukv"]).reshape(-1, h, nope + vd)
        out = np.zeros((len(x), h * vd))
        for i in range(len(x)):
            for head in range(h):
                qi = np.concatenate([q[i, head, :nope],
                                     rot(q[i, head, nope:], i)])
                s = np.array([qi @ np.concatenate(
                    [kv[j, head, :nope], rot(ckr[j, rank:], j)])
                    for j in range(i + 1)]) * scale
                w = np.exp(s - s.max())
                out[i, head * vd:(head + 1) * vd] = \
                    (w / w.sum()) @ kv[:i + 1, head, nope:]
        x = x + out @ blk["wo"]
        u = norm(x, blk["norm_ffn"])
        f_out = np.zeros_like(x)
        for i in range(len(x)):
            if "router" not in blk:
                f_out[i] = glu(u[i], blk["w1"], blk["w2"])
                continue
            s = 1 / (1 + np.exp(-(u[i] @ blk["router"])))
            top = np.argsort(-s, kind="stable")[:sizes["num_experts_per_tok"]]
            for e in top:
                if sizes["experts_held"][0] <= e < sum(sizes["experts_held"]):
                    f_out[i] += 2.5 * s[e] / (s[top].sum() + 1e-20) * glu(
                        u[i], blk["w1"][e - sizes["experts_held"][0]],
                        blk["w2"][e - sizes["experts_held"][0]])
            f_out[i] += glu(u[i], blk["shared_w1"], blk["shared_w2"])
        x = x + f_out
    return norm(x, p["norm_f"]) @ p["head"].T


TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (27,), 1, 96))


def test_the_reference_is_the_equations_a_position_at_a_time(params):
    got = np.asarray(reference.logits(params, TINY, jnp.asarray(TOKENS)))
    want = _by_position(params, TINY, TOKENS)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    rows = np.asarray(reference.logits(params, TINY, jnp.asarray(TOKENS),
                                       rows=[3, 26]))
    np.testing.assert_allclose(rows, got[[3, 26]], atol=1e-6)


def test_the_scale_and_the_blend_are_seen_by_the_logits(params):
    """Positions past the original context (16 here) read other logits
    without m^2 in the scale, and without YaRN's blend of the frequencies:
    both are in the reference, and each moves it by far more than the
    comparison's tolerance."""
    got = np.asarray(reference.logits(params, TINY, jnp.asarray(TOKENS)))
    for broken in (dict(m_squared=False), dict(blend=False)):
        other = _by_position(params, TINY, TOKENS, **broken)
        assert np.abs(other - got)[20:].max() > 1e-2, broken


def test_the_fp8_control_moves_the_logits_and_bf16_barely_does(params):
    full = np.asarray(reference.logits(params, TINY, jnp.asarray(TOKENS)))
    low = np.asarray(reference.logits(params, TINY, jnp.asarray(TOKENS),
                                      quant=True))
    half = np.asarray(reference.logits(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), TINY,
        jnp.asarray(TOKENS)))
    assert np.abs(low - full).mean() > 4 * np.abs(half - full).mean() > 0


def test_the_config_file_is_the_catalogs_with_the_cut_written_out():
    d = weights_axk1.dims(SIZES)
    assert (d["hidden"], d["heads"], d["q_rank"], d["kv_rank"]) \
        == (7168, 64, 1536, 512)
    assert (d["nope"], d["rope"], d["v"]) == (128, 64, 128)
    assert (d["dense"], d["expert"], d["top_k"]) == (18432, 2048, 8)
    assert (d["experts"], d["first"], d["held"]) == (192, 0, 12)
    assert d["vocab"] == 20480 and d["layers"] == 6
    assert SIZES["routed_scaling_factor"] == 2.5
    assert SIZES["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (SIZES["n_group"], SIZES["topk_group"]) == (8, 4)   # kept, unused
    assert SIZES["published"] == {"num_hidden_layers": 61,
                                  "n_routed_experts": 192,
                                  "vocab_size": 163840}
    entry = next(c for c in BENCH["configs"] if c["name"] == "a.x-k1")
    assert set(SIZES["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert entry["source"] == SIZES["source"]
    assert len(SIZES["assumed"]) >= 5 and all(
        any(a.startswith(f"({x})") for a in SIZES["assumed"])
        for x in "abcd")
    for word in ("sixteen chips", "data-parallel", "eighth of the vocabulary"):
        assert word in SIZES["deployment"], word
    # the catalog's row, key for key, but for the three that are reduced
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
        assert row["source_url"] == SIZES["source"]
        for key, value in row["config"].items():
            if key not in SIZES["reduced"]:
                assert SIZES[key] == value, key
    # bf16 bytes of what the file describes: 8.33 GB of weights
    attn = 7168 * 1536 + 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 \
        + 512 * 64 * 256 + 64 * 128 * 7168 + 2 * 7168
    expert = 3 * 7168 * 2048
    sparse = attn + 7168 * 192 + 13 * expert
    total = attn + 3 * 7168 * 18432 + 5 * sparse + 2 * 20480 * 7168 + 7168
    assert 8.32e9 < 2 * total < 8.34e9
    shapes = jax.eval_shape(lambda k: weights_axk1.axk1_params(SIZES, k),
                            jax.random.PRNGKey(0))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) \
        == 2 * total


def test_kernel_costs_latent_by_hand():
    s = SIZES
    # one position in one layer: 512 + 64 values, bf16; stored in five tiles
    assert kernel_costs_latent.row_values(s) == 576
    assert kernel_costs_latent.token_bytes(s) == 1152
    assert kernel_costs_latent.stored_token_bytes(s) == 1280
    # every head's own keys (192) and values (128): 35.6x the latent
    assert kernel_costs_latent.expanded_token_bytes(s) == 40_960
    # the cell's arena: 2,048 pages of 256 tokens, six layers
    assert kernel_costs_latent.stored_cache_bytes(2048, 256, s) \
        == 2048 * 256 * 6 * 1280 == 4_026_531_840
    # a layer of a round over 32 rows holding 100,000 tokens: every row
    # once for all 64 heads, q' (576) in and the heads' sums (512) out
    assert kernel_costs_latent.decode_bytes(100_000, 32, s) \
        == 100_000 * 1152 + 32 * 64 * (576 + 512) * 2
    # 64 heads x (576 + 512) multiply-adds a cached token: 139 kFLOP
    assert kernel_costs_latent.decode_flops(1, s) == 139_264
    # 121 FLOP a byte: half the v5e's ridge (197e12 / 819e9 = 240)
    assert 120 < 139_264 / 1152 < 122
    # the model's own attention: 64 heads x (192 + 128) a visible pair
    assert kernel_costs_latent.chunk_model_flops(1000, s) \
        == 2 * 1000 * 64 * 320
    # which is 29 % of what the absorbed kernel multiplies
    assert 320 / (576 + 512) == pytest.approx(0.294, abs=1e-3)


def _hand_made_run():
    """Two rounds and one chunk call: `latent_decode` 1 ms a round (six
    calls stand in one event), `latent_chunk` 2 ms, of 10 ms busy; the
    expert kernel's 2-D results are none of theirs."""
    ms = 1_000_000
    ops = [
        ["%_decode_paged.1 custom-call tpu_custom_call bf16[32,1,64,512]",
         0, 1 * ms],
        ["%_decode_paged.2 custom-call tpu_custom_call bf16[384,4096]",
         1 * ms, 1 * ms],
        ["%_prefill_chunk_paged.3 custom-call tpu_custom_call "
         "bf16[2,32,512,512]", 4 * ms, 2 * ms],
        ["%_prefill_chunk_paged.4 custom-call tpu_custom_call "
         "bf16[6144,4096]", 6 * ms, 1 * ms],
        ["%_decode_paged.1 custom-call tpu_custom_call bf16[32,1,64,512]",
         8 * ms, 1 * ms],
        ["%fusion.7 fusion", 9 * ms, 1 * ms],
    ]
    modules = [["jit__decode_paged(1)", 0, 3 * ms],
               ["jit__prefill_chunk_paged(2)", 4 * ms, 4 * ms],
               ["jit__decode_paged(1)", 8 * ms, 2 * ms]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    counted = {"prefill_chunks": 1, "prefill_attn_pairs": 200_000,
               "prefill_pages_walked": 40, "prefill_pages_bucket": 128}
    return {"trace": {"trace": trace, "window_s": 0.02, "counted": counted,
                      "decode_calls": [100_000, 120_000]},
            "busy": {"busy_s": 0.007, "per_chip_s": [0.007]},
            "sizes": SIZES, "device_kind": "TPU v5 lite", "chips": 1,
            "cell": {"serve_config": {"max_decode_slots": 32}},
            "serve": {"arena_pages": 2048}}


def test_the_five_readers_on_a_hand_made_run():
    run = _hand_made_run()
    # a round's six layers: the larger of bytes at 819 GB/s and FLOPs at
    # 197 TFLOP/s — bytes, at 121 FLOP a byte — over the kernel's 2 ms
    # INSIDE the decode program (the chunk program's 2 ms are not its)
    qo = 32 * 64 * (576 + 512) * 2
    least = 6 * ((100_000 * 1152 + qo) + (120_000 * 1152 + qo)) / 819e9
    assert (100_000 * 1152 + qo) / 819e9 > 100_000 * 139_264 / 197e12
    assert _reader("latent_decode_roofline").read(run) \
        == pytest.approx(100 * least / 0.002)
    # the model's FLOPs of the counted pairs, six layers, over the chunk
    # kernel's 2 ms inside the chunk program
    assert _reader("latent_chunk_roofline").read(run) == pytest.approx(
        100 * 6 * 2 * 200_000 * 64 * 320 / 197e12 / 0.002)
    assert _reader("latent_attn_share_pct").read(run) \
        == pytest.approx(100 * 0.004 / 0.007)
    assert _reader("latent_decode_step_device_ms").read(run) \
        == pytest.approx(2.5)
    assert _reader("latent_prefill_chunk_device_ms").read(run) \
        == pytest.approx(4.0)
    for name in ("latent_decode_roofline", "latent_chunk_roofline"):
        assert 0 < _reader(name).read(run) < 100


def test_a_reader_that_finds_nothing_returns_none():
    for name in FIVE:
        assert _reader(name).read({"chips": 1}) is None
        assert _reader(name).read({"serve": {}, "trace": None}) is None
    run = _hand_made_run()
    ops = run["trace"]["trace"]["planes"][0]["lines"][0]
    ops["events"] = [e for e in ops["events"] if "bf16[32,1," not in e[0]]
    assert _reader("latent_decode_roofline").read(run) is None
    assert _reader("latent_attn_share_pct").read(run) \
        == pytest.approx(100 * 0.002 / 0.007)     # the chunk kernel alone
    run["trace"]["counted"]["prefill_attn_pairs"] = 0
    assert _reader("latent_chunk_roofline").read(run) is None
    run["trace"]["trace"]["planes"][0]["lines"][1]["events"] = []
    assert _reader("latent_decode_step_device_ms").read(run) is None
    assert _reader("latent_prefill_chunk_device_ms").read(run) is None
    assert _reader("latent_attn_share_pct").read(run) is None


def test_the_five_are_the_last_entries_and_list_this_cell_alone():
    assert [m["name"] for m in BENCH["per_layer"]][-5:] == list(FIVE)
    for name in FIVE:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} \
            == _reader(name).META
        assert entry["moves"] == "token_gap_p95_ms"
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["workloads"][-1]["chips"] == 1
    # the twins' namesakes stay the Mistral cell's, one cell each
    for name in ("decode_step_device_ms", "prefill_chunk_device_ms",
                 "session_host_ms_per_step", "paged_decode_roofline"):
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [MISTRAL]
    # PR 36's seven keep their three cells: this runner logs them instead
    three = [w["name"] for w in BENCH["workloads"][:4] if w["chips"] == 1]
    assert serve_latent.UNLISTED[3] == "session_host_ms_per_step"
    for name in serve_latent.UNLISTED[4:]:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == three
    # and no listed reader shares a name with one that ships unlisted
    assert not set(FIVE) & (set(serve_hybrid.UNLISTED)
                            | set(serve_window.UNLISTED))
    for name in ("token_gap_p95_ms", "admit_wait_mean_ms", "ttft_p90_ms",
                 "kv_arena_use_pct", "device_idle_pct.chat"):
        entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"][-1] == CELL


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic",
                           "long-doc.json")) as f:
        mix = json.load(f)
    assert mix["prompt_len"] == {"dist": "loguniform", "min": 2048,
                                 "max": 15616}
    assert mix["output_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.6, "min": 16, "max": 768}
    assert mix["shared_prefix"] is None
    assert mix["arrivals"]["process"] == "poisson"
    assert 5 <= mix["ramp"]["seconds"] <= 10 and mix["tail_s"] == 20
    with open(os.path.join(contract.ROOT, "chipbench", "cells",
                           CELL + ".json")) as f:
        cell = json.load(f)
    sc = cell["serve_config"]
    assert sc["decode_buckets"] == [16384] and sc["max_decode_slots"] == 32
    assert sc["prefill_chunk"] == 256 and sc["kv_arena_pages"] == 2048
    # the session the issue names: two prefill rows, one chunk call a step
    assert (sc["prefill_batch"], sc["prefill_chunks_per_step"]) == (2, 1)
    assert not sc["enable_prefix_cache"] and not sc["speculate_k"]
    # every prompt fits its bucket with its longest output
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 16384
    # 0.8 of the knee swept with this session (1.1/s: PERF.md section 4)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(0.8 * 1.1)
    assert cell["check"]["requests"] >= 6
    assert cell["check"]["long_requests"] >= 2
    assert cell["check"]["longer_than"] == 8192 > SIZES["rope_scaling"][
        "original_max_position_embeddings"]


def test_one_order_of_arrivals_every_seed_and_the_ids_the_seeds():
    """The mix's `order_seed` is the generator's own draw under that seed:
    the lengths, the due times and the outputs of every run are its, the
    ids are the run's seed's."""
    from chipbench import traffic_gen

    with open(os.path.join(contract.ROOT, "chipbench", "traffic",
                           "long-doc.json")) as f:
        mix = json.load(f)
    small = dict(mix, prompt_len=dict(mix["prompt_len"], min=8, max=64))
    seeds = (2 ** 31 + 39, 7)
    a, b = (serve_latent.arrival_trace(small, s, 50.0, 20480)
            for s in seeds)
    drawn = traffic_gen.serve_schedule(small, mix["order_seed"], 50.0, 20480)

    def shape(schedule):
        return [(r["due_s"], len(r["prompt"]), r["max_new"], r["phase"])
                for r in schedule["requests"]]

    assert shape(a) == shape(b) == shape(drawn)
    assert sum(r["phase"] == "window" for r in a["requests"]) == 44
    assert [r["prompt"] for r in a["requests"]] \
        != [r["prompt"] for r in b["requests"]]
    again = serve_latent.arrival_trace(small, seeds[0], 50.0, 20480)
    assert [r["prompt"] for r in a["requests"]] \
        == [r["prompt"] for r in again["requests"]]
    assert all(1 <= t < 20480 for r in a["requests"] for t in r["prompt"])


def test_the_numbers_compared_are_the_ones_the_cell_limits():
    """The widest gap, the mean, and the share of served tokens that are
    not the reference's first choice: one key a limit, in the cell and in
    its tiny twin."""
    deficits = [0.0] * 195 + [0.1, 0.2, 0.3, 0.4, 1.0]
    numbers = serve_latent._numbers(deficits)
    assert numbers == {"deficit_max": 1.0,
                       "deficit_mean": pytest.approx(2.0 / 200),
                       "not_first_choice_pct": pytest.approx(2.5)}
    with open(os.path.join(contract.ROOT, "chipbench", "cells",
                           CELL + ".json")) as f:
        cell = json.load(f)
    for check in (cell["check"], cell["rehearse"]["cell"]["check"]):
        assert set(check["limits"]) == set(numbers)


def test_the_sample_holds_two_requests_past_the_original_context():
    finished = [{"req": {"prompt": [1] * n}, "ids": [2] * m}
                for n, m in ((3000, 50), (9000, 50), (2500, 20), (15000, 10),
                             (8000, 300), (12000, 30), (4000, 100),
                             (5000, 60))]
    logged = []
    spec = {"requests": 6, "long_requests": 2, "longer_than": 8192}
    sample = serve_latent.sample_requests(finished, 5, spec, logged.append)
    sizes = [len(r["req"]["prompt"]) + len(r["ids"]) for r in sample]
    assert len(sample) == 6 and len({id(r) for r in sample}) == 6
    assert sizes[0] == 15010 and sizes[1] > 8192        # the two long ones
    assert sum(s > 8192 for s in sizes) >= 2
    assert "2 from 4 finished requests longer than 8192" in logged[0]
    # none long: the six are drawn from what there is
    short = [r for r in finished
             if len(r["req"]["prompt"]) + len(r["ids"]) <= 8192]
    sample = serve_latent.sample_requests(short, 5, dict(spec, requests=3),
                                          logged.append)
    assert len(sample) == 3
