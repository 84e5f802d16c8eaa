"""The K-EXAONE additions of the benchmark: the plain reference against an
even plainer one written here (a loop over positions) and against its own
masks, the seeded weights, the fp8 control, the configuration file against
the catalog's numbers, `kernel_costs_window` against counts worked by hand,
and the new readers on a hand-made run.  (The reference imports nothing of
the program; `tests/test_models/test_exaone_moe.py` holds the program to
it.)"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, kernel_costs_window, weights_exaone
from chipbench.reference import exaone_moe as reference
from chipbench.runners import serve_window

TINY = dict(
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
with open(os.path.join(contract.ROOT, "chipbench", "configs",
                       "k-exaone-236b-a23b.json")) as f:
    SIZES = json.load(f)


def _reader(name):
    path = os.path.join(contract.ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return weights_exaone.exaone_params(TINY, weights_exaone.seed_key(4),
                                        dtype=jnp.float32)


def test_the_same_seed_makes_the_same_weights_and_the_tree_the_model_reads():
    a, b, c = (weights_exaone.exaone_params(
        TINY, weights_exaone.seed_key(s), dtype=jnp.float32)
        for s in (7, 7, 2 ** 31 + 5))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a["wte"], c["wte"])
    dense, sparse = a["blocks"][0], a["blocks"][1]
    assert dense["w1"].shape == (32, 96) and dense["w2"].shape == (48, 32)
    assert "router" not in dense
    assert sparse["router"].shape == (32, 8)
    assert sparse["router_bias"].shape == (8,)
    assert sparse["w1"].shape == (4, 32, 32)
    assert sparse["shared_w2"].shape == (16, 32)
    assert sparse["wq"].shape == (32, 32) and sparse["wk"].shape == (32, 16)
    assert sparse["q_norm"].shape == (8,)
    assert a["head"].shape == a["wte"].shape == (96, 32)
    assert not np.array_equal(a["head"], a["wte"])          # untied
    # the bias is small beside the scores' spread, and not nothing
    assert 0 < np.abs(np.asarray(sparse["router_bias"])).max() < 0.05
    with pytest.raises(ValueError, match="sizes disagree"):
        weights_exaone.dims(dict(TINY, sliding_windows=[8, 8, 8, 8]))
    with pytest.raises(ValueError, match="sizes disagree"):
        weights_exaone.dims(dict(TINY, num_experts=8))


def _by_position(params, sizes, tokens):
    """The equations of ISSUE 33 a position at a time, in float64 numpy:
    nothing of `reference` but its weights' layout."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    hd, n_q, n_kv = sizes["head_dim"], 4, 2
    eps, window = sizes["rms_norm_eps"], sizes["sliding_window"]

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def silu(x):
        return x / (1 + np.exp(-x))

    def glu(u, w1, w2):
        ab = u @ w1
        return (silu(ab[:ab.size // 2]) * ab[ab.size // 2:]) @ w2

    def rope(x, pos):
        half = hd // 2
        ang = pos / (1e6 ** (np.arange(half) / half))
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    h = p["wte"][np.asarray(tokens)]
    for kind, blk in zip(sizes["layer_types"], p["blocks"]):
        sliding = kind == "sliding_attention"
        q = norm((h @ blk["wq"]).reshape(-1, n_q, hd), blk["q_norm"])
        k = norm((h @ blk["wk"]).reshape(-1, n_kv, hd), blk["k_norm"])
        v = (h @ blk["wv"]).reshape(-1, n_kv, hd)
        out = np.zeros_like(h)
        for i in range(len(h)):
            lo = max(0, i - window + 1) if sliding else 0
            att = []
            for head in range(n_q):
                qi = rope(q[i, head], i) if sliding else q[i, head]
                ks = np.stack([rope(k[j, head // 2], j) if sliding
                               else k[j, head // 2]
                               for j in range(lo, i + 1)])
                s = ks @ qi / np.sqrt(hd)
                w = np.exp(s - s.max())
                att.append((w / w.sum()) @ v[lo:i + 1, head // 2])
            a = np.concatenate(att) @ blk["wo"]
            x = h[i] + norm(a, blk["norm_attn"])
            if "router" in blk:
                s = 1 / (1 + np.exp(-(x @ blk["router"])))
                chosen = np.argsort(-(s + blk["router_bias"]))[:2]
                f = glu(x, blk["shared_w1"], blk["shared_w2"])
                for e in chosen:
                    if e < 4:      # held: experts 0-3
                        f = f + 2.5 * s[e] / (s[chosen].sum() + 1e-20) \
                            * glu(x, blk["w1"][e], blk["w2"][e])
            else:
                f = glu(x, blk["w1"], blk["w2"])
            out[i] = x + norm(f, blk["norm_ffn"])
        h = out
    return norm(h, p["norm_f"]) @ p["head"].T


def test_the_reference_is_the_equations_a_position_at_a_time(params):
    tokens = np.random.default_rng(0).integers(1, 96, size=29)
    want = _by_position(params, TINY, tokens)
    got = np.asarray(reference.logits(params, TINY, tokens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * want.std())
    rows = reference.logits(params, TINY, tokens, rows=[3, 27])
    np.testing.assert_allclose(rows, got[[3, 27]], rtol=1e-6)


def test_the_window_and_the_missing_rope_are_seen_by_the_logits(params):
    """What the comparison can see: past the window the logits move by a
    large part of their spread when the mask is a position off, and so they
    do when the full layer is given rotary positions."""
    tokens = np.random.default_rng(1).integers(1, 96, size=40)
    ref = np.asarray(reference.logits(params, TINY, tokens))
    wide = np.asarray(reference.logits(
        params, dict(TINY, sliding_window=9), tokens))
    np.testing.assert_allclose(wide[:8], ref[:8], atol=1e-5)   # inside it
    assert np.abs(wide[9:] - ref[9:]).max() > 0.1 * ref.std()
    roped = np.asarray(reference.logits(
        params, dict(TINY, layer_types=["sliding_attention"] * 4,
                     sliding_window=10 ** 6), tokens))
    assert np.abs(roped - ref).max() > 0.1 * ref.std()
    assert (ref.argmax(-1) != tokens).mean() > 0.5     # not an echo
    assert len(set(ref.argmax(-1).tolist())) > 10      # nor one token


def test_the_fp8_control_moves_the_logits_and_bf16_barely_does(params):
    tokens = np.random.default_rng(1).integers(1, 96, size=24)
    ref = np.asarray(reference.logits(params, TINY, tokens))
    low = np.asarray(reference.logits(params, TINY, tokens, quant=True))
    bf16 = np.asarray(reference.logits(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), TINY, tokens))
    assert np.abs(low - ref).max() > 3 * np.abs(bf16 - ref).max() > 0


def test_the_config_file_is_the_catalogs_with_the_cut_written_out():
    d = weights_exaone.dims(SIZES)
    assert (d["hidden"], d["q"], d["kv"], d["hd"]) == (6144, 64, 8, 128)
    assert (d["dense"], d["expert"], d["top_k"]) == (18432, 2048, 8)
    assert (d["experts"], d["first"], d["held"]) == (128, 0, 16)
    assert d["vocab"] == 19200 and d["mlp"] == ("dense",) + ("sparse",) * 4
    assert SIZES["sliding_window"] == 128
    assert SIZES["rope_parameters"]["rope_theta"] == 1e6
    assert SIZES["routed_scaling_factor"] == 2.5
    assert SIZES["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert SIZES["published"]["num_experts"] == 128
    assert SIZES["published"]["vocab_size"] == 153600
    assert SIZES["published"]["num_hidden_layers"] == 48
    assert set(SIZES["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert "v5e-64" in SIZES["deployment"]
    # bf16 bytes of what the file describes: 7.42 GB of weights
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024 + 2 * 128 + 2 * 6144
    expert = 3 * 6144 * 2048
    sparse = attn + 6144 * 128 + 128 * 2 + 17 * expert  # bias is float32
    total = attn + 3 * 6144 * 18432 + 4 * sparse + 2 * 19200 * 6144 + 6144
    assert 7.41e9 < 2 * total < 7.43e9
    shapes = jax.eval_shape(lambda k: weights_exaone.exaone_params(SIZES, k),
                            jax.random.PRNGKey(0))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) \
        == 2 * total


def test_kernel_costs_window_by_hand():
    s = SIZES
    assert kernel_costs_window.full_layers(s) == 1
    assert kernel_costs_window.sliding_layers(s) == 4
    # one position in one layer: K and V, 8 heads of 128, bf16 = 4 KiB
    assert kernel_costs_window.kv_token_bytes(s) == 4096
    # the rings: 64 slots x 4 layers x 128 rows x 4 KiB = 128 MiB, at any
    # length; a window of 100 would round up to 104 rows
    assert kernel_costs_window.ring_bytes(64, s) == 134_217_728
    assert kernel_costs_window.ring_bytes(1, dict(s, sliding_window=100)) \
        == 4 * 104 * 4096
    # a page of 256 tokens: the ONE full layer's rows = 1 MiB
    assert kernel_costs_window.paged_bytes(256, s) == 1_048_576
    # a round over 64 rows holding 100,000 tokens: 409.6 MB of K and V and
    # 2 x 64 x 64 x 128 x 2 B of q and o, once (one full layer)
    assert kernel_costs_window.full_decode_bytes(100_000, 64, s) \
        == 100_000 * 4096 + 2_097_152
    assert kernel_costs_window.full_decode_flops(100_000, s) \
        == 4 * 100_000 * 64 * 128
    two = dict(s, num_hidden_layers=8, layer_types=s["layer_types"][:4] * 2)
    assert kernel_costs_window.full_decode_bytes(1000, 64, two) \
        == 2 * (1000 * 4096 + 2_097_152)


def _hand_made_run():
    """Two rounds and one chunk call: the expert kernel 3 ms, the full
    layer's decode kernel 1 ms a round, of 10 ms busy."""
    ms = 1_000_000
    ops = [
        ["%_decode_paged_state.1 custom-call tpu_custom_call "
         "bf16[768,4096]", 0, 1 * ms],
        ["%_decode_paged_state.2 custom-call tpu_custom_call "
         "bf16[768,6144]", 1 * ms, 1 * ms],
        ["%_prefill_chunk_paged_state.3 custom-call tpu_custom_call "
         "bf16[6144,4096]", 2 * ms, 1 * ms],
        ["%_decode_paged_state.5 custom-call tpu_custom_call "
         "bf16[64,8,8,128]", 5 * ms, 1 * ms],
        ["%_decode_paged_state.5 custom-call tpu_custom_call "
         "bf16[64,8,8,128]", 8 * ms, 1 * ms],
        ["%fusion.7 fusion", 6 * ms, 2 * ms],
    ]
    modules = [["jit__decode_paged_state(1)", 0, 4 * ms],
               ["jit__prefill_chunk_paged_state(2)", 4 * ms, 2 * ms],
               ["jit__decode_paged_state(1)", 6 * ms, 3 * ms],
               ["jit__decode_paged_state(1)", 9 * ms, 1 * ms]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    counted = {"moe_rounds": 2, "moe_pairs_routed": 512,
               "moe_experts_hit": 120, "moe_max_expert_pairs": 48,
               "moe_prefill_calls": 1, "moe_prefill_pairs_routed": 2048,
               "moe_prefill_experts_hit": 64, "tokens_generated": 100,
               "prefill_chunks": 1}
    return {"trace": {"trace": trace, "window_s": 0.02, "counted": counted,
                      "decode_calls": [100_000, 120_000]},
            "busy": {"busy_s": 0.010, "per_chip_s": [0.010]},
            "sizes": SIZES, "device_kind": "TPU v5 lite", "chips": 1,
            "cell": {"serve_config": {"max_decode_slots": 64}},
            "serve": {"ring_slots_in_use": [32, 48], "ring_slots": 64}}


def test_the_new_readers_on_a_hand_made_run():
    run = _hand_made_run()
    # two rounds' K and V and q, o at 819 GB/s over the kernel's 2 ms
    least = (220_000 * 4096 + 2 * 2_097_152) / 819e9
    assert _reader("full_attn_decode_roofline").read(run) \
        == pytest.approx(100 * least / 0.002)
    assert _reader("window_decode_step_device_ms").read(run) \
        == pytest.approx(3.0)
    assert _reader("window_prefill_chunk_device_ms").read(run) \
        == pytest.approx(2.0)
    # the Granite cell's expert readers, fed an expert's width under
    # their key: 37,748,736 parameters an expert
    run["sizes"] = dict(SIZES, intermediate_size=2048)
    assert _reader("expert_ffn_share_pct").read(run) == pytest.approx(30.0)
    dec = 2 * (120 * 37_748_736 + 512 * (2 * 6144 + 3 * 2048))
    pre = 2 * (64 * 37_748_736 + 2048 * (2 * 6144 + 3 * 2048))
    assert pre / 819e9 > 2 * 2048 * 37_748_736 / 197e12   # bytes bind both
    assert _reader("expert_ffn_roofline").read(run) == pytest.approx(
        100 * (dec + pre) / 819e9 / 0.003)
    assert _reader("expert_load_max_over_mean").read(run) \
        == pytest.approx(48 * 16 / 512)


def test_a_new_reader_that_finds_nothing_returns_none():
    run = _hand_made_run()
    for name in serve_window.UNLISTED:
        assert _reader(name).META["moves"] == "token_gap_p95_ms"
        assert _reader(name).read({"chips": 1}) is None
    run["trace"]["trace"]["planes"][0]["lines"][0]["events"] = [
        ["%fusion.7 fusion", 0, 1000]]
    assert _reader("full_attn_decode_roofline").read(run) is None
    run["trace"]["trace"]["planes"][0]["lines"][1]["events"] = []
    assert _reader("window_decode_step_device_ms").read(run) is None
    assert _reader("window_prefill_chunk_device_ms").read(run) is None


def test_the_sample_prefers_requests_that_leave_the_window():
    finished = [{"req": {"prompt": [1] * n}, "ids": [2] * m}
                for n, m in ((10, 5), (400, 50), (90, 20), (1000, 10),
                             (380, 3), (200, 300))]
    logged = []
    sample = serve_window.sample_requests(finished, 5, 3, 384, logged.append)
    assert [len(r["req"]["prompt"]) for r in sample][0] == 1000   # longest
    assert all(len(r["req"]["prompt"]) + len(r["ids"]) > 384 for r in sample)
    assert "3 from 3 finished requests longer than 384" in logged[0]
    # too few long ones: topped up from the others, all the long ones kept
    sample = serve_window.sample_requests(finished, 5, 5, 384, logged.append)
    assert len(sample) == 5 and sum(
        len(r["req"]["prompt"]) + len(r["ids"]) > 384 for r in sample) == 3
