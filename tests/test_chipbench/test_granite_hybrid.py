"""The Granite 4.0-H additions of the benchmark: the plain reference
against the program's own float32 model code at a tiny size, the seeded
weights, the fp8 control, `kernel_costs_hybrid` against counts worked by
hand, and the readers on a hand-made run.  (The reference imports nothing
of the program; these tests do.)"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (contract, hybrid_trace, kernel_costs,
                       kernel_costs_hybrid, weights_granite)
from chipbench.reference import granite_hybrid as reference
from chipbench.runners import serve_hybrid

TINY = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=0.125, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
    mamba_chunk_size=8, router_experts=8, experts_held=[0, 4],
    num_local_experts=4, num_experts_per_tok=2, intermediate_size=16,
    shared_intermediate_size=24, layer_types=["mamba", "attention", "mamba"],
    num_hidden_layers=3, vocab_size=96, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5)
with open(os.path.join(contract.ROOT, "chipbench", "configs",
                       "granite-4.0-h-small.json")) as f:
    SIZES = __import__("json").load(f)


def _reader(name):
    path = os.path.join(contract.ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_same_seed_makes_the_same_weights_and_the_tree_the_model_reads():
    a = weights_granite.granite_params(TINY, weights_granite.seed_key(7),
                                       dtype=jnp.float32)
    b = weights_granite.granite_params(TINY, weights_granite.seed_key(7),
                                       dtype=jnp.float32)
    c = weights_granite.granite_params(TINY, weights_granite.seed_key(8),
                                       dtype=jnp.float32)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a["wte"], c["wte"])
    mamba, attn = a["blocks"][0], a["blocks"][1]
    assert mamba["w_in"].shape == (32, 2 * 64 + 2 * 16 + 4)
    assert mamba["conv_w"].shape == (4, 64 + 32)
    assert mamba["w1"].shape == (4, 32, 32)
    assert mamba["router"].shape == (32, 8)
    assert attn["wq"].shape == (32, 32) and attn["wk"].shape == (32, 16)
    assert "w_in" not in attn and "wq" not in mamba
    # softplus(dt_bias) lands in 1e-3..1e-1, A = -exp(a_log) in -16..-1
    dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    assert (np.exp(mamba["a_log"]) >= 1).all()


def test_the_reference_agrees_with_the_programs_steps():
    """Chunked prefill (chunks of 8) and two decode steps of the program
    in float32 against one full forward of the reference: the two differ
    in the order of sums only."""
    from easydist_tpu.models import granite_hybrid as gh
    from easydist_tpu.models.decoder import Paged, State, chunk, decode

    params = weights_granite.granite_params(TINY, weights_granite.seed_key(3),
                                            dtype=jnp.float32)
    cfg = serve_hybrid.model_config(TINY)
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": "float32"})
    dec = gh.decoder(cfg)
    tokens = np.random.default_rng(0).integers(1, 96, size=22)
    want = np.asarray(reference.logits(params, TINY, tokens))
    cache = {**Paged.init(dec, 4, 8), **State.init(dec, 1)}
    table = jnp.arange(4, dtype=jnp.int32)[None]

    def adapters(cache):
        kv, st = State.split(dec, cache)
        return Paged(kv, table), st

    got = {}
    for start in (0, 8, 16):
        seg = np.zeros((1, 8), np.int32)
        n = min(8, 20 - start)
        seg[0, :n] = tokens[start:start + n]
        kv, leaves = adapters(cache)
        st = State(leaves, jnp.asarray([True]), jnp.asarray([0]),
                   fresh=jnp.asarray([start == 0]))
        cache, logits = chunk(dec, kv, params, jnp.asarray(seg),
                              jnp.asarray([start]), jnp.asarray([20]),
                              state=st)
    got[19] = np.asarray(logits[0])
    for pos in (20, 21):
        kv, leaves = adapters(cache)
        cache, logits = decode(dec, kv, params, jnp.asarray([tokens[pos]]),
                               jnp.asarray([pos]),
                               state=State(leaves, jnp.asarray([True])))
        got[pos] = np.asarray(logits[0])
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], rtol=2e-4,
                                   atol=2e-5 * want.std())
    rows = reference.logits(params, TINY, tokens, rows=[3, 7])
    np.testing.assert_allclose(rows, want[[3, 7]], rtol=1e-6)


def test_the_fp8_control_moves_the_logits_and_bf16_barely_does():
    params = weights_granite.granite_params(TINY, weights_granite.seed_key(4),
                                            dtype=jnp.float32)
    tokens = np.random.default_rng(1).integers(1, 96, size=24)
    ref = np.asarray(reference.logits(params, TINY, tokens))
    low = np.asarray(reference.logits(params, TINY, tokens, quant=True))
    bf16 = np.asarray(reference.logits(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), TINY, tokens))
    assert np.abs(low - ref).max() > 3 * np.abs(bf16 - ref).max() > 0


def test_a_layer_left_out_of_the_reference_moves_the_logits_by_their_spread():
    """What the comparison can see: a model whose logits were all but the
    tied head's self term would hide everything else."""
    params = weights_granite.granite_params(TINY, weights_granite.seed_key(4),
                                            dtype=jnp.float32)
    tokens = np.random.default_rng(2).integers(1, 96, size=24)
    ref = np.asarray(reference.logits(params, TINY, tokens))
    cut = dict(params, blocks=params["blocks"][:2])
    less = np.asarray(reference.logits(
        cut, dict(TINY, layer_types=TINY["layer_types"][:2]), tokens))
    assert np.abs(less - ref).max() > ref.std()
    assert (ref.argmax(-1) != tokens).mean() > 0.5     # not an echo


def test_the_config_file_is_the_catalogs_with_the_cut_written_out():
    d = weights_granite.dims(SIZES)
    assert (d["hidden"], d["q"], d["kv"], d["hd"]) == (4096, 32, 8, 128)
    assert (d["heads"], d["p"], d["n"], d["d_conv"]) == (128, 64, 128, 4)
    assert (d["d_inner"], d["conv"]) == (8192, 8448)
    assert (d["experts"], d["held"], d["top_k"]) == (72, 36, 10)
    assert (d["expert"], d["shared"], d["vocab"]) == (768, 1536, 50176)
    assert d["kinds"].count("mamba") == 9 and d["kinds"][5] == "attention"
    assert SIZES["published"]["num_local_experts"] == 72
    assert set(SIZES["reduced"]) == {"num_hidden_layers", "layer_types",
                                     "num_local_experts", "vocab_size"}
    # bf16 bytes of what the file describes: 9.51 GB of weights
    per = lambda kind: (  # noqa: E731
        2 * 4096 + 4096 * 72 + 36 * 3 * 4096 * 768 + 3 * 4096 * 1536
        + (2 * 4096 * 4096 + 2 * 4096 * 1024 if kind == "attention" else
           4096 * 16768 + 5 * 8448 + 3 * 128 + 8192 + 8192 * 4096))
    total = sum(per(k) for k in d["kinds"]) + 50176 * 4096 + 4096
    assert 9.45e9 < 2 * total < 9.56e9


def test_kernel_costs_hybrid_by_hand():
    s = SIZES
    # one expert: 4096 x 1536 in, 768 x 4096 back = 9,437,184 parameters
    assert kernel_costs_hybrid.expert_params(s) == 9_437_184
    # 320 pairs: 2 FLOPs a parameter a pair
    assert kernel_costs_hybrid.expert_ffn_flops(320, s) \
        == 2 * 320 * 9_437_184
    # 36 experts hit, bf16: their weights once, and per pair a 4096 row in,
    # 1536 out and 768 in between the products, a 4096 row out
    assert kernel_costs_hybrid.expert_ffn_bytes(320, 36, s) \
        == 2 * (36 * 9_437_184 + 320 * (4096 + 1536 + 768 + 4096))
    # no expert hit, no pair: nothing is needed
    assert kernel_costs_hybrid.expert_ffn_bytes(0, 0, s) == 0
    # one sequence's state in one layer: 128 x 64 x 128 float32 = 4 MiB
    assert kernel_costs_hybrid.ssm_state_bytes(s) == 4_194_304
    # 40 live rows, 9 layers: read and written
    assert kernel_costs_hybrid.ssm_update_bytes(40, 9, s) \
        == 2 * 40 * 9 * 4_194_304
    assert kernel_costs_hybrid.ssm_update_flops(40, 9, s) \
        == 5 * 40 * 9 * 1_048_576
    # a round of 64 rows with every expert hit is bound by bytes on the
    # v5e, and so is one layer of a 1024-token chunk call (36 experts'
    # weights against 5,120 pairs); ten times the pairs are bound by FLOPs
    peak = kernel_costs.peaks("TPU v5 lite")
    for pairs, hit, bound in ((320, 36, "bytes"), (5120, 36, "bytes"),
                              (51200, 36, "flops")):
        assert kernel_costs.roofline_seconds(
            kernel_costs_hybrid.expert_ffn_flops(pairs, s),
            kernel_costs_hybrid.expert_ffn_bytes(pairs, hit, s),
            peak)[1] == bound
    assert kernel_costs.roofline_seconds(
        kernel_costs_hybrid.ssm_update_flops(64, 9, s),
        kernel_costs_hybrid.ssm_update_bytes(64, 9, s), peak)[1] == "bytes"
    # the chunked scan: per token and layer 128 x 256 + 8192 x 256 + 4 x
    # 8192 x 128 FLOPs at blocks of 256
    assert kernel_costs_hybrid.ssd_scan_flops(1000, 9, s, 256) \
        == 1000 * 9 * (128 * 256 + 8192 * 256 + 4 * 8192 * 128)


def _hand_made_run():
    """Two rounds and one chunk call: the expert kernel 3 ms, the state
    kernel 2 ms, the attention kernel 1 ms, of 10 ms busy."""
    ms = 1_000_000
    ops = [
        ["%_decode_paged_state.1 custom-call tpu_custom_call "
         "bf16[1792,1536]", 0, 1 * ms],
        ["%_decode_paged_state.2 custom-call tpu_custom_call "
         "bf16[1792,4096]", 1 * ms, 1 * ms],
        ["%_prefill_chunk_paged_state.3 custom-call tpu_custom_call "
         "bf16[14848,1536]", 2 * ms, 1 * ms],
        ["%_decode_paged_state.4 custom-call tpu_custom_call "
         "f32[64,128,64,128]", 3 * ms, 2 * ms],
        ["%_decode_paged_state.5 custom-call tpu_custom_call "
         "bf16[64,8,4,128]", 5 * ms, 1 * ms],
        ["%fusion.7 fusion", 6 * ms, 4 * ms],
    ]
    modules = [["jit__decode_paged_state(1)", 0, 4 * ms],
               ["jit__prefill_chunk_paged_state(2)", 4 * ms, 2 * ms],
               ["jit__decode_paged_state(1)", 6 * ms, 3 * ms],
               ["jit__decode_paged_state(1)", 9 * ms, 1 * ms]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    counted = {"moe_rounds": 2, "moe_pairs_routed": 6400,
               "moe_experts_hit": 720, "moe_max_expert_pairs": 400,
               "moe_prefill_calls": 1, "moe_prefill_pairs_routed": 51200,
               "moe_prefill_experts_hit": 360, "tokens_generated": 100,
               "prefill_chunks": 1}
    return {"trace": {"trace": trace, "window_s": 0.02, "counted": counted},
            "busy": {"busy_s": 0.010, "per_chip_s": [0.010]},
            "sizes": SIZES, "device_kind": "TPU v5 lite", "chips": 1,
            "serve": {"state_slots_in_use": [32, 48, 40], "state_slots": 64}}


def test_the_readers_on_a_hand_made_run():
    run = _hand_made_run()
    assert hybrid_trace.seconds(run, hybrid_trace.EXPERT_MATMUL) \
        == pytest.approx(0.003)
    assert hybrid_trace.seconds(run, hybrid_trace.STATE_UPDATE) \
        == pytest.approx(0.002)
    assert hybrid_trace.state_layers(SIZES) == 9
    assert _reader("expert_ffn_share_pct").read(run) == pytest.approx(30.0)
    assert _reader("ssm_update_share_pct").read(run) == pytest.approx(20.0)
    # each class at its own bound: the rounds' 720 expert-layers hit and
    # the chunk call's 360, both by bytes (the call's 0.966 TFLOP would
    # take 4.9 ms, its 7.9 GB take 9.6)
    dec_bytes = 2 * (720 * 9_437_184 + 6400 * 10_496)
    pre_bytes = 2 * (360 * 9_437_184 + 51_200 * 10_496)
    assert pre_bytes / 819e9 > 2 * 51_200 * 9_437_184 / 197e12
    least = dec_bytes / 819e9 + pre_bytes / 819e9
    assert _reader("expert_ffn_roofline").read(run) \
        == pytest.approx(100 * least / 0.003)
    assert _reader("ssm_decode_roofline").read(run) == pytest.approx(
        100 * (2 * 100 * 9 * 4_194_304 / 819e9) / 0.002)
    assert _reader("state_pool_use_pct").read(run) == pytest.approx(62.5)
    # the busiest expert's 400 pairs over the mean 6400 / 36
    assert _reader("expert_load_max_over_mean").read(run) \
        == pytest.approx(400 * 36 / 6400)
    assert _reader("hybrid_decode_step_device_ms").read(run) \
        == pytest.approx(3.0)
    assert _reader("hybrid_prefill_chunk_device_ms").read(run) \
        == pytest.approx(2.0)


def test_a_reader_that_cannot_find_its_op_returns_none():
    run = _hand_made_run()
    run["trace"]["trace"]["planes"][0]["lines"][0]["events"] = [
        ["%fusion.7 fusion", 0, 1000]]
    for name in ("expert_ffn_share_pct", "expert_ffn_roofline",
                 "ssm_update_share_pct", "ssm_decode_roofline"):
        assert _reader(name).read(run) is None
    del run["trace"]["counted"]      # a program without the counters
    assert _reader("expert_load_max_over_mean").read(run) is None
