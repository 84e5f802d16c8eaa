"""The plain references against the program's own float32 model code at
tiny sizes, the seeded weights, and the comparison arithmetic.  (The
references import nothing of the program; these tests do.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, traffic_gen, weights
from chipbench.reference import gpt2, mistral

MISTRAL_TINY = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                    head_dim=8, num_attention_heads=4, num_key_value_heads=2,
                    num_hidden_layers=2, rope_theta=1e6, rms_norm_eps=1e-5)
GPT_TINY = dict(n_layer=2, n_embd=40, n_head=5, n_positions=32,
                vocab_size=120, padded_vocab_size=128,
                layer_norm_epsilon=1e-5)


def test_a_seed_past_two_to_the_31_makes_a_key():
    a = weights.seed_key(2 ** 31 + 5)
    b = weights.seed_key(5)
    assert not (np.asarray(a) == np.asarray(b)).all()


def test_mistral_reference_agrees_with_llama_apply():
    from easydist_tpu.models.llama import LlamaConfig, llama_apply

    params = weights.mistral_params(MISTRAL_TINY, weights.seed_key(3),
                                    dtype=jnp.float32)
    cfg = LlamaConfig(vocab=96, seq=24, dim=32, heads=4, kv_heads=2,
                      layers=2, ffn_dim=64, rope_theta=1e6, dtype="float32")
    tokens = np.random.default_rng(0).integers(1, 96, size=24)
    with jax.default_matmul_precision("highest"):
        want = llama_apply(params, cfg, jnp.asarray(tokens)[None])[0]
    got = mistral.logits(params, MISTRAL_TINY, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    rows = mistral.logits(params, MISTRAL_TINY, tokens, rows=[3, 7])
    np.testing.assert_allclose(rows, np.asarray(got)[[3, 7]], rtol=1e-6)


def test_the_fp8_control_moves_the_mistral_logits_and_bf16_barely_does():
    params = weights.mistral_params(MISTRAL_TINY, weights.seed_key(4),
                                    dtype=jnp.float32)
    tokens = np.random.default_rng(1).integers(1, 96, size=24)
    ref = np.asarray(mistral.logits(params, MISTRAL_TINY, tokens))
    low = np.asarray(mistral.logits(params, MISTRAL_TINY, tokens,
                                    quant=True))
    bf16 = np.asarray(mistral.logits(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
        MISTRAL_TINY, tokens))
    assert np.abs(low - ref).max() > 3 * np.abs(bf16 - ref).max() > 0


def test_gpt2_weights_are_the_same_stacked_or_not():
    key = weights.seed_key(9)
    flat = weights.gpt2_params(GPT_TINY, key)
    stacked = weights.gpt2_params(GPT_TINY, key, stacked=True)
    assert len(flat["blocks"]) == 2
    for i, blk in enumerate(flat["blocks"]):
        want = jax.tree.map(lambda a, i=i: a[i], stacked["blocks"])
        for a, b in zip(jax.tree.leaves(blk), jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(flat["wte"], stacked["wte"])


def _program_numbers(key, mix, n_steps, lr, dtype="float32"):
    from easydist_tpu.models import GPTConfig, make_gpt_train_step

    cfg = GPTConfig(vocab=128, seq=32, dim=40, heads=5, layers=2,
                    dtype=dtype)
    step, _ = make_gpt_train_step(cfg, lr=lr)
    params0 = weights.gpt2_params(GPT_TINY, key)
    state = (params0, weights.adam_zeros(params0))
    out = {"losses": []}
    with jax.default_matmul_precision("highest"):
        for i in range(n_steps):
            tok, tgt = traffic_gen.train_batch(mix, 11, i, 120)
            state, loss = jax.jit(step)(state, tok, tgt)
            out["losses"].append(float(loss))
            if i == 0:
                out["grad_norms"] = {
                    k: v / 0.1 for k, v in gpt2.flat_norms(
                        gpt2.leaf_norms(state[1]["mu"])).items()}
    out["delta_norms"] = gpt2.flat_norms(
        gpt2.delta_norms(state[0], params0))
    return out


def test_gpt2_reference_step_agrees_with_the_programs_step():
    from chipbench.runners import train

    mix = {"global_batch": 4, "seq_len": 32}
    program = _program_numbers(weights.seed_key(11), mix, 3, 1e-4)
    reference = train.reference_numbers(GPT_TINY, mix, 11, 3, 1e-4,
                                        jax.devices()[:1])
    numbers = compare.train_numbers(program, reference)
    assert set(program["grad_norms"]) == set(reference["grad_norms"])
    assert "blocks/1/attn/qkv/w" in program["grad_norms"]
    assert max(numbers[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-5
    assert numbers["grad_norm_gap"] < 1e-3
    assert numbers["update_norm_gap"] < 1e-2


def test_the_reference_spreads_over_four_devices_and_says_the_same():
    from chipbench.runners import train

    mix = {"global_batch": 4, "seq_len": 32}
    one = train.reference_numbers(GPT_TINY, mix, 5, 2, 1e-4,
                                  jax.devices()[:1])
    four = train.reference_numbers(GPT_TINY, mix, 5, 2, 1e-4,
                                   jax.devices()[:4])
    np.testing.assert_allclose(one["losses"], four["losses"], rtol=1e-5)


def test_the_fp8_control_is_not_correct_where_the_bf16_program_is():
    """The control at a size a test can hold (width 512, 2 layers, 8 x 128
    tokens), held to limits set the way the cell's are: above what the
    bf16 program reads there, below what the fp8 reference reads (three
    seeds on the CPU: bf16 loss gaps <= 2.3e-5 and gradient gap <= 0.0028;
    fp8 worst loss gap >= 1.4e-4).  It has to fail one number, not each."""
    from easydist_tpu.models import GPTConfig, make_gpt_train_step

    from chipbench.runners import train

    sizes = dict(n_layer=2, n_embd=512, n_head=8, n_positions=128,
                 vocab_size=2000, padded_vocab_size=2048,
                 layer_norm_epsilon=1e-5)
    mix = {"global_batch": 8, "seq_len": 128}
    limits = {"loss_gap_step1": 6e-5, "loss_gap_step2": 6e-5,
              "loss_gap_step3": 6e-5, "grad_norm_gap": 0.01,
              "update_norm_gap": 0.6}
    seed, dev = 3, jax.devices()[:1]

    cfg = GPTConfig(vocab=2048, seq=128, dim=512, heads=8, layers=2,
                    dtype="bfloat16")
    step = jax.jit(make_gpt_train_step(cfg, lr=1e-4)[0])
    params0 = weights.gpt2_params(sizes, weights.seed_key(seed))
    state = (params0, weights.adam_zeros(params0))
    program = {"losses": []}
    for i in range(3):
        tok, tgt = traffic_gen.train_batch(mix, seed, i, 2000)
        state, loss = step(state, tok, tgt)
        program["losses"].append(float(loss))
        if i == 0:
            program["grad_norms"] = {
                k: v / 0.1 for k, v in gpt2.flat_norms(
                    gpt2.leaf_norms(state[1]["mu"])).items()}
    program["delta_norms"] = gpt2.flat_norms(
        gpt2.delta_norms(state[0], params0))

    reference = train.reference_numbers(sizes, mix, seed, 3, 1e-4, dev)
    control = train.reference_numbers(sizes, mix, seed, 3, 1e-4, dev,
                                      quant=True)
    notes = []
    assert compare.training(program, reference, limits,
                            notes.append)["correct"] is True, notes
    assert compare.training(control, reference, limits,
                            notes.append)["correct"] is False, notes


def test_worst_leaf_gap_by_hand():
    want = {"a": 10.0, "b": 2.0, "c": 1e-9}
    got = {"a": 10.5, "b": 2.0, "c": 1e-3}
    # a: 0.5 / 10; c: 1e-3 against the median leaf (2.0), not its own 1e-9
    assert compare.worst_leaf_gap(got, want) == pytest.approx(0.05)
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": 1.0}, want)


def test_sample_requests_keeps_the_longest_and_draws_from_the_seed():
    fin = [{"req": {"prompt": [1] * n}, "ids": [2] * 3} for n in
           (5, 50, 9, 30, 12, 7)]
    a = compare.sample_requests(fin, 2 ** 31 + 1, 3)
    assert len(a) == 3 and len(a[0]["req"]["prompt"]) == 50
    assert a == compare.sample_requests(fin, 2 ** 31 + 1, 3)
    assert compare.sample_requests([], 1, 3) == []
