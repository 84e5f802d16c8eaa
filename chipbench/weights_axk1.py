"""Seeded random weights of A.X-K1 (`axk1`), made on the device a layer at
a time (one jitted call per KIND of FFN, so two compiles) in the type they
are served in.  `models/axk1.py` and `reference/axk1.py` are both given
this tree; neither makes weights of its own.  (`weights.py` is yardstick
and is not edited; its `seed_key` is what turns `--seed` into a key here
too.)

    {"wte" [vocab, hidden], "head" [vocab, hidden], "blocks": [block],
    "norm_f"}; a block: "w_dq" [hidden, q_lora_rank], "q_norm", "w_uq"
    [q_lora_rank, heads * (nope + rope)], "w_dkv" [hidden, kv_lora_rank +
    rope], "kv_norm", "w_ukv" [kv_lora_rank, heads * (nope + v)], "wo"
    [heads * v, hidden], "norm_attn" "norm_ffn" [hidden], and either the
    dense "w1" [hidden, 2 * intermediate] (gate | up), "w2" [intermediate,
    hidden] or "router" [hidden, router_experts], "w1" [held, hidden, 2 *
    moe_intermediate], "w2" [held, moe_intermediate, hidden], "shared_w1",
    "shared_w2" (one shared expert of the same width).

Matrices are normal / sqrt(fan_in), gains 1 + 0.1 normal (so a dropped gain
shows), the embedding normal * 1 and the untied head normal / sqrt(hidden):
the blocks are pre-norm, so the stream is the embedding plus twelve
sublayer outputs of about 0.3-0.6 an element each, the token just read
stays a large part of it, and the logits come out spread about 1 (PERF.md
section 4 says what spread was read on the chip).  A query's score against
a key has a spread of about sqrt(192) * 0.1309 = 1.8 before the softmax, so
attention is neither flat nor one-hot at 16k keys, and leaving m^2 out of
the scale (1.0 then) moves every layer's output.  The held experts are
`experts_held` = [first, how many] of the router's `router_experts`
outputs; `vocab_size` rows of embedding and head are the slice held."""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key  # noqa: F401  (re-exported)


def dims(sizes: dict) -> dict:
    """The shapes the config's keys give."""
    if sizes["n_routed_experts"] != sizes["experts_held"][1] \
            or sizes["n_shared_experts"] != 1 \
            or sizes["first_k_dense_replace"] != 1 \
            or sizes["moe_layer_freq"] != 1 \
            or sizes["topk_method"] != "none" \
            or sizes["rope_scaling"]["type"] != "yarn":
        raise ValueError(
            "the configuration's sizes disagree with what is built: "
            "n_routed_experts must be the experts held, one shared expert, "
            "one leading dense layer, every later layer sparse, plain "
            "top-k routing, YaRN positions")
    return {
        "hidden": sizes["hidden_size"], "vocab": sizes["vocab_size"],
        "layers": sizes["num_hidden_layers"],
        "heads": sizes["num_attention_heads"],
        "q_rank": sizes["q_lora_rank"], "kv_rank": sizes["kv_lora_rank"],
        "nope": sizes["qk_nope_head_dim"], "rope": sizes["qk_rope_head_dim"],
        "v": sizes["v_head_dim"], "dense": sizes["intermediate_size"],
        "expert": sizes["moe_intermediate_size"],
        "experts": sizes["router_experts"],
        "first": sizes["experts_held"][0], "held": sizes["experts_held"][1],
        "top_k": sizes["num_experts_per_tok"],
    }


@functools.partial(jax.jit, static_argnames=("dense", "d", "dtype"))
def _block(key, *, dense, d, dtype):
    d = dict(d)
    hidden = d["hidden"]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=hidden):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    k = jax.random.split(key, 16)
    blk = {"w_dq": mat(k[0], hidden, d["q_rank"]),
           "q_norm": gain(k[1], d["q_rank"]),
           "w_uq": mat(k[2], d["q_rank"],
                       d["heads"] * (d["nope"] + d["rope"])),
           "w_dkv": mat(k[3], hidden, d["kv_rank"] + d["rope"]),
           "kv_norm": gain(k[4], d["kv_rank"]),
           "w_ukv": mat(k[5], d["kv_rank"], d["heads"] * (d["nope"] + d["v"])),
           "wo": mat(k[6], d["heads"] * d["v"], hidden),
           "norm_attn": gain(k[7]), "norm_ffn": gain(k[8])}
    if dense:
        blk.update(w1=mat(k[9], hidden, 2 * d["dense"]),
                   w2=mat(k[10], d["dense"], hidden))
        return blk
    blk.update(router=mat(k[9], hidden, d["experts"]),
               w1=mat(k[10], d["held"], hidden, 2 * d["expert"]),
               w2=mat(k[11], d["held"], d["expert"], hidden),
               shared_w1=mat(k[12], hidden, 2 * d["expert"]),
               shared_w2=mat(k[13], d["expert"], hidden))
    return blk


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, *, vocab, hidden, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (vocab, hidden), jnp.float32).astype(dtype),
            (jax.random.normal(k2, (vocab, hidden), jnp.float32)
             / math.sqrt(hidden)).astype(dtype),
            (1.0 + 0.1 * jax.random.normal(k3, (hidden,), jnp.float32)
             ).astype(dtype))


def axk1_params(sizes: dict, key, dtype=jnp.bfloat16):
    d = dims(sizes)
    n = d["layers"]
    frozen = tuple(sorted(d.items()))
    keys = jax.random.split(key, n + 1)
    blocks = [_block(keys[i], dense=i < sizes["first_k_dense_replace"],
                     d=frozen, dtype=jnp.dtype(dtype)) for i in range(n)]
    wte, head, norm_f = _ends(keys[-1], vocab=d["vocab"], hidden=d["hidden"],
                              dtype=jnp.dtype(dtype))
    return {"wte": wte, "head": head, "blocks": blocks, "norm_f": norm_f}
