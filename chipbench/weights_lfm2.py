"""Seeded random weights of LFM2-MoE (`lfm2_moe`, LFM2-8B-A1B), made on the
device a layer at a time (one jitted call per KIND of layer: mixer x FFN, so
four compiles at most) in the type they are served in.
`models/lfm2_moe.py` and `reference/lfm2_moe.py` are both given this tree;
neither makes weights of its own.  (`weights.py` is yardstick and is not
edited; its `seed_key` is what turns `--seed` into a key here too.)

    {"wte" [vocab, hidden] (the head too: tied), "blocks": [block],
    "norm_f"}; a block: "norm_op" "norm_ffn" [hidden]; the mixer's "w_in"
    [hidden, 3 * hidden] (columns B | C | x~), "conv_w" [taps, hidden] (row
    j multiplies the input taps - 1 - j positions back), "w_out" [hidden,
    hidden], or "wq" [hidden, hidden], "wk" "wv" [hidden, kv heads * head],
    "wo" [hidden, hidden], "q_norm" "k_norm" [head]; and the dense "w1"
    [hidden, 2 * intermediate] (gate | up), "w2" [intermediate, hidden] or
    "router" [hidden, router_experts], "router_bias" [router_experts]
    (float32), "w1" [held, hidden, 2 * moe_intermediate], "w2" [held,
    moe_intermediate, hidden].

Matrices are normal / sqrt(fan_in) and gains 1 + 0.1 normal (so a dropped
gain shows); the conv's taps normal / sqrt(taps).  The block is pre-normed,
so the stream is a sum of sublayer outputs of about 1 an element, and the
tied embedding is normal * 0.02: the logits then spread about 0.02
sqrt(hidden) ~ 1 over the vocabulary, and the row of the token just read is
a small part of what the head sees (at 1 the head would repeat it: PERF.md
section 6, PR 31).  The selection bias is normal * 0.02 — small against the
spread of the scores (sigmoid of a unit normal: 0.2 from end to end of the
middle half), so that it flips a share of the fourth choices and no more.
The held experts are `experts_held` = [first, how many] of the router's
`router_experts` outputs."""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key  # noqa: F401  (re-exported)


def dims(sizes: dict) -> dict:
    """The shapes the config's keys give."""
    n = sizes["num_hidden_layers"]
    if sizes["conv_bias"] or not sizes.get("tie_word_embeddings", True) \
            or not sizes["norm_topk_prob"] or not sizes["use_expert_bias"] \
            or sizes.get("num_shared_experts", 0) \
            or sizes["num_experts"] != sizes["experts_held"][1] \
            or sizes["hidden_size"] % sizes["num_attention_heads"] \
            or len(sizes["layer_types"]) != n \
            or set(sizes["layer_types"]) - {"conv", "full_attention"} \
            or not 0 <= sizes["num_dense_layers"] <= n:
        raise ValueError(
            "the configuration's sizes disagree with what is built: a conv "
            "without a bias, a tied head, chosen scores normalised "
            "(norm_topk_prob), a selection bias, no shared expert, "
            "num_experts the experts held, heads that divide the hidden "
            "size, and layer_types one entry a layer, each `conv` or "
            "`full_attention`")
    return {
        "hidden": sizes["hidden_size"], "vocab": sizes["vocab_size"],
        "q": sizes["num_attention_heads"], "kv": sizes["num_key_value_heads"],
        "hd": sizes["hidden_size"] // sizes["num_attention_heads"],
        "taps": sizes["conv_L_cache"], "dense": sizes["intermediate_size"],
        "expert": sizes["moe_intermediate_size"],
        "experts": sizes["router_experts"],
        "first": sizes["experts_held"][0], "held": sizes["experts_held"][1],
        "top_k": sizes["num_experts_per_tok"],
        "kinds": tuple(sizes["layer_types"]),
        "dense_layers": sizes["num_dense_layers"],
    }


@functools.partial(jax.jit, static_argnames=("kind", "dense", "d", "dtype"))
def _block(key, *, kind, dense, d, dtype):
    d = dict(d)
    hidden = d["hidden"]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=hidden):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    k = jax.random.split(key, 16)
    blk = {"norm_op": gain(k[0]), "norm_ffn": gain(k[1])}
    if kind == "conv":
        blk.update(w_in=mat(k[2], hidden, 3 * hidden),
                   conv_w=mat(k[3], d["taps"], hidden),
                   w_out=mat(k[4], hidden, hidden))
    else:
        blk.update(wq=mat(k[2], hidden, d["q"] * d["hd"]),
                   wk=mat(k[3], hidden, d["kv"] * d["hd"]),
                   wv=mat(k[4], hidden, d["kv"] * d["hd"]),
                   wo=mat(k[5], d["q"] * d["hd"], hidden),
                   q_norm=gain(k[6], d["hd"]), k_norm=gain(k[7], d["hd"]))
    if dense:
        blk.update(w1=mat(k[8], hidden, 2 * d["dense"]),
                   w2=mat(k[9], d["dense"], hidden))
    else:
        blk.update(router=mat(k[8], hidden, d["experts"]),
                   router_bias=0.02 * jax.random.normal(
                       k[9], (d["experts"],), jnp.float32),
                   w1=mat(k[10], d["held"], hidden, 2 * d["expert"]),
                   w2=mat(k[11], d["held"], d["expert"], hidden))
    return blk


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, *, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return ((0.02 * jax.random.normal(k1, (vocab, hidden), jnp.float32)
             ).astype(dtype),
            (1.0 + 0.1 * jax.random.normal(k2, (hidden,), jnp.float32)
             ).astype(dtype))


def lfm2_params(sizes: dict, key, dtype=jnp.bfloat16):
    d = dims(sizes)
    kinds, dense_layers = d.pop("kinds"), d.pop("dense_layers")
    frozen = tuple(sorted(d.items()))
    keys = jax.random.split(key, len(kinds) + 1)
    blocks = [_block(keys[i], kind=kind, dense=i < dense_layers, d=frozen,
                     dtype=jnp.dtype(dtype))
              for i, kind in enumerate(kinds)]
    wte, norm_f = _ends(keys[-1], vocab=d["vocab"], hidden=d["hidden"],
                        dtype=jnp.dtype(dtype))
    return {"wte": wte, "blocks": blocks, "norm_f": norm_f}
