"""Seeded random weights of Olmo Hybrid (`olmo_hybrid`), made on the device
a layer at a time (one jitted call per KIND of layer, so two compiles) in
the type they are served in.  `models/olmo_hybrid.py` and
`reference/olmo_hybrid.py` are both given this tree; neither makes weights
of its own.  (`weights.py` is yardstick and is not edited; its `seed_key`
is what turns `--seed` into a key here too.)

    {"wte" [vocab, hidden], "head" [vocab, hidden], "blocks": [block],
    "norm_f"}; a block: "norm_attn" "norm_ffn" [hidden], "w1" [hidden, 2 *
    intermediate] (gate | up), "w2" [intermediate, hidden], and either "wq"
    "wk" "wv" "wo" [hidden, hidden], "q_norm" "k_norm" [hidden] or the
    delta-rule mixer's "w_qkv" [hidden, heads * (2 * key + value)] (columns
    q~ | k~ | v~), "conv_w" [taps, the same width] (row j multiplies the
    input taps - 1 - j positions back; no bias), "w_ab" [hidden, 2 * heads]
    (columns a | b), "a_log" "dt_bias" [heads] (float32), "w_gate" [hidden,
    heads * value], "norm_gate" [value], "w_out" [heads * value, hidden].

Matrices are normal / sqrt(fan_in), gains 1 + 0.1 normal (so a dropped gain
shows), the embedding normal * 1 and the untied head normal / sqrt(hidden):
every sublayer's output is normed to about 1 an element before it is added,
so the embedding has to be of that size for the token just read to stay in
the stream, and the logits come out spread about 1 (PERF.md section 4 says
what spread was read on the chip).  `a_log` = log(uniform(1, 16)) and
`dt_bias` such that softplus lands log-uniformly in 1e-3..1e-1: a head's
decay a token is exp(-A softplus(x W_a + dt_bias)), from all but none to a
few tenths, so the state carries from a handful to hundreds of positions
by the head, and a correction lost early is still in the output late."""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key  # noqa: F401  (re-exported)


def dims(sizes: dict) -> dict:
    """The shapes the config's keys give."""
    if sizes["linear_num_key_heads"] != sizes["linear_num_value_heads"] \
            or sizes["hidden_size"] % sizes["num_attention_heads"] \
            or sizes["tie_word_embeddings"] or sizes["attention_bias"] \
            or sizes["rope_parameters"]["rope_theta"] is not None:
        raise ValueError(
            "the configuration's sizes disagree with what is built: as "
            "many key heads as value heads on the delta-rule layers, heads "
            "that divide the hidden size, an untied head, no attention "
            "bias, no rotary positions")
    layers = sizes["num_hidden_layers"]
    return {
        "hidden": sizes["hidden_size"], "vocab": sizes["vocab_size"],
        "ffn": sizes["intermediate_size"],
        "q": sizes["num_attention_heads"], "kv": sizes["num_key_value_heads"],
        "hd": sizes["hidden_size"] // sizes["num_attention_heads"],
        "heads": sizes["linear_num_value_heads"],
        "dk": sizes["linear_key_head_dim"],
        "dv": sizes["linear_value_head_dim"],
        "taps": sizes["linear_conv_kernel_dim"],
        "kinds": tuple(sizes["layer_types"][:layers]),
    }


@functools.partial(jax.jit, static_argnames=("kind", "d", "dtype"))
def _block(key, *, kind, d, dtype):
    d = dict(d)
    hidden, h = d["hidden"], d["heads"]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=hidden):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    k = jax.random.split(key, 16)
    blk = {"norm_attn": gain(k[0]), "norm_ffn": gain(k[1]),
           "w1": mat(k[2], hidden, 2 * d["ffn"]),
           "w2": mat(k[3], d["ffn"], hidden)}
    if kind == "full_attention":
        blk.update(wq=mat(k[4], hidden, d["q"] * d["hd"]),
                   wk=mat(k[5], hidden, d["kv"] * d["hd"]),
                   wv=mat(k[6], hidden, d["kv"] * d["hd"]),
                   wo=mat(k[7], d["q"] * d["hd"], hidden),
                   q_norm=gain(k[8], d["q"] * d["hd"]),
                   k_norm=gain(k[9], d["kv"] * d["hd"]))
        return blk
    conv = h * (2 * d["dk"] + d["dv"])
    dt = jnp.exp(jax.random.uniform(k[10], (h,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    blk.update(
        w_qkv=mat(k[4], hidden, conv), conv_w=mat(k[5], d["taps"], conv),
        w_ab=mat(k[6], hidden, 2 * h),
        a_log=jnp.log(jax.random.uniform(k[7], (h,), jnp.float32, 1.0, 16.0)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        w_gate=mat(k[8], hidden, h * d["dv"]),
        norm_gate=gain(k[9], d["dv"]),
        w_out=mat(k[11], h * d["dv"], hidden))
    return blk


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, *, vocab, hidden, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (vocab, hidden), jnp.float32).astype(dtype),
            (jax.random.normal(k2, (vocab, hidden), jnp.float32)
             / math.sqrt(hidden)).astype(dtype),
            (1.0 + 0.1 * jax.random.normal(k3, (hidden,), jnp.float32)
             ).astype(dtype))


def olmo_params(sizes: dict, key, dtype=jnp.bfloat16):
    d = dims(sizes)
    kinds = d.pop("kinds")
    frozen = tuple(sorted(d.items()))
    keys = jax.random.split(key, len(kinds) + 1)
    blocks = [_block(keys[i], kind=kind, d=frozen, dtype=jnp.dtype(dtype))
              for i, kind in enumerate(kinds)]
    wte, head, norm_f = _ends(keys[-1], vocab=d["vocab"], hidden=d["hidden"],
                              dtype=jnp.dtype(dtype))
    return {"wte": wte, "head": head, "blocks": blocks, "norm_f": norm_f}
