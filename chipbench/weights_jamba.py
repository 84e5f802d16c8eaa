"""Seeded random weights of Jamba (`jamba`, the dense Jamba2-3B reading),
made on the device a layer at a time (one jitted call per KIND of layer, so
two compiles) in the type they are served in.  `models/jamba.py` and
`reference/jamba.py` are both given this tree; neither makes weights of its
own.  (`weights.py` is yardstick and is not edited; its `seed_key` is what
turns `--seed` into a key here too.)

    {"wte" [vocab, hidden] (the head too: tied), "blocks": [block],
    "norm_f"}; a block: "norm_in" "norm_ff" [hidden], "w1" [hidden, 2 *
    intermediate] (gate | up), "w2" [intermediate, hidden], and either "wq"
    [hidden, hidden], "wk" "wv" [hidden, kv heads * head], "wo" [hidden,
    hidden] or the selective mixer's "w_in" [hidden, 2 * inner] (columns x~
    | z), "conv_w" [taps, inner] (row j multiplies the input taps - 1 - j
    positions back), "conv_b" [inner], "w_x" [inner, rank + 2 * state]
    (columns dt~ | B | C), "norm_dt" [rank], "norm_b" "norm_c" [state],
    "w_dt" [rank, inner], "dt_bias" [inner], "a_log" [state, inner] (the
    channels on the lanes, as the state is stored), "d_skip" [inner] (the
    last three float32), "w_out" [inner, hidden].

Matrices are normal / sqrt(fan_in) and gains 1 + 0.1 normal (so a dropped
gain shows).  The block is pre-normed, so the stream is a sum of sublayer
outputs of about 1 an element, and the tied embedding is normal * 0.02: the
logits then spread about 0.02 sqrt(hidden) ~ 1 over the vocabulary, and the
row of the token just read is a small part of what the head sees (at 1 the
head would repeat it: PERF.md section 4 says what spread and how many
distinct tokens were read on the chip).  `a_log[n, d]` = log(n + 1)
(S4D-real), `dt_bias` such that softplus lands log-uniformly in 1e-3..1e-1
and `d_skip` 1: a (channel, index) pair's decay a token is exp(-(n + 1)
softplus(dt~ W_dt + dt_bias)), from e^-0.001 to e^-1.6 and beyond, so the
state carries from one to hundreds of positions by the pair, and what a
chunk boundary or a dead row's update loses early is still in the output
late."""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.jamba import kinds
from chipbench.weights import seed_key  # noqa: F401  (re-exported)


def dims(sizes: dict) -> dict:
    """The shapes the config's keys give."""
    if sizes["hidden_size"] % sizes["num_attention_heads"] \
            or not sizes["tie_word_embeddings"] or sizes["num_experts"] != 1 \
            or sizes["mamba_proj_bias"] or not sizes["mamba_conv_bias"] \
            or sizes["sliding_window"] is not None:
        raise ValueError(
            "the configuration's sizes disagree with what is built: heads "
            "that divide the hidden size, a tied head, one expert (a plain "
            "MLP), a conv with a bias, projections without, no window")
    return {
        "hidden": sizes["hidden_size"], "vocab": sizes["vocab_size"],
        "ffn": sizes["intermediate_size"],
        "q": sizes["num_attention_heads"], "kv": sizes["num_key_value_heads"],
        "hd": sizes["hidden_size"] // sizes["num_attention_heads"],
        "inner": sizes["mamba_expand"] * sizes["hidden_size"],
        "state": sizes["mamba_d_state"], "rank": sizes["mamba_dt_rank"],
        "taps": sizes["mamba_d_conv"],
        "kinds": kinds(sizes),
    }


@functools.partial(jax.jit, static_argnames=("kind", "d", "dtype"))
def _block(key, *, kind, d, dtype):
    d = dict(d)
    hidden, e, n, r = d["hidden"], d["inner"], d["state"], d["rank"]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=hidden):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    k = jax.random.split(key, 16)
    blk = {"norm_in": gain(k[0]), "norm_ff": gain(k[1]),
           "w1": mat(k[2], hidden, 2 * d["ffn"]),
           "w2": mat(k[3], d["ffn"], hidden)}
    if kind == "attention":
        blk.update(wq=mat(k[4], hidden, d["q"] * d["hd"]),
                   wk=mat(k[5], hidden, d["kv"] * d["hd"]),
                   wv=mat(k[6], hidden, d["kv"] * d["hd"]),
                   wo=mat(k[7], d["q"] * d["hd"], hidden))
        return blk
    dt = jnp.exp(jax.random.uniform(k[8], (e,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    blk.update(
        w_in=mat(k[4], hidden, 2 * e), conv_w=mat(k[5], d["taps"], e),
        conv_b=(0.1 * jax.random.normal(k[6], (e,), jnp.float32)
                ).astype(dtype),
        w_x=mat(k[7], e, r + 2 * n),
        norm_dt=gain(k[9], r), norm_b=gain(k[10], n), norm_c=gain(k[11], n),
        w_dt=mat(k[12], r, e),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        a_log=jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, e)),
        d_skip=jnp.ones((e,), jnp.float32),
        w_out=mat(k[13], e, hidden))
    return blk


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _ends(key, *, vocab, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return ((0.02 * jax.random.normal(k1, (vocab, hidden), jnp.float32)
             ).astype(dtype),
            (1.0 + 0.1 * jax.random.normal(k2, (hidden,), jnp.float32)
             ).astype(dtype))


def jamba_params(sizes: dict, key, dtype=jnp.bfloat16):
    d = dims(sizes)
    kinds = d.pop("kinds")
    frozen = tuple(sorted(d.items()))
    keys = jax.random.split(key, len(kinds) + 1)
    blocks = [_block(keys[i], kind=kind, d=frozen, dtype=jnp.dtype(dtype))
              for i, kind in enumerate(kinds)]
    wte, norm_f = _ends(keys[-1], vocab=d["vocab"], hidden=d["hidden"],
                        dtype=jnp.dtype(dtype))
    return {"wte": wte, "blocks": blocks, "norm_f": norm_f}
