"""Seeded random weights of the Granite 4.0-H family, made on the device a
layer at a time (one jitted call per KIND of layer, so two compiles) in the
type they are served in.  `models/granite_hybrid.py` and
`reference/granite_hybrid.py` are both given this tree; neither makes
weights of its own.  (`weights.py` is yardstick and is not edited; its
`seed_key` is what turns `--seed` into a key here too.)

    {"wte", "blocks": [block], "norm_f"}; a block: "norm_in", "norm_post",
    "router" [hidden, router_experts], "w1" [held, hidden, 2 * expert],
    "w2" [held, expert, hidden], "shared_w1" [hidden, 2 * shared],
    "shared_w2" [shared, hidden], and either "wq" "wk" "wv" "wo" or the
    Mamba-2 mixer's "w_in" [hidden, 2 * d_inner + 2 * d_state + heads]
    (columns z | x B C | dt), "conv_w" [d_conv, d_inner + 2 * d_state]
    (row j multiplies the input d_conv - 1 - j positions back), "conv_b",
    "dt_bias", "a_log", "d_skip" [heads] (float32), "norm_gate" [d_inner],
    "w_out" [d_inner, hidden].

Matrices are normal / sqrt(fan_in), gains 1 + 0.1 normal (so a dropped gain
shows), the embedding normal * 0.02 / embedding_multiplier — so that what
enters the first layer has the 0.02 the other configurations' embeddings
have: at normal * 0.02 the tied head's self term (multiplier * |e|^2) puts
the token just read 15 sigma above every other logit at these widths, the
served stream is one token repeated, and no comparison has any power —
`a_log` = log(uniform(1, 16)),
`dt_bias` such that softplus lands log-uniformly in 1e-3..1e-1, `d_skip` 1.
The held experts are `experts_held` = [first, how many] of the router's
`router_experts` outputs."""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key  # noqa: F401  (re-exported)


def dims(sizes: dict) -> dict:
    """The shapes the config's keys give."""
    hidden = sizes["hidden_size"]
    heads = sizes["mamba_n_heads"]
    d_inner = heads * sizes["mamba_d_head"]
    if d_inner != sizes["mamba_expand"] * hidden \
            or sizes["mamba_n_groups"] != 1 \
            or sizes["num_local_experts"] != sizes["experts_held"][1]:
        raise ValueError(
            "the configuration's sizes disagree: mamba_n_heads * "
            "mamba_d_head must be mamba_expand * hidden_size, "
            "mamba_n_groups 1, num_local_experts the experts held")
    return {
        "hidden": hidden, "vocab": sizes["vocab_size"],
        "q": sizes["num_attention_heads"], "kv": sizes["num_key_value_heads"],
        "hd": hidden // sizes["num_attention_heads"],
        "heads": heads, "p": sizes["mamba_d_head"],
        "n": sizes["mamba_d_state"], "d_conv": sizes["mamba_d_conv"],
        "d_inner": d_inner, "conv": d_inner + 2 * sizes["mamba_d_state"],
        "experts": sizes["router_experts"], "held": sizes["experts_held"][1],
        "first": sizes["experts_held"][0],
        "top_k": sizes["num_experts_per_tok"],
        "expert": sizes["intermediate_size"],
        "shared": sizes["shared_intermediate_size"],
        "kinds": tuple(sizes["layer_types"][:sizes["num_hidden_layers"]]),
    }


@functools.partial(jax.jit, static_argnames=("kind", "d", "dtype"))
def _block(key, *, kind, d, dtype):
    d = dict(d)
    hidden = d["hidden"]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=hidden):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    k = jax.random.split(key, 16)
    blk = {"norm_in": gain(k[0]), "norm_post": gain(k[1]),
           "router": mat(k[2], hidden, d["experts"]),
           "w1": mat(k[3], d["held"], hidden, 2 * d["expert"]),
           "w2": mat(k[4], d["held"], d["expert"], hidden),
           "shared_w1": mat(k[5], hidden, 2 * d["shared"]),
           "shared_w2": mat(k[6], d["shared"], hidden)}
    if kind == "attention":
        blk.update(wq=mat(k[7], hidden, d["q"] * d["hd"]),
                   wk=mat(k[8], hidden, d["kv"] * d["hd"]),
                   wv=mat(k[9], hidden, d["kv"] * d["hd"]),
                   wo=mat(k[10], d["q"] * d["hd"], hidden))
        return blk
    dt = jnp.exp(jax.random.uniform(k[11], (d["heads"],), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    blk.update(
        w_in=mat(k[7], hidden, 2 * d["d_inner"] + 2 * d["n"] + d["heads"]),
        conv_w=mat(k[8], d["d_conv"], d["conv"]),
        conv_b=(0.1 * jax.random.normal(k[9], (d["conv"],), jnp.float32)
                ).astype(dtype),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        a_log=jnp.log(jax.random.uniform(k[10], (d["heads"],), jnp.float32,
                                         1.0, 16.0)),
        d_skip=jnp.ones((d["heads"],), jnp.float32),
        norm_gate=gain(k[12], d["d_inner"]),
        w_out=mat(k[13], d["d_inner"], hidden))
    return blk


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype",
                                             "multiplier"))
def _ends(key, *, vocab, hidden, dtype, multiplier):
    k1, k2 = jax.random.split(key)
    return ((jax.random.normal(k1, (vocab, hidden), jnp.float32)
             * (0.02 / multiplier)).astype(dtype),
            (1.0 + 0.1 * jax.random.normal(k2, (hidden,), jnp.float32)
             ).astype(dtype))


def granite_params(sizes: dict, key, dtype=jnp.bfloat16):
    d = dims(sizes)
    kinds = d.pop("kinds")
    frozen = tuple(sorted(d.items()))
    keys = jax.random.split(key, len(kinds) + 1)
    blocks = [_block(keys[i], kind=kind, d=frozen, dtype=jnp.dtype(dtype))
              for i, kind in enumerate(kinds)]
    wte, norm_f = _ends(keys[-1], vocab=d["vocab"], hidden=d["hidden"],
                        dtype=jnp.dtype(dtype),
                        multiplier=float(sizes["embedding_multiplier"]))
    return {"wte": wte, "blocks": blocks, "norm_f": norm_f}
