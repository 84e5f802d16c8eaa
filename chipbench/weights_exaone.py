"""Seeded random weights of the K-EXAONE family (`exaone_moe`), made on the
device a layer at a time (one jitted call per KIND of FFN, so two compiles)
in the type they are served in.  `models/exaone_moe.py` and
`reference/exaone_moe.py` are both given this tree; neither makes weights of
its own.  (`weights.py` is yardstick and is not edited; its `seed_key` is
what turns `--seed` into a key here too.)

    {"wte" [vocab, hidden], "head" [vocab, hidden], "blocks": [block],
    "norm_f"}; a block: "wq" "wk" "wv" "wo", "q_norm" "k_norm" [head_dim],
    "norm_attn" "norm_ffn" [hidden], and either the dense "w1" [hidden, 2 *
    intermediate] (gate | up), "w2" [intermediate, hidden] or "router"
    [hidden, router_experts], "router_bias" [router_experts] (float32),
    "w1" [held, hidden, 2 * moe_intermediate], "w2" [held,
    moe_intermediate, hidden], "shared_w1", "shared_w2" (one shared expert
    of the same width).

Matrices are normal / sqrt(fan_in), gains 1 + 0.1 normal (so a dropped gain
shows), the selection bias normal * 0.01 (so that it flips some of the
choices: the chosen scores lie closer than that about once in ten tokens),
the embedding normal * 1: every sublayer's output is normed to about 1
before it is added, so an embedding of 0.02 would leave the stream with
next to nothing of the token just read — the attention of random weights
is nearly flat, its output nearly the same at every position, and the
served streams would be one repeated token (PERF.md section 4, PR 31's
trap in this family's form).  The head is untied.  The held experts are
`experts_held` = [first, how many] of the router's `router_experts`
outputs; `vocab_size` rows of embedding and head are the slice held."""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key  # noqa: F401  (re-exported)


def dims(sizes: dict) -> dict:
    """The shapes the config's keys give."""
    n = sizes["num_hidden_layers"]
    if sizes["num_experts"] != sizes["experts_held"][1] \
            or sizes["num_shared_experts"] != 1 \
            or sizes["n_group"] != 1 or sizes["topk_group"] != 1 \
            or len(sizes["layer_types"]) != n \
            or len(sizes["mlp_layer_types"]) != n \
            or [w != 0 for w in sizes["sliding_windows"]] != [
                t == "sliding_attention" for t in sizes["layer_types"]] \
            or any(w not in (0, sizes["sliding_window"])
                   for w in sizes["sliding_windows"]):
        raise ValueError(
            "the configuration's sizes disagree: num_experts must be the "
            "experts held, one shared expert, no group limit, and "
            "layer_types / mlp_layer_types / sliding_windows one entry a "
            "layer that agree on which layers slide")
    return {
        "hidden": sizes["hidden_size"], "vocab": sizes["vocab_size"],
        "q": sizes["num_attention_heads"], "kv": sizes["num_key_value_heads"],
        "hd": sizes["head_dim"], "dense": sizes["intermediate_size"],
        "expert": sizes["moe_intermediate_size"],
        "experts": sizes["router_experts"],
        "first": sizes["experts_held"][0], "held": sizes["experts_held"][1],
        "top_k": sizes["num_experts_per_tok"],
        "mlp": tuple(sizes["mlp_layer_types"]),
    }


@functools.partial(jax.jit, static_argnames=("mlp", "d", "dtype"))
def _block(key, *, mlp, d, dtype):
    d = dict(d)
    hidden = d["hidden"]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=hidden):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    k = jax.random.split(key, 16)
    blk = {"wq": mat(k[0], hidden, d["q"] * d["hd"]),
           "wk": mat(k[1], hidden, d["kv"] * d["hd"]),
           "wv": mat(k[2], hidden, d["kv"] * d["hd"]),
           "wo": mat(k[3], d["q"] * d["hd"], hidden),
           "q_norm": gain(k[4], d["hd"]), "k_norm": gain(k[5], d["hd"]),
           "norm_attn": gain(k[6]), "norm_ffn": gain(k[7])}
    if mlp == "dense":
        blk.update(w1=mat(k[8], hidden, 2 * d["dense"]),
                   w2=mat(k[9], d["dense"], hidden))
        return blk
    blk.update(router=mat(k[8], hidden, d["experts"]),
               router_bias=0.01 * jax.random.normal(k[9], (d["experts"],),
                                                    jnp.float32),
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


def exaone_params(sizes: dict, key, dtype=jnp.bfloat16):
    d = dims(sizes)
    mlps = d.pop("mlp")
    frozen = tuple(sorted(d.items()))
    keys = jax.random.split(key, len(mlps) + 1)
    blocks = [_block(keys[i], mlp=mlp, d=frozen, dtype=jnp.dtype(dtype))
              for i, mlp in enumerate(mlps)]
    wte, head, norm_f = _ends(keys[-1], vocab=d["vocab"], hidden=d["hidden"],
                              dtype=jnp.dtype(dtype))
    return {"wte": wte, "head": head, "blocks": blocks, "norm_f": norm_f}
