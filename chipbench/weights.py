"""Seeded random weights, made by the benchmark on the device in one jitted
call, in the type they are served or trained in.  The program under test and
the plain reference are both given what these functions make; neither makes
weights of its own."""

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31, which `PRNGKey` alone refuses without x64)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def mistral_params(sizes: dict, key, dtype=jnp.bfloat16):
    """The parameter tree `models/llama.py` and `reference/mistral.py` read:
    {"wte", "blocks": [{attn_norm, wq, wk, wv, wo, ffn_norm, w_gate, w_up,
    w_down}], "norm_f"}.  Matrices are normal / sqrt(fan_in), the embedding
    normal * 0.02, the norm gains 1 + normal * 0.1 (so a dropped gain
    shows)."""
    dim, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["head_dim"]
    n_q, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layers = sizes["num_hidden_layers"]

    def mat(k, n_in, n_out):
        return (jax.random.normal(k, (n_in, n_out), jnp.float32)
                / math.sqrt(n_in)).astype(dtype)

    def gain(k):
        return (1.0 + 0.1 * jax.random.normal(k, (dim,), jnp.float32)
                ).astype(dtype)

    keys = jax.random.split(key, layers + 2)
    blocks = []
    for i in range(layers):
        bk = jax.random.split(keys[i], 9)
        blocks.append({
            "attn_norm": gain(bk[0]),
            "wq": mat(bk[1], dim, n_q * hd),
            "wk": mat(bk[2], dim, n_kv * hd),
            "wv": mat(bk[3], dim, n_kv * hd),
            "wo": mat(bk[4], n_q * hd, dim),
            "ffn_norm": gain(bk[5]),
            "w_gate": mat(bk[6], dim, ffn),
            "w_up": mat(bk[7], dim, ffn),
            "w_down": mat(bk[8], ffn, dim),
        })
    wte = (jax.random.normal(keys[layers], (sizes["vocab_size"], dim),
                             jnp.float32) * 0.02).astype(dtype)
    return {"wte": wte, "blocks": blocks, "norm_f": gain(keys[layers + 1])}


def gpt2_params(sizes: dict, key, stacked: bool = False):
    """The float32 parameter tree `models/gpt.py` reads: {"wte", "wpe",
    "blocks": [{ln1, attn: {qkv, proj}, ln2, mlp: {fc, proj}}], "ln_f"}.
    GPT-2's published initialisation: normal * 0.02, the two residual
    projections scaled by 1 / sqrt(2 * layers), biases 0, gains 1.  Each
    layer is drawn as one slice of a layer-stacked array; `stacked=True`
    returns the stacked form (what `reference/gpt2.py` scans over),
    otherwise the list of per-layer slices of the same values."""
    dim, layers = sizes["n_embd"], sizes["n_layer"]
    vocab = sizes.get("padded_vocab_size", sizes["vocab_size"])
    k_wte, k_wpe, k_qkv, k_ap, k_fc, k_mp = jax.random.split(key, 6)
    res = 0.02 / math.sqrt(2.0 * layers)

    def lin(k, n_in, n_out, std):
        return {"w": jax.random.normal(k, (layers, n_in, n_out),
                                       jnp.float32) * std,
                "b": jnp.zeros((layers, n_out), jnp.float32)}

    def ln():
        return {"g": jnp.ones((layers, dim), jnp.float32),
                "b": jnp.zeros((layers, dim), jnp.float32)}

    blocks = {"ln1": ln(),
              "attn": {"qkv": lin(k_qkv, dim, 3 * dim, 0.02),
                       "proj": lin(k_ap, dim, dim, res)},
              "ln2": ln(),
              "mlp": {"fc": lin(k_fc, dim, 4 * dim, 0.02),
                      "proj": lin(k_mp, 4 * dim, dim, res)}}
    if not stacked:
        blocks = [jax.tree.map(lambda a, i=i: a[i], blocks)
                  for i in range(layers)]
    return {
        "wte": jax.random.normal(k_wte, (vocab, dim), jnp.float32) * 0.02,
        "wpe": jax.random.normal(k_wpe, (sizes["n_positions"], dim),
                                 jnp.float32) * 0.01,
        "blocks": blocks,
        "ln_f": {"g": jnp.ones((dim,), jnp.float32),
                 "b": jnp.zeros((dim,), jnp.float32)},
    }


def adam_zeros(params):
    """The optimizer state `models/optim.py::adam_update` expects."""
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}
