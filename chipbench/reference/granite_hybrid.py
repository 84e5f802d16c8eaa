"""Plain reference for the Granite 4.0-H hybrid decoder (`model_type`
`granitemoehybrid`): Mamba-2 state layers and grouped-query attention
layers with no positional encoding, each followed by a routed expert FFN
plus a shared MLP, four scalar multipliers.  float32 `jax.numpy` under
`default_matmul_precision("highest")`; the recurrence is a sequential scan
over positions, the experts a dense loop over the ones held; no kernels, no
cache, no batching; imports nothing of the program.

    h0 = embed[token] * embedding_multiplier
    r = h; u = rmsnorm(h, g_in);   m = mamba(u) | attn(u);   h = r + res * m
    r = h; u = rmsnorm(h, g_post); f = moe(u) + shared(u);   h = r + res * f
    logits = rmsnorm(h, g_f) @ embed.T / logits_scaling

The chip's share (config `reduced`): of the router's `router_experts`
outputs this reference holds `experts_held` = [first, how many]; it takes
the top `num_experts_per_tok` over ALL outputs, softmaxes over those, and
adds up the held experts' part — what the absent ones would add is left
out, as in the program.  The vocabulary is the slice the weights hold.

One full forward over prompt + served tokens, a layer at a time, the
(bf16) weights upcast inside each layer's program.  `quant` is the
control's lower precision: every matmul operand is rounded to fp8 (e4m3)
with a per-row scale first (the recurrence itself has no matmul)."""

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def fake_fp8(x, axis=-1):
    """Round to float8_e4m3 with a per-row absmax scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, quant):
    if quant:
        a, w = fake_fp8(a, -1), fake_fp8(w, 0)
    return a @ w


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _glu(u, w1, w2, quant):
    ab = _mm(u, w1, quant)
    half = ab.shape[-1] // 2
    return _mm(jax.nn.silu(ab[:, :half]) * ab[:, half:], w2, quant)


def _attention(u, blk, c, quant):
    t = u.shape[0]
    n_q, n_kv, hd = c["n_q"], c["n_kv"], c["hd"]
    q = _mm(u, blk["wq"], quant).reshape(t, n_kv, n_q // n_kv, hd)
    k = _mm(u, blk["wk"], quant).reshape(t, n_kv, hd)
    v = _mm(u, blk["wv"], quant).reshape(t, n_kv, hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def group(qkv):   # one KV head and the query heads that share it
        qg, kg, vg = qkv                       # [t, rep, hd], [t, hd] x 2
        s = jnp.einsum("qrd,kd->rqk", qg, kg) * c["attn_scale"]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", p, vg)

    att = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                              v.transpose(1, 0, 2)))       # [kv, t, rep, hd]
    att = att.transpose(1, 0, 2, 3).reshape(t, n_q * hd)
    return _mm(att, blk["wo"], quant)


def _mamba(u, blk, c, quant):
    t = u.shape[0]
    h, p, n, d_in = c["heads"], c["p"], c["n"], c["heads"] * c["p"]
    conv_dim = d_in + 2 * n
    zxbcdt = _mm(u, blk["w_in"], quant)
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + conv_dim],
                  zxbcdt[:, d_in + conv_dim:])
    taps = blk["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim)), xbc])
    conv = sum(padded[j:j + t] * blk["conv_w"][j] for j in range(taps)) \
        + blk["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(t, h, p)
    b_mat, c_mat = xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
    dt = jax.nn.softplus(dt + blk["dt_bias"])               # [t, h]
    a = -jnp.exp(blk["a_log"])

    def step(state, inp):                      # state [h, p, n]
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n)), (x, dt, b_mat, c_mat))
    y = y + blk["d_skip"][None, :, None] * x
    y = _rmsnorm(y.reshape(t, d_in) * jax.nn.silu(z), blk["norm_gate"],
                 c["eps"])
    return _mm(y, blk["w_out"], quant)


def _moe(u, blk, c, quant):
    scores = _mm(u, blk["router"], quant)                    # [t, experts]
    top, idx = jax.lax.top_k(scores, c["top_k"])
    gate = jax.nn.softmax(top, axis=-1)

    def one(acc, ew):       # the held experts, one after the other, dense
        e, w1, w2 = ew
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        return acc + weight[:, None] * _glu(u, w1, w2, quant), None

    held = blk["w1"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (c["first"] + jnp.arange(held), blk["w1"],
                           blk["w2"]))
    return out


@functools.partial(jax.jit, static_argnames=("kind", "c", "quant"))
def _layer(x, blk, *, kind, c, quant):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
        u = _rmsnorm(x, blk["norm_in"], c["eps"])
        mixed = _attention(u, blk, c, quant) if kind == "attention" \
            else _mamba(u, blk, c, quant)
        x = x + c["res"] * mixed
        u = _rmsnorm(x, blk["norm_post"], c["eps"])
        return x + c["res"] * (
            _moe(u, blk, c, quant)
            + _glu(u, blk["shared_w1"], blk["shared_w2"], quant))


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "quant"))
def _head(x, norm_f, wte, *, eps, scaling, quant):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, norm_f.astype(jnp.float32), eps)
        return _mm(x, wte.astype(jnp.float32).T, quant) / scaling


def constants(sizes: dict) -> tuple:
    """What a layer's program needs of the config, hashable."""
    return tuple(sorted({
        "n_q": sizes["num_attention_heads"],
        "n_kv": sizes["num_key_value_heads"],
        "hd": sizes["hidden_size"] // sizes["num_attention_heads"],
        "attn_scale": float(sizes["attention_multiplier"]),
        "heads": sizes["mamba_n_heads"], "p": sizes["mamba_d_head"],
        "n": sizes["mamba_d_state"],
        "top_k": sizes["num_experts_per_tok"],
        "first": sizes["experts_held"][0],
        "res": float(sizes["residual_multiplier"]),
        "eps": float(sizes["rms_norm_eps"])}.items()))


def logits(params, sizes: dict, tokens, rows=None, quant: bool = False):
    """tokens: int32 [t] -> float32 logits [len(rows) or t, vocab]."""
    c = constants(sizes)
    x = params["wte"][jnp.asarray(tokens)].astype(jnp.float32) \
        * float(sizes["embedding_multiplier"])
    for kind, blk in zip(sizes["layer_types"], params["blocks"]):
        x = _layer(x, blk, kind=kind, c=c, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm_f"], params["wte"],
                 eps=float(sizes["rms_norm_eps"]),
                 scaling=float(sizes["logits_scaling"]), quant=quant)
