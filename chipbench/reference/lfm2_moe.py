"""Plain reference for the LFM2-MoE decoder (`model_type` `lfm2_moe`,
LFM2-8B-A1B): gated short convolutions on the layers `layer_types` calls
`conv`, grouped-query attention with per-head RMS norms and rotary
positions on those it calls `full_attention`, each followed by a dense
SwiGLU (the first `num_dense_layers`) or a sigmoid-routed expert FFN with
no shared expert; pre-normed, the head tied to the embedding.  float32
`jax.numpy` under `default_matmul_precision("highest")`; one full forward
over a whole sequence, the conv as a sum of shifted copies, attention under
an explicit [T, T] mask a KV head at a time, every held expert a dense
SwiGLU under a mask of the tokens that chose it; no kernels, no cache, no
chunks, no batching; imports nothing of the program.

    h = wte[tokens]
    u = rmsnorm(h, g_op)
    conv:       [B | C | x~] = u W_in;  y = C * conv3(B * x~);  h = h + y W_out
                conv3(z)[t] = w[2] z[t] + w[1] z[t-1] + w[0] z[t-2]
                (causal, depthwise, no bias, no activation)
    attention:  q = rmsnorm_64(u Wq) g_q, k = rmsnorm_64(u Wk) g_k, v = u Wv
                q, k = rope(q), rope(k)     theta 1e6, rotate-half, all 64
                h = h + softmax(causal(q k^T / 8)) v Wo
    f = rmsnorm(h, g_ffn)
    dense:      h = h + W2(silu(W1a f) * (W1b f))
    experts:    s = sigmoid(f W_r);  chosen = top-4 of s + b
                g_j = scale * s_j / (sum of the chosen s + 1e-6)
                h = h + sum_j g_j E_j(f)
    logits = rmsnorm(h, g_f) @ wte.T

The chip's share (config `reduced`): of the router's 32 outputs this
reference holds `experts_held` = [first, how many]; it takes the top-4 over
ALL outputs and adds up the held experts' part — what the absent ones would
add is left out, as in the program.

The (bf16) weights are upcast inside each layer's program, the experts one
at a time.  `quant` is a control's lower precision, one of two.
`"fp8_operands"` (or True): every matmul operand is rounded to fp8 (e4m3)
with a per-row scale first.  `"bf16_router"`: the precision the
configuration states (matmul operands in bfloat16, float32 sums) with ONE
thing a step below it — the router's scores, which the configuration keeps
in float32, are rounded to bfloat16 before the choice: near-ties then
choose other experts."""

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0
FP8, BF16_ROUTER = "fp8_operands", "bf16_router"
ROUTER_EPS = 1e-6


def fake_fp8(x, axis=-1):
    """Round to float8_e4m3 with a per-row absmax scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x):
    """Round float32 to bfloat16's 8 bits of mantissa.  (A pair of converts
    would be taken out by the TPU compiler, which allows excess precision.)"""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(a, w, quant):
    if quant == FP8:
        a, w = fake_fp8(a, -1), fake_fp8(w, 0)
    elif quant == BF16_ROUTER:          # the weights are bfloat16 as drawn
        a = _bf16(a)
    return a @ w


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _swiglu(u, w1, w2, quant):
    """W_down(silu(W_gate u) * W_up u), [W_gate | W_up] = w1."""
    ab = _mm(u, w1.astype(jnp.float32), quant)
    half = ab.shape[-1] // 2
    return _mm(jax.nn.silu(ab[:, :half]) * ab[:, half:],
               w2.astype(jnp.float32), quant)


def _rope(x, theta):
    """x [t, n, hd] at positions 0..t-1: dim i rotated with dim i + hd/2."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _shortconv(u, blk, c, quant):
    t, dim = u.shape
    bcx = _mm(u, blk["w_in"], quant)
    gate_in, gate_out, x = (bcx[:, i * dim:(i + 1) * dim] for i in range(3))
    z = gate_in * x
    taps = blk["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, dim)), z])
    conv = sum(padded[j:j + t] * blk["conv_w"][j] for j in range(taps))
    return _mm(gate_out * conv, blk["w_out"], quant)


def _attention(u, blk, c, quant):
    t = u.shape[0]
    n_q, n_kv, hd = c["n_q"], c["n_kv"], c["hd"]
    rep = n_q // n_kv
    q = _mm(u, blk["wq"], quant).reshape(t, n_q, hd)
    k = _mm(u, blk["wk"], quant).reshape(t, n_kv, hd)
    v = _mm(u, blk["wv"], quant).reshape(t, n_kv, hd)
    q = _rope(_rmsnorm(q, blk["q_norm"], c["eps"]), c["theta"])
    k = _rope(_rmsnorm(k, blk["k_norm"], c["eps"]), c["theta"])
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def group(qkv):   # one KV head and the query heads that share it
        qg, kg, vg = qkv                       # [rep, t, hd], [t, hd] x 2

        def head(qh):
            s = (qh @ kg.T) * hd ** -0.5
            return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1) @ vg

        return jax.lax.map(head, qg)           # [rep, t, hd]

    att = jax.lax.map(group, (
        q.reshape(t, n_kv, rep, hd).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [kv, rep, t, hd]
    att = att.transpose(2, 0, 1, 3).reshape(t, n_q * hd)
    return _mm(att, blk["wo"], quant)


def _moe(f, blk, c, quant):
    s = jax.nn.sigmoid(_mm(f, blk["router"], quant))        # [t, experts]
    if quant == BF16_ROUTER:
        s = _bf16(s)
    _, idx = jax.lax.top_k(s + blk["router_bias"], c["top_k"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gate = c["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True)
                                  + ROUTER_EPS)

    def one(acc, ew):       # the held experts, one after the other, dense
        e, w1, w2 = ew
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(f, w1, w2, quant), None

    held = blk["w1"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(f),
                          (c["first"] + jnp.arange(held), blk["w1"],
                           blk["w2"]))
    return out


@functools.partial(jax.jit, static_argnames=("kind", "c", "quant"))
def _layer(x, blk, *, kind, c, quant):
    c = dict(c)
    big = ("w1", "w2")                               # upcast where used
    with jax.default_matmul_precision("highest"):
        blk = {k: a if k in big else a.astype(jnp.float32)
               for k, a in blk.items()}
        u = _rmsnorm(x, blk["norm_op"], c["eps"])
        x = x + (_shortconv(u, blk, c, quant) if kind == "conv"
                 else _attention(u, blk, c, quant))
        f = _rmsnorm(x, blk["norm_ffn"], c["eps"])
        return x + (_moe(f, blk, c, quant) if "router" in blk
                    else _swiglu(f, blk["w1"], blk["w2"], quant))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, wte, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, norm_f.astype(jnp.float32), eps)
        return _mm(x, wte.astype(jnp.float32).T, quant)


def constants(sizes: dict) -> tuple:
    """What a layer's program needs of the config, hashable."""
    return tuple(sorted({
        "n_q": sizes["num_attention_heads"],
        "n_kv": sizes["num_key_value_heads"],
        "hd": sizes["hidden_size"] // sizes["num_attention_heads"],
        "theta": float(sizes["rope_theta"]),
        "top_k": sizes["num_experts_per_tok"],
        "first": sizes["experts_held"][0],
        "scale": float(sizes["routed_scaling_factor"]),
        "eps": float(sizes["norm_eps"])}.items()))


def logits(params, sizes: dict, tokens, rows=None, quant=False):
    """tokens: int32 [t] -> float32 logits [len(rows) or t, vocab]; `quant`
    False, or a control's lower precision (the module's docstring)."""
    quant = FP8 if quant is True else quant
    if quant not in (False, FP8, BF16_ROUTER):
        raise ValueError(f"no such control: {quant!r}")
    c = constants(sizes)
    x = params["wte"][jnp.asarray(tokens)].astype(jnp.float32)
    for kind, blk in zip(sizes["layer_types"], params["blocks"]):
        x = _layer(x, blk, kind=kind, c=c, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm_f"], params["wte"],
                 eps=float(sizes["norm_eps"]), quant=quant)
