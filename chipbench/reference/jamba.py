"""Plain reference for the Jamba decoder (`model_type` `jamba`, the dense
Jamba2-3B reading): Mamba-1 selective state-space layers and multi-query
attention layers with no positional term, each followed by a dense SwiGLU,
pre-normed, the head tied to the embedding.  float32 `jax.numpy` under
`default_matmul_precision("highest")`; the recurrence is a sequential scan
over positions, a position at a time, with the state h [channels, state
index] as the equations write it; no kernels, no cache, no chunks, no
batching; imports nothing of the program.

    x = x + mixer(rmsnorm(x, g_in))            selective state | attention
    x = x + W_down(silu(W_gate u') * W_up u'),  u' = rmsnorm(x, g_ff)
    logits = rmsnorm(x, g_f) @ wte.T

    selective state (layer i with i % attn_layer_period != attn_layer_offset):
        [x~ | z] = u W_in;   x = silu(conv4(x~) + b_conv)        x~ ALONE
        [dt~ | B | C] = x W_x, each RMS-normed with its gain
        dt = softplus(dt~ W_dt + b_dt);   A = -exp(A_log)        [channels, n]
        h = exp(dt[:, None] A) h + (dt x)[:, None] B[None, :];   y = h C + D x
        out = (y * silu(z)) W_out
    attention:  q = u W_q (heads of `hd`), k, v = u W_k, u W_v (ONE head),
        causal softmax(q k^T / sqrt(hd)) v, W_o; no positions

One full forward over prompt + served tokens, a layer at a time, the (bf16)
weights upcast inside each layer's program; attention a query head at a
time, so that a [t, t] score matrix is all that is held.

`quant` is a control's lower precision, one of two.  `"fp8_operands"` (or
True): every matmul operand is rounded to fp8 (e4m3) with a per-row scale
first; the recurrence, which has no matmul, stays as it is.
`"bf16_recurrence"`: the precision the configuration states (assumption
(e): matmul operands in bfloat16, float32 sums) with ONE thing a step below
it — what (e) keeps in float32 on the selective layers is kept in bfloat16
instead: the conv (its inputs, taps, products and sums), dt, the decay, and
the state h, which every position reads from and rounds back to bfloat16;
the sum over the state index inside one position stays float32.  It is the
mildest program with a state leaf of half the bytes."""

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0
FP8, BF16_RECURRENCE = "fp8_operands", "bf16_recurrence"


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
    elif quant == BF16_RECURRENCE:      # the weights are bfloat16 as drawn
        a = _bf16(a)
    return a @ w


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _swiglu(x, w1, w2, quant):
    ab = _mm(x, w1, quant)
    half = ab.shape[-1] // 2
    return _mm(jax.nn.silu(ab[:, :half]) * ab[:, half:], w2, quant)


def _attention(u, blk, c, quant):
    t = u.shape[0]
    n_q, hd = c["n_q"], c["hd"]
    q = _mm(u, blk["wq"], quant).reshape(t, n_q, hd)
    k, v = _mm(u, blk["wk"], quant), _mm(u, blk["wv"], quant)   # ONE head
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def head(qh):                                      # [t, hd]
        s = (qh @ k.T) * hd ** -0.5
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v

    att = jax.lax.map(head, q.transpose(1, 0, 2))      # [heads, t, hd]
    return _mm(att.transpose(1, 0, 2).reshape(t, n_q * hd), blk["wo"], quant)


def _selective(u, blk, c, quant):
    t = u.shape[0]
    e, n, r = c["e"], c["n"], c["r"]
    # the control's rounding: to bfloat16 and back after every step that a
    # bfloat16 program would store
    low = _bf16 if quant == BF16_RECURRENCE else (lambda a: a)
    xz = _mm(u, blk["w_in"], quant)
    pre, z = low(xz[:, :e]), xz[:, e:]
    taps = blk["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, e)), pre])
    conv = jnp.zeros_like(pre)
    for j in range(taps):
        conv = low(conv + low(padded[j:j + t] * low(blk["conv_w"][j])))
    x = low(jax.nn.silu(low(conv + low(blk["conv_b"]))))
    proj = _mm(x, blk["w_x"], quant)
    dt = _rmsnorm(proj[:, :r], blk["norm_dt"], c["eps"])
    b_mat = _rmsnorm(proj[:, r:r + n], blk["norm_b"], c["eps"])
    c_mat = _rmsnorm(proj[:, r + n:], blk["norm_c"], c["eps"])
    dt = low(jax.nn.softplus(_mm(dt, blk["w_dt"], quant) + blk["dt_bias"]))
    a = -jnp.exp(blk["a_log"]).T                       # [channels, n]

    def step(h, inp):                                  # h [channels, n]
        x_t, dt_t, b_t, c_t = inp
        decay = low(jnp.exp(dt_t[:, None] * a))
        h = low(decay * h + (dt_t * x_t)[:, None] * b_t[None, :])
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((e, n)), (x, dt, b_mat, c_mat))
    y = y + blk["d_skip"] * x
    return _mm(y * jax.nn.silu(z), blk["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("kind", "c", "quant"))
def _layer(x, blk, *, kind, c, quant):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
        u = _rmsnorm(x, blk["norm_in"], c["eps"])
        x = x + (_attention(u, blk, c, quant) if kind == "attention"
                 else _selective(u, blk, c, quant))
        return x + _swiglu(_rmsnorm(x, blk["norm_ff"], c["eps"]), blk["w1"],
                           blk["w2"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, wte, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, norm_f.astype(jnp.float32), eps)
        return _mm(x, wte.astype(jnp.float32).T, quant)


def kinds(sizes: dict) -> tuple:
    """Layer i is attention iff i % attn_layer_period == attn_layer_offset
    (the `jamba` model's rule: assumption (a))."""
    return tuple(
        "attention" if i % sizes["attn_layer_period"]
        == sizes["attn_layer_offset"] else "mamba"
        for i in range(sizes["num_hidden_layers"]))


def constants(sizes: dict) -> tuple:
    """What a layer's program needs of the config, hashable."""
    return tuple(sorted({
        "n_q": sizes["num_attention_heads"],
        "hd": sizes["hidden_size"] // sizes["num_attention_heads"],
        "e": sizes["mamba_expand"] * sizes["hidden_size"],
        "n": sizes["mamba_d_state"], "r": sizes["mamba_dt_rank"],
        "eps": float(sizes["rms_norm_eps"])}.items()))


def logits(params, sizes: dict, tokens, rows=None, quant=False):
    """tokens: int32 [t] -> float32 logits [len(rows) or t, vocab]; `quant`
    False, or a control's lower precision (the module's docstring)."""
    quant = FP8 if quant is True else quant
    if quant not in (False, FP8, BF16_RECURRENCE):
        raise ValueError(f"no such control: {quant!r}")
    c = constants(sizes)
    x = params["wte"][jnp.asarray(tokens)].astype(jnp.float32)
    for kind, blk in zip(kinds(sizes), params["blocks"]):
        x = _layer(x, blk, kind=kind, c=c, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm_f"], params["wte"],
                 eps=float(sizes["rms_norm_eps"]), quant=quant)
