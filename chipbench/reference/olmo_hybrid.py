"""Plain reference for the Olmo Hybrid decoder (`model_type` `olmo_hybrid`):
gated delta-rule layers (Gated DeltaNet) and full multi-head attention
layers with no positional term, three to one, each followed by a dense
SwiGLU, both sublayers normed on their OUTPUT.  float32 `jax.numpy` under
`default_matmul_precision("highest")`; the recurrence is a sequential scan
over positions, a position at a time, with the state S [heads, value,
key] as the equations write it; no kernels, no cache, no chunks, no
batching; imports nothing of the program.

    h = h + rmsnorm(mixer(h), g_attn)          delta rule | attention
    h = h + rmsnorm(swiglu(h), g_ffn)
    logits = rmsnorm(h, g_f) @ head.T

    delta rule, a head:   c = silu(conv4([q~ | k~ | v~]))   (no bias)
        q = c_q / |c_q| * key^-1/2;  k = c_k / |c_k|;  v = c_v
        beta = 2 sigmoid(h W_b);  a = exp(-exp(A_log) softplus(h W_a + dt))
        S = a S + beta (v - a S k) k^T;   o = S q
        out = concat(rmsnorm(o, g_gate) * silu(h W_g)) W_o
    attention:  q, k = rmsnorm(h Wq, g_q), rmsnorm(h Wk, g_k) over the whole
        projection, then heads of hidden / heads; causal softmax, no rope

One full forward over prompt + served tokens, a layer at a time, the (bf16)
weights upcast inside each layer's program; attention a head at a time, so
that a [t, t] score matrix is all that is held.

`quant` is a control's lower precision, one of two.  `"fp8_operands"` (or
True): every matmul operand is rounded to fp8 (e4m3) with a per-row scale
first; the recurrence, which has no matmul, stays as it is.
`"bf16_recurrence"`: the precision the configuration states (assumption
(f): matmul operands in bfloat16, float32 sums) with ONE thing a step below
it — what (f) keeps in float32 on the delta-rule layers is kept in bfloat16
instead: the conv (its inputs, taps, products and sums), the decay, beta,
and the state S, which every position reads from and rounds back to
bfloat16; the sums inside one position's update stay float32.  It is the
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


def _mm(a, w, quant):
    if quant == FP8:
        a, w = fake_fp8(a, -1), fake_fp8(w, 0)
    elif quant == BF16_RECURRENCE:      # the weights are bfloat16 as drawn
        a = _bf16(a)
    return a @ w


def _bf16(x):
    """Round float32 to bfloat16's 8 bits of mantissa.  (A pair of converts
    would be taken out by the TPU compiler, which allows excess precision.)"""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _swiglu(x, w1, w2, quant):
    ab = _mm(x, w1, quant)
    half = ab.shape[-1] // 2
    return _mm(jax.nn.silu(ab[:, :half]) * ab[:, half:], w2, quant)


def _attention(x, blk, c, quant):
    t = x.shape[0]
    n_q, n_kv, hd = c["n_q"], c["n_kv"], c["hd"]
    q = _rmsnorm(_mm(x, blk["wq"], quant), blk["q_norm"], c["eps"])
    k = _rmsnorm(_mm(x, blk["wk"], quant), blk["k_norm"], c["eps"])
    q = q.reshape(t, n_kv, n_q // n_kv, hd)
    k = k.reshape(t, n_kv, hd)
    v = _mm(x, blk["wv"], quant).reshape(t, n_kv, hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def group(qkv):   # one KV head and the query heads that share it
        qg, kg, vg = qkv                       # [t, rep, hd], [t, hd] x 2
        s = jnp.einsum("qrd,kd->rqk", qg, kg) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", p, vg)

    att = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                              v.transpose(1, 0, 2)))       # [kv, t, rep, hd]
    att = att.transpose(1, 0, 2, 3).reshape(t, n_q * hd)
    return _mm(att, blk["wo"], quant)


def _delta_rule(x, blk, c, quant):
    t = x.shape[0]
    h, dk, dv = c["lin_heads"], c["dk"], c["dv"]
    # the control's rounding: to bfloat16 and back after every step that a
    # bfloat16 program would store
    low = _bf16 if quant == BF16_RECURRENCE else (lambda a: a)
    qkv = low(_mm(x, blk["w_qkv"], quant))
    taps = blk["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    conv = jnp.zeros_like(qkv)
    for j in range(taps):
        conv = low(conv + low(padded[j:j + t] * low(blk["conv_w"][j])))
    conv = low(jax.nn.silu(conv))
    q = _unit(conv[:, :h * dk].reshape(t, h, dk), c["eps"]) * dk ** -0.5
    k = _unit(conv[:, h * dk:2 * h * dk].reshape(t, h, dk), c["eps"])
    v = conv[:, 2 * h * dk:].reshape(t, h, dv)
    ab = _mm(x, blk["w_ab"], quant)
    alpha = low(jnp.exp(-jnp.exp(blk["a_log"])
                        * jax.nn.softplus(ab[:, :h] + blk["dt_bias"])))
    beta = low(c["beta_max"] * jax.nn.sigmoid(ab[:, h:]))

    def step(state, inp):                      # state [h, dv, dk]
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[:, None, None] * state
        seen = jnp.sum(state * k_t[:, None, :], axis=-1)        # S k
        state = low(state + (b_t[:, None] * (v_t - seen))[:, :, None]
                    * k_t[:, None, :])
        return state, jnp.sum(state * q_t[:, None, :], axis=-1)

    _, o = jax.lax.scan(step, jnp.zeros((h, dv, dk)), (q, k, v, alpha, beta))
    gate = jax.nn.silu(_mm(x, blk["w_gate"], quant)).reshape(t, h, dv)
    y = _rmsnorm(o, blk["norm_gate"], c["eps"]) * gate
    return _mm(y.reshape(t, h * dv), blk["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("kind", "c", "quant"))
def _layer(x, blk, *, kind, c, quant):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
        mixed = _attention(x, blk, c, quant) if kind == "full_attention" \
            else _delta_rule(x, blk, c, quant)
        x = x + _rmsnorm(mixed, blk["norm_attn"], c["eps"])
        return x + _rmsnorm(_swiglu(x, blk["w1"], blk["w2"], quant),
                            blk["norm_ffn"], c["eps"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, head, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, norm_f.astype(jnp.float32), eps)
        return _mm(x, head.astype(jnp.float32).T, quant)


def constants(sizes: dict) -> tuple:
    """What a layer's program needs of the config, hashable."""
    return tuple(sorted({
        "n_q": sizes["num_attention_heads"],
        "n_kv": sizes["num_key_value_heads"],
        "hd": sizes["hidden_size"] // sizes["num_attention_heads"],
        "lin_heads": sizes["linear_num_value_heads"],
        "dk": sizes["linear_key_head_dim"],
        "dv": sizes["linear_value_head_dim"],
        "beta_max": 2.0 if sizes["linear_allow_neg_eigval"] else 1.0,
        "eps": float(sizes["rms_norm_eps"])}.items()))


def logits(params, sizes: dict, tokens, rows=None, quant=False):
    """tokens: int32 [t] -> float32 logits [len(rows) or t, vocab]; `quant`
    False, or a control's lower precision (the module's docstring)."""
    quant = FP8 if quant is True else quant
    if quant not in (False, FP8, BF16_RECURRENCE):
        raise ValueError(f"no such control: {quant!r}")
    c = constants(sizes)
    x = params["wte"][jnp.asarray(tokens)].astype(jnp.float32)
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    for kind, blk in zip(kinds, params["blocks"]):
        x = _layer(x, blk, kind=kind, c=c, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm_f"], params["head"],
                 eps=float(sizes["rms_norm_eps"]), quant=quant)
