"""Plain reference for A.X-K1 (`model_type` `axk1`): multi-head latent
attention in every layer, in its EXPANDED form — every head's keys and
values re-made from the latent, nothing absorbed — each layer followed by a
dense SwiGLU (the leading layer) or a sigmoid-routed expert FFN plus one
shared expert; pre-norm, untied head.  float32 `jax.numpy` under
`default_matmul_precision("highest")`; one full forward over a whole
sequence with an explicit causal mask, every held expert a dense SwiGLU
under a mask of the tokens that chose it; no kernels, no cache, no
batching; imports nothing of the program.

    u   = rmsnorm(h) g_attn
    c_q = rmsnorm(u W_dq) g_q;   q = c_q W_uq -> 64 heads x (128 nope | 64 rope)
    [c | k_r] = u W_dkv;  c = rmsnorm(c) g_kv     ONE k_r a token, all heads
    [k_n | v] = c W_ukv  -> 64 heads x (128 | 128)
    q_r, k_r = rope(q_r), rope(k_r)
    s_ij = (q_n,i . k_n,j + q_r,i . k_r,j) * scale,  j <= i
    h = h + (softmax(s) v) W_o
    scale = 192^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
    rope: theta on 64 dims = 32 frequencies f_i, YaRN: f_i / factor * (1 -
          keep_i) + f_i * keep_i, keep_i = 1 - clip((i - low) / (high -
          low), 0, 1), low / high the dims that turn beta_fast / beta_slow
          times in the original context; cos and sin times m(mscale) /
          m(mscale_all_dim); dim i rotated with dim i + 32
    u = rmsnorm(h) g_ffn
    s = sigmoid(u W_r); chosen = top-8 of s; g_i = 2.5 s_i / (sum of the
    chosen s + 1e-20);  h = h + sum_i g_i E_i(u) + E_shared(u)   | dense(u)
    logits = rmsnorm(h) g_f @ head.T

The chip's share (config `reduced`): of the router's 192 outputs this
reference holds `experts_held` = [first, how many]; it takes the top-8 over
ALL outputs and adds up the held experts' part — what the absent ones would
add is left out, as in the program.  The vocabulary is the slice the
weights hold.

Attention is computed a head and a block of query rows at a time (a head's
queries, keys and values are made inside its turn), the FFN a block of rows
at a time, so that 16k positions fit a chip beside the weights; the (bf16)
weights are upcast inside each layer's program.  `quant` is the control's
lower precision: every matmul operand is rounded to fp8 (e4m3) with a
per-row scale first."""

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0
ROWS = 1024       # rows of a block: of queries, of the FFN's tokens


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


def _swiglu(u, w1, w2, quant):
    """W_down(silu(W_gate u) * W_up u), [W_gate | W_up] = w1."""
    ab = _mm(u, w1.astype(jnp.float32), quant)
    half = ab.shape[-1] // 2
    return _mm(jax.nn.silu(ab[:, :half]) * ab[:, half:],
               w2.astype(jnp.float32), quant)


def yarn(c: dict):
    """(inv_freq [rope / 2], the factor on cos and sin, the softmax scale)
    from the published `rope_scaling`."""
    dim, base = c["rope"], c["theta"]
    half = dim // 2
    f = base ** (-jnp.arange(half, dtype=jnp.float32) / half)

    def correction_dim(turns):
        return dim * math.log(c["original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    def magnitude(mscale):
        return 0.1 * mscale * math.log(c["factor"]) + 1.0 \
            if c["factor"] > 1 else 1.0

    low = max(math.floor(correction_dim(c["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(c["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return (f / c["factor"] * (1.0 - keep) + f * keep,
            magnitude(c["mscale"]) / magnitude(c["mscale_all_dim"]),
            (c["nope"] + c["rope"]) ** -0.5 * magnitude(c["mscale_all_dim"])
            ** 2)


def _rope(x, inv_freq, factor):
    """x [t, rope] at positions 0..t-1: dim i rotated with dim i + rope/2."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(t: int) -> int:
    return t // math.gcd(t, ROWS)


def _attention(u, blk, c, quant):
    t = u.shape[0]
    h, nope, rope, vd = c["heads"], c["nope"], c["rope"], c["v"]
    inv_freq, factor, scale = yarn(c)
    nb = _blocks(t)
    c_q = _rmsnorm(_mm(u, blk["w_dq"], quant), blk["q_norm"], c["eps"])
    ckr = _mm(u, blk["w_dkv"], quant)
    lat = _rmsnorm(ckr[:, :c["kv_rank"]], blk["kv_norm"], c["eps"])
    k_r = _rope(ckr[:, c["kv_rank"]:], inv_freq, factor)   # one for all heads
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def head(w):          # one head: its queries, keys and values, expanded
        w_uq, w_ukv = w                     # [q_rank, nope + rope], [kv_rank,
        q = _mm(c_q, w_uq, quant)           #                    nope + v]
        kv = _mm(lat, w_ukv, quant)
        q = jnp.concatenate([q[:, :nope],
                             _rope(q[:, nope:], inv_freq, factor)], -1)
        k = jnp.concatenate([kv[:, :nope], k_r], -1)
        v = kv[:, nope:]

        def rows(qm):                       # a block of query rows
            qb, mb = qm                     # [bq, nope + rope], [bq, t]
            s = (qb @ k.T) * scale
            return jax.nn.softmax(jnp.where(mb, s, -jnp.inf), axis=-1) @ v

        return jax.lax.map(rows, (q.reshape(nb, t // nb, nope + rope),
                                  mask.reshape(nb, t // nb, t))
                           ).reshape(t, vd)

    att = jax.lax.map(head, (
        blk["w_uq"].reshape(-1, h, nope + rope).transpose(1, 0, 2),
        blk["w_ukv"].reshape(-1, h, nope + vd).transpose(1, 0, 2)))
    return _mm(att.transpose(1, 0, 2).reshape(t, h * vd), blk["wo"], quant)


def _moe(u, blk, c, quant):
    s = jax.nn.sigmoid(_mm(u, blk["router"], quant))        # [t, experts]
    _, idx = jax.lax.top_k(s, c["top_k"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gate = c["routed"] * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)

    def one(acc, ew):       # the held experts, one after the other, dense
        e, w1, w2 = ew
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(u, w1, w2, quant), None

    held = blk["w1"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (c["first"] + jnp.arange(held), blk["w1"],
                           blk["w2"]))
    return out + _swiglu(u, blk["shared_w1"], blk["shared_w2"], quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _layer(x, blk, *, c, quant):
    c = dict(c)
    big = ("w1", "w2", "shared_w1", "shared_w2")    # upcast where used
    with jax.default_matmul_precision("highest"):
        blk = {k: a if k in big else a.astype(jnp.float32)
               for k, a in blk.items()}
        x = x + _attention(_rmsnorm(x, blk["norm_attn"], c["eps"]), blk, c,
                           quant)
        t, dim = x.shape
        u = _rmsnorm(x, blk["norm_ffn"], c["eps"])
        ffn = (lambda r: _moe(r, blk, c, quant)) if "router" in blk \
            else (lambda r: _swiglu(r, blk["w1"], blk["w2"], quant))
        return x + jax.lax.map(ffn, u.reshape(_blocks(t), -1, dim)
                               ).reshape(t, dim)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, head, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, norm_f.astype(jnp.float32), eps)
        return _mm(x, head.astype(jnp.float32).T, quant)


def constants(sizes: dict) -> tuple:
    """What a layer's program needs of the config, hashable."""
    rs = sizes["rope_scaling"]
    return tuple(sorted({
        "heads": sizes["num_attention_heads"],
        "kv_rank": sizes["kv_lora_rank"], "nope": sizes["qk_nope_head_dim"],
        "rope": sizes["qk_rope_head_dim"], "v": sizes["v_head_dim"],
        "theta": float(sizes["rope_theta"]), "factor": float(rs["factor"]),
        "beta_fast": float(rs["beta_fast"]),
        "beta_slow": float(rs["beta_slow"]),
        "original": int(rs["original_max_position_embeddings"]),
        "mscale": float(rs["mscale"]),
        "mscale_all_dim": float(rs["mscale_all_dim"]),
        "top_k": sizes["num_experts_per_tok"],
        "first": sizes["experts_held"][0],
        "routed": float(sizes["routed_scaling_factor"]),
        "eps": float(sizes["rms_norm_eps"])}.items()))


def logits(params, sizes: dict, tokens, rows=None, quant: bool = False):
    """tokens: int32 [t] -> float32 logits [len(rows) or t, vocab]."""
    c = constants(sizes)
    x = params["wte"][jnp.asarray(tokens)].astype(jnp.float32)
    for blk in params["blocks"]:
        x = _layer(x, blk, c=c, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm_f"], params["head"],
                 eps=float(sizes["rms_norm_eps"]), quant=quant)
