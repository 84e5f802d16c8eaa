"""Plain reference for K-EXAONE (`model_type` `exaone_moe`): grouped-query
attention layers of two kinds — sliding (the last `sliding_window`
positions, rotary) and full (everything, no positional term) — each
followed by a dense SwiGLU (the leading layer) or a sigmoid-routed expert
FFN plus one shared expert, both sublayers normed on their output.  float32
`jax.numpy` under `default_matmul_precision("highest")`; one full forward
over a whole sequence with an explicit [T, T] mask per layer kind, every
held expert a dense SwiGLU under a mask of the tokens that chose it; no
kernels, no cache, no batching; imports nothing of the program.

    q, k, v = h Wq, h Wk, h Wv;  q, k = rmsnorm_128(q) g_q, rmsnorm_128(k) g_k
    q, k = rope(q), rope(k)                       sliding layers only
    a = softmax(mask(q k^T / sqrt(128))) v        mask: j <= i, and on a
                                                  sliding layer i - 128 < j
    h = h + rmsnorm(a Wo) g_attn
    s = sigmoid(h W_r); chosen = top-8 of s + b; g_i = 2.5 s_i / (sum of
    the chosen s + 1e-20);  f = sum_i g_i E_i(h) + E_shared(h)   | dense(h)
    h = h + rmsnorm(f) g_ffn
    logits = rmsnorm(h) g_f @ head.T

The chip's share (config `reduced`): of the router's 128 outputs this
reference holds `experts_held` = [first, how many]; it takes the top-8
over ALL outputs and adds up the held experts' part — what the absent ones
would add is left out, as in the program.  The vocabulary is the slice the
weights hold.

Attention is computed a KV head and a block of query rows at a time, the
FFN a block of rows at a time, so that 8k positions fit a chip beside the
weights; the (bf16) weights are upcast inside each layer's program, the
experts one at a time.  `quant` is the control's lower precision: every
matmul operand is rounded to fp8 (e4m3) with a per-row scale first."""

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


def _rope(x, theta):
    """x [t, n, hd] at positions 0..t-1: dim i rotated with dim i + hd/2."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(t: int) -> int:
    return t // math.gcd(t, ROWS)


def _attention(x, blk, c, sliding, quant):
    t = x.shape[0]
    n_q, n_kv, hd = c["n_q"], c["n_kv"], c["hd"]
    rep, nb = n_q // n_kv, _blocks(t)
    q = _mm(x, blk["wq"], quant).reshape(t, n_q, hd)
    k = _mm(x, blk["wk"], quant).reshape(t, n_kv, hd)
    v = _mm(x, blk["wv"], quant).reshape(t, n_kv, hd)
    q = _rmsnorm(q, blk["q_norm"], c["eps"])
    k = _rmsnorm(k, blk["k_norm"], c["eps"])
    if sliding:
        q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if sliding:
        mask &= j > i - c["window"]

    def group(qkv):   # one KV head and the query heads that share it
        qg, kg, vg = qkv                       # [t, rep, hd], [t, hd] x 2

        def rows(qm):                          # a block of query rows
            qb, mb = qm                        # [bq, rep, hd], [bq, t]
            s = jnp.einsum("qrd,kd->rqk", qb, kg) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(mb[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("rqk,kd->qrd", p, vg)

        out = jax.lax.map(rows, (qg.reshape(nb, t // nb, rep, hd),
                                 mask.reshape(nb, t // nb, t)))
        return out.reshape(t, rep, hd)

    att = jax.lax.map(group, (
        q.reshape(t, n_kv, rep, hd).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))       # [kv, t, rep, hd]
    att = att.transpose(1, 0, 2, 3).reshape(t, n_q * hd)
    return _mm(att, blk["wo"], quant)


def _moe(u, blk, c, quant):
    s = jax.nn.sigmoid(_mm(u, blk["router"], quant))        # [t, experts]
    _, idx = jax.lax.top_k(s + blk["router_bias"], c["top_k"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gate = c["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)

    def one(acc, ew):       # the held experts, one after the other, dense
        e, w1, w2 = ew
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(u, w1, w2, quant), None

    held = blk["w1"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (c["first"] + jnp.arange(held), blk["w1"],
                           blk["w2"]))
    return out + _swiglu(u, blk["shared_w1"], blk["shared_w2"], quant)


@functools.partial(jax.jit, static_argnames=("sliding", "c", "quant"))
def _layer(x, blk, *, sliding, c, quant):
    c = dict(c)
    big = ("w1", "w2", "shared_w1", "shared_w2")    # upcast where used
    with jax.default_matmul_precision("highest"):
        blk = {k: a if k in big else a.astype(jnp.float32)
               for k, a in blk.items()}
        x = x + _rmsnorm(_attention(x, blk, c, sliding, quant),
                         blk["norm_attn"], c["eps"])
        t, dim = x.shape
        ffn = (lambda u: _moe(u, blk, c, quant)) if "router" in blk \
            else (lambda u: _swiglu(u, blk["w1"], blk["w2"], quant))
        f = jax.lax.map(ffn, x.reshape(_blocks(t), -1, dim)).reshape(t, dim)
        return x + _rmsnorm(f, blk["norm_ffn"], c["eps"])


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
        "hd": sizes["head_dim"], "window": sizes["sliding_window"],
        "theta": float(sizes["rope_parameters"]["rope_theta"]),
        "top_k": sizes["num_experts_per_tok"],
        "first": sizes["experts_held"][0],
        "scale": float(sizes["routed_scaling_factor"]),
        "eps": float(sizes["rms_norm_eps"])}.items()))


def logits(params, sizes: dict, tokens, rows=None, quant: bool = False):
    """tokens: int32 [t] -> float32 logits [len(rows) or t, vocab]."""
    c = constants(sizes)
    x = params["wte"][jnp.asarray(tokens)].astype(jnp.float32)
    for kind, blk in zip(sizes["layer_types"], params["blocks"]):
        x = _layer(x, blk, sliding=kind == "sliding_attention", c=c,
                   quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm_f"], params["head"],
                 eps=float(sizes["rms_norm_eps"]), quant=quant)
