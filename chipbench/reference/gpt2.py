"""Plain reference for GPT-2 and its training step: learned positions,
pre-LayerNorm blocks, tanh-GELU, tied output head, mean next-token loss,
Adam as `torch.optim.Adam` defines it.  float32 `jax.numpy` under
`default_matmul_precision("highest")`; no kernels; imports nothing of the
program.

The blocks are layer-stacked (leading axis = layer) and scanned, each block
rematerialised in the backward pass, so that 48 layers at 1600 wide fit
beside their Adam state when the leaves are spread over the chips with a
plain `NamedSharding`.  `quant` is the control's lower precision: matmul
operands rounded to fp8 with a per-row scale, e4m3 forward and e5m2 for the
gradient in the backward products, as an fp8 training recipe has it."""

import functools
import math

import jax
import jax.numpy as jnp

from .mistral import fake_fp8

B1, B2, EPS = 0.9, 0.999, 1e-8


def _fp8_e5m2(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-30) / 57344.0
    return (x / scale).astype(jnp.float8_e5m2).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_matmul(x, w):
    """x @ w as an fp8 training recipe computes it: operands rounded to
    e4m3 in the forward product, the incoming gradient to e5m2 in the two
    backward products."""
    return fake_fp8(x, -1) @ fake_fp8(w, 0)


def _fp8_matmul_fwd(x, w):
    xq, wq = fake_fp8(x, -1), fake_fp8(w, 0)
    return xq @ wq, (xq, wq)


def _fp8_matmul_bwd(res, g):
    xq, wq = res
    gq = _fp8_e5m2(g)
    dx = gq @ wq.T
    dw = jnp.einsum("...i,...o->io", xq, gq)
    return dx, dw


_fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


def _lin(x, p, quant):
    return (_fp8_matmul(x, p["w"]) if quant else x @ p["w"]) + p["b"]


def _layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def _block(x, blk, heads, eps, quant):
    b, t, d = x.shape
    hd = d // heads
    h = _layernorm(x, blk["ln1"], eps)
    q, k, v = jnp.split(_lin(h, blk["attn"]["qkv"], quant), 3, axis=-1)
    q, k, v = (a.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
               for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    att = att.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _lin(att, blk["attn"]["proj"], quant)
    h = _layernorm(x, blk["ln2"], eps)
    h = jax.nn.gelu(_lin(h, blk["mlp"]["fc"], quant), approximate=True)
    return x + _lin(h, blk["mlp"]["proj"], quant)


def loss_fn(params, tokens, targets, *, heads, eps, quant=False):
    """params: the stacked tree of `weights.gpt2_params(stacked=True)`."""
    x = params["wte"][tokens] + params["wpe"][None, :tokens.shape[1]]
    block = jax.checkpoint(
        functools.partial(_block, heads=heads, eps=eps, quant=quant))
    x, _ = jax.lax.scan(lambda h, blk: (block(h, blk), None), x,
                        params["blocks"])
    x = _layernorm(x, params["ln_f"], eps)
    logits = _fp8_matmul(x, params["wte"].T) if quant \
        else x @ params["wte"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def leaf_norms(tree):
    """Per-leaf L2 norms; a layer-stacked leaf gives one norm per layer."""
    def norm(path, a):
        stacked = any(getattr(k, "key", None) == "blocks" for k in path) \
            and not any(hasattr(k, "idx") for k in path)
        axes = tuple(range(1, a.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)), axes))
    return jax.tree_util.tree_map_with_path(norm, tree)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "lr", "quant"),
                   donate_argnums=(0,))
def train_step(state, tokens, targets, *, heads, eps, lr, quant=False):
    """state = (params, mu, nu, count) -> (state, loss, grad leaf norms)."""
    with jax.default_matmul_precision("highest"):
        params, mu, nu, count = state
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, targets, heads=heads, eps=eps, quant=quant)
        count = count + 1
        c = count.astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - B1 ** c))
            / (jnp.sqrt(v / (1 - B2 ** c)) + EPS), params, mu, nu)
        return (params, mu, nu, count), loss, leaf_norms(grads)


@jax.jit
def delta_norms(params, params0):
    return leaf_norms(jax.tree.map(lambda a, b: a - b, params, params0))


def flat_norms(norms) -> dict:
    """{"blocks/3/attn/qkv/w": norm, ...} from either a stacked norms tree
    or the program's per-layer list, so that the two line up by name."""
    import numpy as np

    out = {}
    for path, val in jax.tree_util.tree_flatten_with_path(norms)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        val = np.asarray(val, np.float64)
        if val.ndim == 0:
            out["/".join(keys)] = float(val)
        else:  # stacked: blocks/<leaf path> -> blocks/<layer>/<leaf path>
            for i, x in enumerate(val):
                out["/".join([keys[0], str(i)] + keys[1:])] = float(x)
    return out
