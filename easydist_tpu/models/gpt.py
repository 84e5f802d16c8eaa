"""GPT-2-style decoder transformer, pure jax (reference headline model:
benchmark/torch/model/gpt.py; config GPT bs4 seq1024 d12288 h48 in
benchmark/bench_case.py:5-14).

TPU-first choices: bf16-ready matmuls on the MXU, static causal mask via
lax.select on an iota comparison (no data-dependent control flow), shapes
kept multiples of 128 at real sizes."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from .decoder import (Contiguous, Decoder, Paged, chunk, decode, split_heads,
                      verify)
from .optim import adam_init, adam_update


@dataclass
class GPTConfig:
    vocab: int = 50257
    seq: int = 1024
    dim: int = 768
    heads: int = 12
    layers: int = 12
    dtype: str = "float32"  # compute dtype; params stay float32
    # attention backend: "einsum" (XLA), "flash" (Pallas kernel), "ring"
    # (sequence-parallel ring attention; needs attn_mesh + attn_axis), or
    # "auto" (solver-visible composite — the auto-parallel ILP chooses
    # batch/head/seq-ring/seq-Ulysses per mesh axis)
    attention: str = "einsum"
    attn_mesh: object = None
    attn_axis: str = "sp"
    # per-block rematerialization: "none", "full" (jax.checkpoint each
    # block), or "dots" (save matmul outputs only) — trades recompute for
    # O(layers) instead of O(layers x activations) live memory in the bwd
    remat: str = "none"
    # rolled layers: params["blocks"] is a layer-stacked pytree (leading dim
    # = layers) and the forward runs one lax.scan over it — XLA compiles the
    # block once regardless of depth (the idiomatic Llama-scale form; the
    # auto-parallel path shards through the scan via the composite rule in
    # jaxfront/interpreter.py::_discover_scan)
    scan_layers: bool = False

    @staticmethod
    def small(**kw):
        return GPTConfig(**kw)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=128, seq=32, dim=32, heads=4, layers=2)
        base.update(kw)
        return GPTConfig(**base)


def _init_linear(key, n_in, n_out, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(n_in)
    wk, _ = jax.random.split(key)
    return {"w": jax.random.normal(wk, (n_in, n_out)) * scale,
            "b": jnp.zeros((n_out,))}


def gpt_init(cfg: GPTConfig, key) -> Dict:
    keys = jax.random.split(key, 2 + cfg.layers)
    params = {
        "wte": jax.random.normal(keys[0], (cfg.vocab, cfg.dim)) * 0.02,
        "wpe": jax.random.normal(keys[1], (cfg.seq, cfg.dim)) * 0.01,
        "blocks": [],
        "ln_f": {"g": jnp.ones((cfg.dim,)), "b": jnp.zeros((cfg.dim,))},
    }
    proj_scale = 1.0 / math.sqrt(cfg.dim) / math.sqrt(2.0 * cfg.layers)
    for i in range(cfg.layers):
        bk = jax.random.split(keys[2 + i], 4)
        params["blocks"].append({
            "ln1": {"g": jnp.ones((cfg.dim,)), "b": jnp.zeros((cfg.dim,))},
            "attn": {
                "qkv": _init_linear(bk[0], cfg.dim, 3 * cfg.dim),
                "proj": _init_linear(bk[1], cfg.dim, cfg.dim, proj_scale),
            },
            "ln2": {"g": jnp.ones((cfg.dim,)), "b": jnp.zeros((cfg.dim,))},
            "mlp": {
                "fc": _init_linear(bk[2], cfg.dim, 4 * cfg.dim),
                "proj": _init_linear(bk[3], 4 * cfg.dim, cfg.dim, proj_scale),
            },
        })
    if cfg.scan_layers:
        params["blocks"] = stack_gpt_blocks(params["blocks"])
    return params


def stack_gpt_blocks(blocks):
    """Per-layer block list -> one layer-stacked pytree (leading dim L)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def _layernorm(x, g, b, eps=1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _attention(x, p, cfg: "GPTConfig", dtype, return_kv: bool = False):
    heads = cfg.heads
    b, t, d = x.shape
    hd = d // heads
    qkv = x @ p["qkv"]["w"].astype(dtype) + p["qkv"]["b"].astype(dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def split_heads(t_):
        return t_.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    if cfg.attention == "auto":
        # solver-visible composite: the auto-parallel ILP picks batch/head/
        # sequence (ring or Ulysses) sharding per mesh axis and emission
        # lowers accordingly (ops/attention_prim.py)
        from easydist_tpu.ops.attention_prim import attention as ed_attention

        out = ed_attention(q, k, v, causal=True)
    elif cfg.attention == "flash":
        from easydist_tpu.ops import flash_attention

        out = flash_attention(q, k, v, True)
    elif cfg.attention == "ring":
        from easydist_tpu.parallel import ring_attention

        out = ring_attention(q, k, v, cfg.attn_mesh, axis=cfg.attn_axis,
                             causal=True)
    else:
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        qi = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        att = jnp.where(ki <= qi, att, jnp.array(-1e9, dtype=att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", att, v)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, d)
    out = out @ p["proj"]["w"].astype(dtype) + p["proj"]["b"].astype(dtype)
    if return_kv:
        return out, k, v  # k, v: [b, heads, t, hd], pre-projection
    return out


def gpt_apply(params, cfg: GPTConfig, tokens):
    """tokens: int32 [batch, seq] -> logits [batch, seq, vocab]."""
    dtype = jnp.dtype(cfg.dtype)
    x = params["wte"][tokens].astype(dtype) + params["wpe"].astype(dtype)[None, :tokens.shape[1]]
    def block_fn(blk, x):
        x = x + _attention(
            _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype),
            blk["attn"], cfg, dtype)
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        return x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                    + blk["mlp"]["proj"]["b"].astype(dtype))

    # per-block remat is driven ONLY by cfg.remat; the EASYDIST_REMAT_POLICY
    # env knob applies to compiled-function emission (jaxfront/api.py), a
    # separate mechanism — stacking both from one knob would double-remat
    remat = cfg.remat
    if remat not in ("none", "full", "dots"):
        raise ValueError(f"unknown GPTConfig.remat {cfg.remat!r}; "
                         f"expected none|full|dots")
    if remat == "full":
        block_fn = jax.checkpoint(block_fn)
    elif remat == "dots":
        block_fn = jax.checkpoint(
            block_fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(lambda h, blk: (block_fn(blk, h), None),
                            x, params["blocks"])
    else:
        for blk in params["blocks"]:
            x = block_fn(blk, x)
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    return x.astype(jnp.float32) @ params["wte"].T


# ---------------------------------------------------------------- serving
#
# The cache-carrying forwards `serve/generation.py` compiles.  The layer
# loop, both KV layouts (the contiguous bucket cache and the page arena of
# `kv/arena.py`, int8 included) and the three step kinds live in
# `models/decoder.py`; this file supplies `decoder(cfg)`, the block's
# arithmetic.  Every step is pure and returns the updated cache first, so a
# jit with the cache donated updates it in place (analyze rule SERVE001).


def _block_list(params, cfg):
    """Per-layer block pytrees whether `params["blocks"]` is a list or the
    scan_layers layer-stacked form."""
    blocks = params["blocks"]
    if cfg.scan_layers:
        return [jax.tree_util.tree_map(lambda p, i=i: p[i], blocks)
                for i in range(cfg.layers)]
    return list(blocks)


def _mlp(blk, x, dtype):
    h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
    h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                    + blk["mlp"]["fc"]["b"].astype(dtype))
    return x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                + blk["mlp"]["proj"]["b"].astype(dtype))


def decoder(cfg: GPTConfig) -> Decoder:
    """The model as `models/decoder.py` serves it: learned positions (so
    no cache outgrows `cfg.seq`), LayerNorm, one fused QKV projection,
    heads == kv_heads, GELU MLP, head tied to the embedding."""
    dtype = jnp.dtype(cfg.dtype)

    def embed(params, tokens, pos):
        return params["wte"][tokens].astype(dtype) \
            + params["wpe"][pos].astype(dtype)

    def qkv(blk, x, pos):
        p = blk["attn"]["qkv"]
        h = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype)
        q, k, v = jnp.split(h @ p["w"].astype(dtype) + p["b"].astype(dtype),
                            3, axis=-1)
        return (split_heads(q, cfg.heads), split_heads(k, cfg.heads),
                split_heads(v, cfg.heads))

    def attn_out(blk, x, att):
        p = blk["attn"]["proj"]
        return x + (att @ p["w"].astype(dtype) + p["b"].astype(dtype))

    return Decoder(
        layers=cfg.layers, heads=cfg.heads, kv_heads=cfg.heads,
        head_dim=cfg.dim // cfg.heads, dtype=dtype, max_positions=cfg.seq,
        blocks=lambda params: _block_list(params, cfg), embed=embed,
        qkv=qkv, attn_out=attn_out, ffn=lambda blk, x: _mlp(blk, x, dtype),
        final_norm=lambda params, x: _layernorm(
            x, params["ln_f"]["g"], params["ln_f"]["b"]),
        unembed=lambda params, x: x.astype(jnp.float32) @ params["wte"].T)


def init_kv_cache(cfg: GPTConfig, batch: int, max_len: int, dtype=None):
    """Zeroed contiguous cache (`decoder.Contiguous.init`); `max_len` may
    not exceed the learned position table, `cfg.seq`."""
    return Contiguous.init(decoder(cfg), batch, max_len, dtype)


def init_kv_pages(cfg: GPTConfig, n_pages: int, page_tokens: int,
                  dtype=None, quant_dtype=None, quant_block: int = 0):
    """Zeroed page arena (`decoder.Paged.init`); `quant_dtype="int8"`
    stores block-scaled int8 with scale leaves beside the payload."""
    return Paged.init(decoder(cfg), n_pages, page_tokens, dtype, quant_dtype,
                      quant_block)


def gpt_prefill(params, cfg: GPTConfig, cache, tokens, lengths):
    """Prompt pass: run `tokens` (int32 [batch, t], padded) through the
    model, write every position's K/V into `cache`, and return
    (cache, logits) with logits [batch, vocab] taken at each row's last
    real position (`lengths` - 1).

    The attention is the standard causal forward, so positions < length
    compute exactly what `gpt_apply` computes; the padded tail writes
    garbage K/V that the decode-step length mask never attends."""
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    x = params["wte"][tokens].astype(dtype) \
        + params["wpe"].astype(dtype)[None, :t]
    ks, vs = [], []
    for blk in _block_list(params, cfg):
        attn_out, k, v = _attention(
            _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype),
            blk["attn"], cfg, dtype, return_kv=True)
        x = x + attn_out
        ks.append(k)
        vs.append(v)
        x = _mlp(blk, x, dtype)
    cache = {
        "k": cache["k"].at[:, :, :, :t, :].set(
            jnp.stack(ks).astype(cache["k"].dtype)),
        "v": cache["v"].at[:, :, :, :t, :].set(
            jnp.stack(vs).astype(cache["v"].dtype)),
    }
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    last = jnp.take_along_axis(
        x, (lengths.astype(jnp.int32) - 1)[:, None, None], axis=1)[:, 0]
    return cache, last.astype(jnp.float32) @ params["wte"].T


def gpt_prefill_chunk(params, cfg: GPTConfig, cache, tokens, start_pos,
                      lengths):
    """`decoder.chunk` on the contiguous cache: (cache, logits [b, vocab])."""
    return chunk(decoder(cfg), Contiguous(cache), params, tokens, start_pos,
                 lengths)


def gpt_verify_step(params, cfg: GPTConfig, cache, tokens, pos):
    """`decoder.verify` on the contiguous cache: logits [b, s, vocab]."""
    return verify(decoder(cfg), Contiguous(cache), params, tokens, pos)


def gpt_decode_step(params, cfg: GPTConfig, cache, token, pos):
    """`decoder.decode` on the contiguous cache: logits [b, vocab]."""
    return decode(decoder(cfg), Contiguous(cache), params, token, pos)


def gpt_prefill_chunk_paged(params, cfg: GPTConfig, pages, table, tokens,
                            start_pos, lengths):
    """`decoder.chunk` through a page table; chunk == page_tokens."""
    return chunk(decoder(cfg), Paged(pages, table), params, tokens,
                 start_pos, lengths)


def gpt_verify_step_paged(params, cfg: GPTConfig, pages, table, tokens,
                          pos):
    """`decoder.verify` through a page table."""
    return verify(decoder(cfg), Paged(pages, table), params, tokens, pos)


def gpt_decode_step_paged(params, cfg: GPTConfig, pages, table, token, pos):
    """`decoder.decode` through a page table."""
    return decode(decoder(cfg), Paged(pages, table), params, token, pos)


def gpt_loss(params, cfg: GPTConfig, tokens, targets):
    logits = gpt_apply(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return nll.mean()


def make_gpt_train_step(cfg: GPTConfig, lr=1e-4):
    """Returns (train_step, init_state): state = (params, opt_state);
    step(state, tokens, targets) -> (new_state, loss)."""

    def init_state(key):
        params = gpt_init(cfg, key)
        return (params, adam_init(params))

    def train_step(state, tokens, targets):
        params, opt = state
        loss, grads = jax.value_and_grad(gpt_loss)(params, cfg, tokens, targets)
        new_params, new_opt = adam_update(params, grads, opt, lr=lr)
        return (new_params, new_opt), loss

    return train_step, init_state


def make_gpt_pipeline_step(cfg: GPTConfig, mesh, n_microbatches: int,
                           lr: float = 1e-4, axis: str = "pp",
                           data_axis=None, schedule: str = "gpipe",
                           n_virtual: int = 1):
    """Pipeline-parallel GPT training: transformer blocks pipelined over the
    `pp` mesh axis (stage-stacked params), embedding/positional/head outside
    the pipelined middle (reference scenario: benchmark/torch/pp/gpt).

    schedule="gpipe"/"remat" differentiates through the forward pipeline;
    schedule="1f1b" runs the DAPPLE-class supertick schedule with
    O(n_stages) live microbatches, backpropagating into the embedding and
    head via the pipeline's aux input/head gradients.  n_virtual>1
    interleaves virtual stage chunks under ANY schedule.

    Requires cfg.layers % (n_stages * n_virtual) == 0.  Returns
    (train_step, init_state): state = (params, opt); train_step(state,
    tokens, targets) -> (state, loss); tokens [n_microbatches, mb, seq].
    """
    from easydist_tpu.parallel import (PipelineConfig, spmd_pipeline,
                                       spmd_pipeline_grad)

    n_stages = mesh.shape[axis]
    n_chunks = n_stages * max(1, n_virtual)
    if cfg.layers % n_chunks != 0:
        raise ValueError(f"layers {cfg.layers} not divisible by "
                         f"{n_chunks} pipeline stages x virtual chunks")
    per_stage = cfg.layers // n_chunks
    dtype = jnp.dtype(cfg.dtype)

    def stage_fn(stage_blocks, x):
        # stage_blocks: block pytree with leading dim per_stage
        for i in range(per_stage):
            blk = jax.tree_util.tree_map(lambda p: p[i], stage_blocks)
            x = x + _attention(
                _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype),
                blk["attn"], cfg, dtype)
            h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
            h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                            + blk["mlp"]["fc"]["b"].astype(dtype))
            x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                     + blk["mlp"]["proj"]["b"].astype(dtype))
        return x

    pipe_cfg = PipelineConfig(n_stages, n_microbatches, axis_name=axis,
                              schedule=schedule, data_axis=data_axis,
                              n_virtual=max(1, n_virtual))

    def stack_blocks(params):
        # list of layer pytrees -> [n_chunks, per_stage, ...] leading dims
        blocks = params["blocks"]
        stages = []
        for s in range(n_chunks):
            chunk = blocks[s * per_stage:(s + 1) * per_stage]
            stages.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *chunk))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stages)

    def embed(wte, wpe, tokens_mb):
        seq = tokens_mb.shape[-1]
        return wte[tokens_mb].astype(dtype) \
            + wpe.astype(dtype)[None, None, :seq]

    def head_loss(x_mb, targets_mb, hp):
        x = _layernorm(x_mb, hp["ln_f"]["g"], hp["ln_f"]["b"])
        logits = x.astype(jnp.float32) @ hp["wte"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets_mb[..., None],
                                    axis=-1).mean()

    if schedule == "1f1b":
        pipe_grad = spmd_pipeline_grad(stage_fn, head_loss, mesh, pipe_cfg,
                                       aux=True)

        def loss_and_grads(params, tokens_mb, targets_mb):
            x_mb, emb_vjp = jax.vjp(
                lambda wte, wpe: embed(wte, wpe, tokens_mb),
                params["wte"], params["wpe"])
            hp = {"ln_f": params["ln_f"], "wte": params["wte"]}
            loss, sgrads, dx_mb, dhp = pipe_grad(
                stack_blocks(params), x_mb, targets_mb, hp)
            dwte_emb, dwpe = emb_vjp(dx_mb)
            dblocks = [
                jax.tree_util.tree_map(lambda l: l[s][i], sgrads)
                for s in range(n_chunks) for i in range(per_stage)]
            grads = {"wte": dwte_emb + dhp["wte"], "wpe": dwpe,
                     "ln_f": dhp["ln_f"], "blocks": dblocks}
            return loss, grads
    else:
        pipe = spmd_pipeline(stage_fn, mesh, pipe_cfg)

        def forward(params, tokens_mb):
            # tokens_mb: [M, mb, seq]
            x = embed(params["wte"], params["wpe"], tokens_mb)
            x = pipe(stack_blocks(params), x)
            x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
            return x.astype(jnp.float32) @ params["wte"].T

        def loss_fn(params, tokens_mb, targets_mb):
            logits = forward(params, tokens_mb)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, targets_mb[..., None],
                                        axis=-1).mean()

        def loss_and_grads(params, tokens_mb, targets_mb):
            return jax.value_and_grad(loss_fn)(params, tokens_mb, targets_mb)

    def init_state(key):
        params = gpt_init(cfg, key)
        return (params, adam_init(params))

    def train_step(state, tokens_mb, targets_mb):
        params, opt = state
        loss, grads = loss_and_grads(params, tokens_mb, targets_mb)
        new_params, new_opt = adam_update(params, grads, opt, lr=lr)
        return (new_params, new_opt), loss

    return train_step, init_state
