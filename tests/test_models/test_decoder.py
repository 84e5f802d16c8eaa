"""A model costs one `Decoder` record: a third, toy decoder defined here from
parts of both models (learned positions like gpt; RMSNorm, separate Q/K/V
and grouped-query attention like llama; a GELU MLP; an untied head) is
served through `GenerationSession(params, model=...)`, int8 and speculation
(the n-gram drafter, and the toy as its own draft model) included, with no
step function written for it — and its greedy stream equals its own uncached
full forward.  And the four families that keep more than K/V pages are
served by a `ServeConfig` that names no layout."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.models import axk1, exaone_moe, granite_hybrid, olmo_hybrid
from easydist_tpu.models.decoder import (Contiguous, Decoder, Paged, chunk,
                                         split_heads)
from easydist_tpu.ops import kv_dequantize, kv_quantize
from easydist_tpu.serve import GenerationSession, ServeConfig

VOCAB, SEQ, DIM, HEADS, KV_HEADS, LAYERS = 96, 32, 32, 4, 2, 2
HD = DIM // HEADS


def _norm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-5) * g


def _init(key):
    def w(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape) \
            / math.sqrt(shape[0])
    blocks = [{"n1": jnp.ones((DIM,)), "n2": jnp.ones((DIM,)),
               "wq": w(10 * i, DIM, HEADS * HD),
               "wk": w(10 * i + 1, DIM, KV_HEADS * HD),
               "wv": w(10 * i + 2, DIM, KV_HEADS * HD),
               "wo": w(10 * i + 3, HEADS * HD, DIM),
               "w1": w(10 * i + 4, DIM, 2 * DIM),
               "w2": w(10 * i + 5, 2 * DIM, DIM)}
              for i in range(1, LAYERS + 1)]
    return {"wte": w(0, VOCAB, DIM), "wpe": 0.3 * w(1, SEQ, DIM),
            "blocks": blocks, "norm_f": jnp.ones((DIM,)),
            "head": w(2, DIM, VOCAB)}


def _qkv(blk, x, pos):
    h = _norm(x, blk["n1"])
    return (split_heads(h @ blk["wq"], HEADS),
            split_heads(h @ blk["wk"], KV_HEADS),
            split_heads(h @ blk["wv"], KV_HEADS))


def _ffn(blk, x):
    return x + jax.nn.gelu(_norm(x, blk["n2"]) @ blk["w1"]) @ blk["w2"]


TOY = Decoder(
    layers=LAYERS, heads=HEADS, kv_heads=KV_HEADS, head_dim=HD,
    dtype=jnp.dtype("float32"), max_positions=SEQ,
    blocks=lambda params: params["blocks"],
    embed=lambda params, tokens, pos: params["wte"][tokens]
    + params["wpe"][pos],
    qkv=_qkv, attn_out=lambda blk, x, att: x + att @ blk["wo"], ffn=_ffn,
    final_norm=lambda params, x: _norm(x, params["norm_f"]),
    unembed=lambda params, x: x @ params["head"])
PARAMS = _init(jax.random.PRNGKey(7))


def _full_forward(params, tokens, int8: bool):
    """The reference: one uncached causal forward over the whole sequence,
    sharing only the block arithmetic with the served path.  `int8` stores
    nothing but rounds K and V through the arena's quantizer, which is what
    a read of an int8 page gives back."""
    t = tokens.shape[0]
    x = params["wte"][tokens] + params["wpe"][:t]
    for blk in params["blocks"]:
        q, k, v = _qkv(blk, x[None], None)        # [1, n, t, hd]
        if int8:
            k = kv_dequantize(*kv_quantize(k, 1))
            v = kv_dequantize(*kv_quantize(v, 1))
        k = jnp.repeat(k, HEADS // KV_HEADS, axis=1)
        v = jnp.repeat(v, HEADS // KV_HEADS, axis=1)
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(HD)
        att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -1e9)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(att, -1), v)
        x = x + out[0].transpose(1, 0, 2).reshape(t, DIM) @ blk["wo"]
        x = _ffn(blk, x)
    return _norm(x, params["norm_f"]) @ params["head"]


_full_forward_jit = jax.jit(_full_forward, static_argnames="int8")


def _uncached_greedy(prompt, n_new, int8):
    # padded to SEQ so every length shares one compile: the mask is causal,
    # so the tail cannot reach the position that is read
    ids = list(prompt)
    for _ in range(n_new):
        padded = jnp.asarray(ids + [0] * (SEQ - len(ids)), jnp.int32)
        logits = _full_forward_jit(PARAMS, padded, int8=int8)
        ids.append(int(jnp.argmax(logits[len(ids) - 1])))
    return ids[len(prompt):]


# one prompt longer than a prefill chunk, one that repeats (so the n-gram
# drafter has something to propose), one short
PROMPTS = [[5, 17, 3, 9, 22, 4, 31, 8, 2, 40, 6], [7, 8, 9, 7, 8, 9, 7, 8],
           [11, 12]]
N_NEW = 7


# "draft-model": the toy drafts for itself through `_wire_draft_model` — the
# one place a `Contiguous` cache still meets the session
@pytest.mark.parametrize("layout,spec_k", [
    ("paged", 0), ("paged", 2), ("paged-int8", 0), ("paged-int8", 2),
    ("paged-draft-model", 2)])
def test_toy_decoder_served_equals_its_full_forward(layout, spec_k):
    kw = dict(config=ServeConfig(
        decode_buckets=(SEQ,), max_decode_slots=2, prefill_chunk=8,
        prefill_batch=2, speculate_k=spec_k,
        speculate_drafter="draft_model" if "draft" in layout else "ngram",
        kv_quant_dtype="int8" if layout == "paged-int8" else "none"))
    if "draft" in layout:
        GenerationSession._wire_draft_model(kw, PARAMS, TOY)
    sess = GenerationSession(PARAMS, model=TOY, **kw)
    futs = [sess.submit(p, max_new_tokens=N_NEW) for p in PROMPTS]
    sess.run_until_drained()
    for prompt, fut in zip(PROMPTS, futs):
        assert fut.result(timeout=5)["ids"] == \
            _uncached_greedy(prompt, N_NEW, layout == "paged-int8")
    if spec_k:
        assert sess.metrics.counter("verify_steps") > 0
    if layout == "paged-int8":
        arena = next(iter(sess._pools.values())).arena
        assert sorted(arena) == ["k", "k_scale", "v", "v_scale"]


FAMILIES = {   # beside or in place of K/V pages: states, rings, latents
    "axk1": (axk1, axk1.AxK1Config, axk1.axk1_init),
    "exaone_moe": (exaone_moe, exaone_moe.ExaoneMoeConfig,
                   exaone_moe.exaone_init),
    "granite_hybrid": (granite_hybrid, granite_hybrid.GraniteHybridConfig,
                       granite_hybrid.granite_init),
    "olmo_hybrid": (olmo_hybrid, olmo_hybrid.OlmoHybridConfig,
                    olmo_hybrid.olmo_hybrid_init),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_config_that_names_no_layout_serves_every_family(family):
    module, config, init = FAMILIES[family]
    cfg = config.tiny()
    sess = GenerationSession(
        init(cfg, jax.random.PRNGKey(3)), model=module.decoder(cfg),
        config=ServeConfig(enable_prefix_cache=False, speculate_k=0,
                           decode_buckets=(64,), max_decode_slots=2,
                           prefill_chunk=8))
    prompt = np.random.default_rng(4).integers(1, cfg.vocab, size=11).tolist()
    fut = sess.submit(prompt, max_new_tokens=4)
    sess.run_until_drained()
    assert len(fut.result(timeout=5)["ids"]) == 4
    assert sess.stats()["buckets"][64]["kv_pool"]["in_use"] == 0
    sess.close()


def test_position_bound_comes_from_the_record():
    """`max_positions` is the one place the learned-table bound lives: the
    cache refuses to outgrow it and so does the session's bucket list."""
    with pytest.raises(ValueError, match="learned position table"):
        Contiguous.init(TOY, 1, SEQ + 1)
    with pytest.raises(ValueError, match="decode_buckets"):
        GenerationSession(PARAMS, model=TOY,
                          config=ServeConfig(decode_buckets=(2 * SEQ,)))


def test_paged_chunk_must_fill_one_page():
    pages = Paged.init(TOY, 4, 8)
    table = jnp.arange(4, dtype=jnp.int32)[None, :]
    with pytest.raises(ValueError, match="page_tokens"):
        chunk(TOY, Paged(pages, table), PARAMS, jnp.zeros((1, 4), jnp.int32),
              jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))
