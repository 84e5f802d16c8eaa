"""The serving programs of the models that were there lower to the
StableHLO they lowered to before `models/decoder.py` learned of window
layers and the expert FFN moved to `models/experts.py` (PR 33): Granite's
pair as the session builds it, and llama's and gpt's six each (paged as the
session builds them, contiguous through the step functions).  The digests
were taken at the parent commit, on the CPU, at the sizes below; they are
of the text JAX prints, so another JAX version skips.

A MIGRATION PROOF, not a property to keep: the digests say that PR 33 moved
these programs by not one byte, and nothing else.  The next PR that means
to change what one of these programs lowers to deletes its entries here (or
the file, when none is left) and says so; it does not re-record them."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from easydist_tpu.models import gpt, granite_hybrid, llama
from easydist_tpu.models.decoder import Contiguous, chunk, decode, verify
from easydist_tpu.serve import GenerationSession, ServeConfig

RECORDED_WITH = "0.9.0"
AT_THE_PARENT = {
    "gpt.c.chunk": "8587ec7489438dff", "gpt.c.decode": "b3636895b74ab444",
    "gpt.c.verify": "d4ecdaf5c35b25ba", "gpt.chunk": "c4a1f05a25df121c",
    "gpt.decode": "721ebfc75efda4f9", "gpt.verify": "490a620b37c845bb",
    "granite.chunk_state": "c85cb3f683d323f8",
    "granite.decode_state": "d89ba6543044bad7",
    "llama.c.chunk": "f9001372d4c7c521", "llama.c.decode": "cc05385022f9eef9",
    "llama.c.verify": "16aa8c3b1cccb986", "llama.chunk": "9d757463f3473a61",
    "llama.decode": "b55e344d021fc71f", "llama.verify": "c1a419303653216b",
}


def _digest(fn, *args):
    """Of the program alone: by shapes, so that where a session's pool was
    put (PR 38: committed to its mesh at birth) is not in the text."""
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        args)
    return hashlib.sha256(
        jax.jit(fn).lower(*args).as_text().encode()).hexdigest()[:16]


def _model(name):
    key = jax.random.PRNGKey(0)
    if name == "granite":
        cfg = granite_hybrid.GraniteHybridConfig.tiny()
        return granite_hybrid.decoder(cfg), \
            granite_hybrid.granite_init(cfg, key)
    if name == "llama":
        cfg = llama.LlamaConfig(vocab=64, seq=32, dim=32, heads=4,
                                kv_heads=2, layers=2, ffn_dim=64)
        return llama.decoder(cfg), llama.llama_init(cfg, key)
    cfg = gpt.GPTConfig(vocab=64, seq=32, dim=32, heads=4, layers=2)
    return gpt.decoder(cfg), gpt.gpt_init(cfg, key)


@pytest.fixture(scope="module")
def lowered():
    if jax.__version__ != RECORDED_WITH:
        pytest.skip(f"digests recorded with jax {RECORDED_WITH}")
    out = {}
    z2, z4 = jnp.zeros((2,), jnp.int32), jnp.zeros((4,), jnp.int32)
    toks, drafts = jnp.zeros((2, 8), jnp.int32), jnp.zeros((4, 3), jnp.int32)
    for name in ("granite", "llama", "gpt"):
        dec, params = _model(name)
        sess = GenerationSession(params, model=dec, config=ServeConfig(
            kv_layout="paged", decode_buckets=(32,), max_decode_slots=4,
            prefill_chunk=8, prefill_batch=2, kv_arena_pages=16,
            enable_prefix_cache=False, speculate_k=0))
        pool = sess._pool_for(32)
        tbl2 = jnp.zeros((2, pool.max_pages), jnp.int32)
        tbl4 = jnp.zeros((4, pool.max_pages), jnp.int32)
        d = sess._paged_defs
        if name == "granite":
            out[name + ".chunk_state"] = _digest(
                d["chunk_state"], pool.arena, params, tbl2, z2, toks, z2,
                z2 + 1)
            out[name + ".decode_state"] = _digest(
                d["decode_state"], pool.arena, params, tbl4,
                jnp.ones((4,), bool), z4, z4)
            sess.close()
            continue
        out[name + ".chunk"] = _digest(d["chunk"], pool.arena, params, tbl2,
                                       toks, z2, z2 + 1)
        out[name + ".decode"] = _digest(d["decode"], pool.arena, params,
                                        tbl4, z4, z4)
        out[name + ".verify"] = _digest(d["verify"], pool.arena, params,
                                        tbl4, drafts, z4)
        sess.close()
        cache = Contiguous.init(dec, 4, 32)
        out[name + ".c.decode"] = _digest(
            lambda c, p, t, pos: decode(dec, Contiguous(c), p, t, pos),
            cache, params, z4, z4)
        out[name + ".c.chunk"] = _digest(
            lambda c, p, t, s, n: chunk(dec, Contiguous(c), p, t, s, n),
            cache, params, jnp.zeros((4, 8), jnp.int32), z4, z4 + 1)
        out[name + ".c.verify"] = _digest(
            lambda c, p, t, pos: verify(dec, Contiguous(c), p, t, pos),
            cache, params, drafts, z4)
    return out


@pytest.mark.parametrize("program", sorted(AT_THE_PARENT))
def test_the_program_lowers_to_the_stablehlo_it_had(lowered, program):
    assert lowered[program] == AT_THE_PARENT[program]
