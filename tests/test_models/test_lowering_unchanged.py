"""The serving programs of the models that were there lower to the
StableHLO they lowered to before `models/decoder.py` learned of window
layers and the expert FFN moved to `models/experts.py` (PR 33): llama's and
gpt's six each (paged as the session builds them, contiguous through the
step functions; Granite's pair until PR 43, below).  The digests
were taken at the parent commit, on the CPU, at the sizes below; they are
of the text JAX prints, so another JAX version skips.

A MIGRATION PROOF, not a property to keep: the digests say that PR 33 moved
these programs by not one byte, and nothing else.  The next PR that means
to change what one of these programs lowers to deletes its entries here (or
the file, when none is left) and says so; it does not re-record them.

PR 40 was asked to do otherwise, once: the paged `chunk`, `decode`,
`chunk_state` and `decode_state` programs take their small operands as ONE
int32 array, so their six digests were re-recorded at that PR (they say
what those programs lower to SINCE it, and guard the next migration), while
the `verify` and contiguous (`.c.`) entries are the parent's still: that
PR's proof that it moved nothing else.

PR 43 meant to change Granite's pair — the expert FFN sums its pairs inside
the second grouped product (`ops/grouped_matmul.py::grouped_matmul_sum`),
so both programs lower to other text — and deleted its two entries; llama's
and gpt's twelve are that PR's proof that it moved nothing else."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from easydist_tpu.models import gpt, llama
from easydist_tpu.models.decoder import Contiguous, chunk, decode, verify
from easydist_tpu.serve import GenerationSession, ServeConfig

RECORDED_WITH = "0.9.0"
AT_THE_PARENT = {
    "gpt.c.chunk": "8587ec7489438dff", "gpt.c.decode": "b3636895b74ab444",
    "gpt.c.verify": "d4ecdaf5c35b25ba", "gpt.chunk": "3920074e8733334c",
    "gpt.decode": "8698e208932bc22d", "gpt.verify": "490a620b37c845bb",
    "llama.c.chunk": "f9001372d4c7c521", "llama.c.decode": "cc05385022f9eef9",
    "llama.c.verify": "16aa8c3b1cccb986", "llama.chunk": "f8eae5c937c90c66",
    "llama.decode": "50f5fd1fa0de0700", "llama.verify": "c1a419303653216b",
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
    z4, drafts = jnp.zeros((4,), jnp.int32), jnp.zeros((4, 3), jnp.int32)
    for name in ("llama", "gpt"):
        dec, params = _model(name)
        sess = GenerationSession(params, model=dec, config=ServeConfig(
            kv_layout="paged", decode_buckets=(32,), max_decode_slots=4,
            prefill_chunk=8, prefill_batch=2, kv_arena_pages=16,
            enable_prefix_cache=False, speculate_k=0))
        pool = sess._pool_for(32)
        tbl4 = jnp.zeros((4, pool.max_pages), jnp.int32)
        d = sess._paged_defs
        # the paged step programs take ONE operand (PR 40), a row a
        # prefill row or a slot, as the session's builders make it: the
        # table row, two columns, a chunk's 8 tokens
        width = pool.max_pages + 2
        for key, shape in (("chunk", (2, width + 8)), ("decode", (4, width))):
            out[f"{name}.{key}"] = _digest(d[key], pool.arena, params,
                                           jnp.zeros(shape, jnp.int32))
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
