"""The paged arena is written in place: the static witness.

A paged step (`*_decode_step_paged`, `*_prefill_chunk_paged`,
`*_verify_step_paged`) takes the arena as a pytree of per-layer leaves
(`kv/arena.py`) and must hand every leaf back through exactly one write
whose operand is that input leaf — never a slice of a stacked array, never
a `concatenate` back into one.  Two readings, neither of which needs a
chip:

  * the jaxpr: the only equations whose output is as large as a payload
    leaf are those writes, and nothing copies, slices or concatenates a
    leaf;
  * the program XLA compiles with the arena donated (CPU backend): every
    leaf is input/output-aliased and the temporaries stay under one leaf;
  * the decode step compiled for a described TPU v5e at the chat cell's
    widths (one layer): there a row write indexed `[page, :, offset, :]`
    made XLA re-lay the whole leaf out before and after the scatter, which
    no CPU compile shows;
  * the A.X-K1 cell's two programs compiled for that v5e: a latent leaf
    whose rows were not whole lane tiles was given another layout than the
    kernels read, and copied whole;
  * the Granite 4.0-H cell's expert FFN compiled for that v5e: the routed
    pairs' products are combined with no float32 copy of them (it lives in
    this file because one worker alone may load the TPU's compiler), and
    the three expert cells' at their chunk shapes: nothing as large as the
    k x rows pair slots exists at all;
  * the flash training kernels at the train cell's call and a streamed
    length: Mosaic takes the walked loops and the lane-major statistics.

This is the guard that keeps a later model file from stacking again
(PR 28: the stacked arena cost 57 % of a decode round on the chip).

Also pinned here: the page wire format.  The session's `_page_export` gives
bitwise the stack of one page across the leaves, `{key: [layers, heads,
page_tokens, *]}`, and export -> import -> export is the identity, so fleet
manifests, host-tier entries and trie restores do not depend on the
arena's layout.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core
from jax.sharding import SingleDeviceSharding

from easydist_tpu.models import gpt, llama
from easydist_tpu.serve import GenerationSession, ServeConfig

N_PAGES, PT, ROWS, MAX_PAGES, VERIFY_S = 256, 8, 2, 4, 3

MODELS = {
    "gpt": (gpt, gpt.GPTConfig.tiny, gpt.gpt_init, {
        "decode": gpt.gpt_decode_step_paged,
        "chunk": gpt.gpt_prefill_chunk_paged,
        "verify": gpt.gpt_verify_step_paged}),
    "llama": (llama, llama.LlamaConfig.tiny, llama.llama_init, {
        "decode": llama.llama_decode_step_paged,
        "chunk": llama.llama_prefill_chunk_paged,
        "verify": llama.llama_verify_step_paged}),
}

# primitives that would move a whole leaf: the stacked arena's slice and
# stack, and their relatives
_MOVES_A_LEAF = {"concatenate", "slice", "dynamic_slice", "squeeze", "copy",
                 "dynamic_update_slice", "convert_element_type", "transpose",
                 "reshape", "broadcast_in_dim", "pad"}


def _step(model, quant, program):
    """(fn(arena, *data) -> (arena, picks), arena, data) for one paged
    step on the tiny config, with the session's argmax on top."""
    mod, tiny, init, steps = MODELS[model]
    cfg = tiny()
    params = init(cfg, jax.random.PRNGKey(0))
    arena = mod.init_kv_pages(cfg, N_PAGES, PT,
                              quant_dtype="int8" if quant else None)
    # row 0 owns pages 0..3, row 1 is dead (sentinel): its writes drop
    table = np.full((ROWS, MAX_PAGES), N_PAGES, np.int32)
    table[0] = np.arange(MAX_PAGES)
    table = jnp.asarray(table)
    if program == "decode":
        data = (table, jnp.asarray([5, 0]), jnp.asarray([9, 0]))
    elif program == "chunk":
        data = (table, jnp.ones((ROWS, PT), jnp.int32),
                jnp.asarray([PT, 0]), jnp.asarray([2 * PT, 0]))
    else:  # a verify window that straddles a page boundary
        data = (table, jnp.ones((ROWS, VERIFY_S), jnp.int32),
                jnp.asarray([PT - 1, 0]))

    def fn(arena, *data):
        arena, logits = steps[program](params, cfg, arena, *data)
        return arena, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return fn, arena, data


def _nbytes(aval) -> int:
    return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize


@pytest.mark.parametrize("program", ["decode", "chunk", "verify"])
@pytest.mark.parametrize("quant", [False, True], ids=["exact", "int8"])
@pytest.mark.parametrize("model", ["llama", "gpt"])
def test_paged_step_writes_every_leaf_in_place(model, quant, program):
    fn, arena, data = _step(model, quant, program)
    leaves = jax.tree_util.tree_leaves(arena)
    n = len(leaves)
    leaf_bytes = min(int(x.nbytes) for x in arena["k"])

    # ---- the jaxpr
    jaxpr = jax.make_jaxpr(fn)(arena, *data).jaxpr
    arena_in = {v: i for i, v in enumerate(jaxpr.invars[:n])}
    arena_out = {v: i for i, v in enumerate(jaxpr.outvars[:n])}
    assert len(arena_out) == n, "two arena outputs are one value"
    written = {}            # written leaf var -> flat leaf index
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        touched = [v for v in eqn.invars
                   if not isinstance(v, jex_core.Literal)
                   and (v in arena_in or v in written)]
        if name == "scatter" and eqn.invars[0] in arena_in:
            idx = arena_in[eqn.invars[0]]
            out = eqn.outvars[0]
            assert idx not in written.values(), \
                f"leaf {idx} is written twice"
            assert arena_out.get(out) == idx, \
                f"the write of leaf {idx} is not returned as leaf {idx}"
            written[out] = idx
            continue
        assert not (touched and name in _MOVES_A_LEAF), \
            f"`{name}` moves an arena leaf: {eqn}"
        assert not any(v in arena_in for v in touched), \
            f"`{name}` reads an input leaf before its write: {eqn}"
        for out in eqn.outvars:
            assert _nbytes(out.aval) < leaf_bytes, \
                (f"`{name}` produces {_nbytes(out.aval)} bytes "
                 f"{out.aval.shape}, as large as a leaf ({leaf_bytes})")
    assert sorted(written.values()) == list(range(n)), \
        "a leaf comes back without passing through its write"

    # ---- the compiled program, arena donated
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(arena, *data).compile()
    header = compiled.as_text().split("\n", 1)[0]
    aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         header)
    assert sorted((int(o), int(i)) for o, i in aliases) == \
        [(i, i) for i in range(n)], header
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes


@pytest.fixture(scope="module")
def v5e_chip():
    """A described (not attached) v5e chip to compile for; described inside
    the fixture so that only the worker given this file loads libtpu."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else it logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to compile for
            pytest.skip(f"no v5e topology can be described here: {e}")
        # an executable for a described chip cannot be read back from the
        # persistent cache: keep it out
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


def _described(chip, tree):
    """The tree's shapes on the described chip: there is no device to hold
    an array."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=chip), tree)


def test_decode_step_writes_in_place_on_tpu(v5e_chip, monkeypatch):
    """The chat cell's decode step (BENCHMARK.json: Mistral-7B widths, 32
    slots, 576 pages of 64 tokens), one layer, with the Pallas kernel."""
    from easydist_tpu import config as edconfig

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    # the backend here is the CPU: steer the step onto its TPU path
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    cfg = llama.LlamaConfig(vocab=32768, seq=2048, dim=4096, heads=32,
                            kv_heads=8, layers=1, ffn_dim=14336,
                            rope_theta=1e6, dtype="bfloat16")
    slots, max_pages = 32, 32

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                 llama.llama_init(cfg, key)),
        jax.random.PRNGKey(0)))
    arena = _described(v5e_chip, jax.eval_shape(
        lambda: llama.init_kv_pages(cfg, 576, 64)))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    table = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32,
                                 sharding=v5e_chip)

    def step(arena, params, table, token, pos):
        return llama.llama_decode_step_paged(params, cfg, arena, table,
                                             token, pos)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arena, params, table, rows, rows).compile()
    leaf_bytes = 576 * 8 * 64 * 128 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * leaf_bytes
    assert mem.temp_size_in_bytes < leaf_bytes, \
        "a leaf is copied (re-laid out?) round its write"
    assert "tpu_custom_call" in compiled.as_text()


# pages whose minor dim is not whole 128-lane tiles: GPT-2's heads of 64, a
# decode round and a chunk of 64 queries, and the int8 arena's scale pages
# of 1 and 2 blocks a row (head_dim, scale blocks, chunk)
NARROW_PAGES = [pytest.param(64, 0, 0, id="heads-of-64-decode"),
                pytest.param(64, 0, 64, id="heads-of-64-chunk"),
                pytest.param(128, 1, 0, id="int8-one-block"),
                pytest.param(128, 2, 0, id="int8-two-blocks"),
                pytest.param(64, 1, 0, id="int8-heads-of-64")]


@pytest.mark.parametrize("d,blocks,chunk", NARROW_PAGES)
def test_narrow_pages_compile_on_tpu(v5e_chip, d, blocks, chunk):
    """The paged kernels copy pages by hand, and Mosaic slices an HBM ref
    along whole 128-lane tiles only — which the cross-lowering of
    tests/test_ops/test_tpu_lowering.py cannot see.  Narrow pages reach the
    kernel in whole lanes (`_whole_lanes`): the v5e's compiler takes the
    call, and what is copied round it is the leaves once, never padded
    (heads of 64: the BlockSpec form's operand was the leaf laid out again
    with every row padded to 128 lanes, twice these bytes)."""
    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    n_pages, kvh, pt, rows, max_pages = 48, 12, 64, 8, 16

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    q = aval((rows, kvh) + ((chunk,) if chunk else ()) + (d,), jnp.bfloat16)
    ints = (aval((rows, max_pages), jnp.int32), aval((rows,), jnp.int32))
    pages = (aval((n_pages, kvh, pt, d),
                  jnp.int8 if blocks else jnp.bfloat16),) * 2
    if blocks:
        pages += (aval((n_pages, kvh, pt, blocks), jnp.float32),) * 2
        call = fa.flash_paged_decode_quant_attention
    else:
        call = fa.flash_paged_chunk_attention if chunk \
            else fa.flash_paged_decode_attention
    compiled = jax.jit(lambda q, *a: call(q, *a, interpret=False)).lower(
        q, *pages, *ints).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if not blocks:
        leaf_bytes = n_pages * kvh * pt * d * 2
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 2 * leaf_bytes + 2 ** 20


# the flash training kernels: (rows, positions, head_dim, dtype) — the train
# cell's own call on a chip (a row's other side held whole, walked by a loop
# whose bounds are the grid position's), its float32 and head-128 kin, and a
# length that streams
FLASH_TRAIN_SHAPES = [
    pytest.param(25, 1024, 64, jnp.bfloat16, id="train-cell"),
    pytest.param(25, 1024, 64, jnp.float32, id="train-cell-f32"),
    pytest.param(8, 1024, 128, jnp.bfloat16, id="d128"),
    pytest.param(4, 8192, 128, jnp.bfloat16, id="streamed"),
]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("rows,t,d,dtype", FLASH_TRAIN_SHAPES)
def test_flash_training_kernels_compile_on_tpu(v5e_chip, rows, t, d, dtype,
                                               causal):
    """Mosaic's own compile of the forward, dQ and dK/dV kernels (it lives
    in this file because one worker alone may load the TPU's compiler): a
    loop with dynamic bounds over sublane slices of a held row, products
    that contract both operands' last axes, lse and delta with positions
    on the lanes — what the cross-lowering of test_tpu_lowering.py cannot
    refuse."""
    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    x = jax.ShapeDtypeStruct((1, rows, t, d), dtype, sharding=v5e_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal, interpret=False).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 3


@pytest.mark.parametrize("quant", [False, True], ids=["exact", "int8"])
@pytest.mark.parametrize("model", ["llama", "gpt"])
def test_page_wire_format_is_the_stack_of_leaf_pages(model, quant):
    """Through the session's own compiled `_page_export` / `_page_import`,
    on an arena a served prompt has written."""
    _, tiny, init, _ = MODELS[model]
    cfg = tiny()
    factory = (GenerationSession.for_gpt if model == "gpt"
               else GenerationSession.for_llama)
    sess = factory(init(cfg, jax.random.PRNGKey(0)), cfg, config=ServeConfig(
        decode_buckets=(32,), max_decode_slots=2,
        prefill_chunk=PT, prefill_batch=2,
        kv_quant_dtype="int8" if quant else "none"))
    fut = sess.submit(list(range(1, 2 * PT + 4)), max_new_tokens=3)
    sess.run_until_drained()
    assert len(fut.result(timeout=5)["ids"]) == 3
    pool = next(iter(sess._pools.values()))
    before = jax.tree.map(np.asarray, pool.arena)
    assert sorted(before) == (["k", "k_scale", "v", "v_scale"] if quant
                              else ["k", "v"])
    used = [p for p in range(pool.pool.n_pages) if before["k"][0][p].any()]
    empty = [p for p in range(pool.pool.n_pages)
             if not any(leaf[p].any() for leaves in before.values()
                        for leaf in leaves)]
    src, dst = used[0], empty[0]

    page = sess._paged_c("export")(pool.arena, jnp.asarray(src, jnp.int32))
    assert sorted(page) == sorted(before)
    for key, leaves in before.items():
        assert page[key].shape == (cfg.layers,) + leaves[0].shape[1:]
        assert page[key].dtype == leaves[0].dtype
        np.testing.assert_array_equal(
            np.asarray(page[key]), np.stack([leaf[src] for leaf in leaves]))

    # export -> import (at another page) -> export is the identity
    pool.arena = sess._paged_c("import")(pool.arena, page,
                                         jnp.asarray(dst, jnp.int32))
    again = sess._paged_c("export")(pool.arena, jnp.asarray(dst, jnp.int32))
    for key in page:
        np.testing.assert_array_equal(np.asarray(again[key]),
                                      np.asarray(page[key]))
    # and the import wrote that page alone
    for key, leaves in pool.arena.items():
        for li, leaf in enumerate(leaves):
            np.testing.assert_array_equal(
                np.delete(np.asarray(leaf), dst, axis=0),
                np.delete(before[key][li], dst, axis=0))
    sess.close()


def _compile_the_grouped_products(monkeypatch):
    """The backend here is the CPU: steer both grouped products of an
    expert layer onto their kernels."""
    import functools

    from easydist_tpu.ops import grouped_matmul as gm

    for name in ("grouped_matmul", "grouped_matmul_sum"):
        monkeypatch.setattr(gm, name, functools.partial(
            getattr(gm, name), backend="pallas", interpret=False))


def test_hybrid_decode_step_writes_arena_and_state_in_place_on_tpu(
        v5e_chip, monkeypatch):
    """The Granite 4.0-H cell's decode round (BENCHMARK.json: published
    widths, 64 state slots, 36 held experts, pages of 256 tokens), one
    Mamba-2 and one attention layer, with its three Pallas kernels: the
    arena's two leaves AND the state's two are donated and handed back
    through writes in place — the SSM leaf (268 MB) by the state-update
    kernel's own alias — so the temporaries stay far under one SSM leaf."""
    import functools

    from easydist_tpu import config as edconfig
    from easydist_tpu.models import granite_hybrid as gh
    from easydist_tpu.models.decoder import Paged, State, decode
    from easydist_tpu.ops import grouped_matmul as gm
    from easydist_tpu.ops import ssm

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    # the backend here is the CPU: steer the step onto its TPU path
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    monkeypatch.setattr(ssm, "ssm_decode_update", functools.partial(
        ssm.ssm_decode_update, backend="pallas", interpret=False))
    _compile_the_grouped_products(monkeypatch)
    cfg = gh.GraniteHybridConfig(vocab=50176,
                                 layer_types=("mamba", "attention"),
                                 experts_held=(0, 36))
    dec = gh.decoder(cfg)
    slots, n_pages, pt, max_pages = 64, 768, 256, 16

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: gh.granite_init(cfg, key), jax.random.PRNGKey(0)))
    cache = _described(v5e_chip, jax.eval_shape(
        lambda: {**Paged.init(dec, n_pages, pt), **State.init(dec, slots)}))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_chip)
    table = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32,
                                 sharding=v5e_chip)

    def step(cache, params, table, live, token, pos):
        pages, leaves = State.split(dec, cache)
        kv = Paged(pages, table)
        cache, logits = decode(dec, kv, params, token, pos,
                               state=State(leaves, live))
        return cache, jnp.argmax(logits, -1), kv.counters

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        cache, params, table, live, rows, rows).compile()
    page_leaf = n_pages * 8 * pt * 128 * 2
    ssm_leaf = slots * 128 * 64 * 128 * 4
    conv_leaf = slots * 3 * 8448 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * page_leaf + ssm_leaf + conv_leaf
    assert mem.temp_size_in_bytes < ssm_leaf // 2, \
        "a state or arena leaf is copied round its write"
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6, \
        "state update, paged decode attention, two grouped products a layer"


def test_hybrid_chunk_step_keeps_the_scan_in_one_kernel_on_tpu(
        v5e_chip, monkeypatch):
    """The Granite 4.0-H cell's CHUNK call (BENCHMARK.json: published
    widths, four rows of 256, 64 state slots, 36 held experts), one Mamba-2
    and one attention layer: the chunked scan lowers through Mosaic at the
    cell's head shape (p 64, d_state 128) as ONE call named
    `ssd_chunk_scan`; no float32 array of rank 4 in the compiled program
    ends in two extents of the window's positions (the jnp form's `seg`,
    `decay` and `mix`, [4, 128, 256, 256] = 134 MB each, which XLA kept
    inside one fusion and a kernel keeps in fast memory); and the arena's
    two leaves and the state's two are donated and handed back through
    writes in place — no copy of the SSM leaf (268 MB), whose four rows the
    kernel updates through its own alias."""
    import functools

    from easydist_tpu import config as edconfig
    from easydist_tpu.models import granite_hybrid as gh
    from easydist_tpu.models.decoder import Paged, State, chunk
    from easydist_tpu.ops import ssm

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    # the backend here is the CPU: steer the step onto its TPU path
    monkeypatch.setattr(edconfig, "prefill_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    monkeypatch.setattr(ssm, "ssd_chunk_scan", functools.partial(
        ssm.ssd_chunk_scan, backend="pallas", interpret=False))
    _compile_the_grouped_products(monkeypatch)
    cfg = gh.GraniteHybridConfig(vocab=50176,
                                 layer_types=("mamba", "attention"),
                                 experts_held=(0, 36))
    dec = gh.decoder(cfg)
    slots, n_pages, pt, max_pages, c_rows = 64, 768, 256, 16, 4
    assert ssm._ssd_tiles(pt, cfg.mamba_heads, cfg.mamba_head_dim,
                          cfg.d_state) == (2, 16, 128)

    def aval(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: gh.granite_init(cfg, key), jax.random.PRNGKey(0)))
    cache = _described(v5e_chip, jax.eval_shape(
        lambda: {**Paged.init(dec, n_pages, pt), **State.init(dec, slots)}))

    def chunk_step(cache, params, table, at, tokens, start, lengths):
        pages, leaves = State.split(dec, cache)
        st = State(leaves, at < slots, at, fresh=start == 0)
        cache, logits = chunk(dec, Paged(pages, table), params, tokens,
                              start, lengths, state=st)
        return cache, jnp.argmax(logits, -1)

    compiled = jax.jit(chunk_step, donate_argnums=(0,)).lower(
        cache, params, aval((c_rows, max_pages)), aval((c_rows,)),
        aval((c_rows, pt)), aval((c_rows,)), aval((c_rows,))).compile()
    page_leaf = n_pages * 8 * pt * 128 * 2
    ssm_leaf = slots * 128 * 64 * 128 * 4
    conv_leaf = slots * 3 * 8448 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * page_leaf + ssm_leaf + conv_leaf
    assert mem.temp_size_in_bytes < ssm_leaf, \
        "a state or arena leaf is copied round its write"
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 6, \
        "the scan, paged chunk attention, two grouped products a layer"
    (scan,) = [ln for ln in calls if "ssd_chunk_scan" in ln.split(" = ")[0]]
    # y first, [rows, positions, heads x head_dim]; the four rows' state,
    # aliased to the operand it came in as
    assert f"(f32[{c_rows},{pt},8192]" in scan
    assert "output_to_operand_aliasing={{1}: (9, {})}" in scan
    square = re.findall(rf"f32\[\d+,\d+,{pt},{pt}\]", text)
    assert not square, sorted(set(square))


def test_window_decode_step_writes_rings_and_arena_in_place_on_tpu(
        v5e_chip, monkeypatch):
    """The K-EXAONE cell's decode round (BENCHMARK.json: published widths,
    64 slots, 16 held experts, pages of 256 tokens, a window of 128), a
    sliding, a full and a sliding layer: the arena's two leaves and the
    rings' four are donated and handed back through writes in place — the
    program's temporaries stay under ONE ring leaf (16 MiB) — and the
    kernels are the full layer's paged decode call and two grouped products
    an expert layer: the rings are read by a fused dot, not by a kernel."""
    import functools

    from easydist_tpu import config as edconfig
    from easydist_tpu.models import exaone_moe as em
    from easydist_tpu.models.decoder import Paged, State, decode
    from easydist_tpu.ops import grouped_matmul as gm

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    # the backend here is the CPU: steer the step onto its TPU path
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    _compile_the_grouped_products(monkeypatch)
    cfg = em.ExaoneMoeConfig(
        vocab=19200, layer_types=("sliding_attention", "full_attention",
                                  "sliding_attention"),
        mlp_layer_types=("dense", "sparse", "sparse"), experts_held=(0, 16))
    dec = em.decoder(cfg)
    slots, n_pages, pt, max_pages = 64, 2048, 256, 32

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: em.exaone_init(cfg, key), jax.random.PRNGKey(0)))
    cache = _described(v5e_chip, jax.eval_shape(
        lambda: {**Paged.init(dec, n_pages, pt), **State.init(dec, slots)}))
    assert sorted(cache) == ["k", "ring_k", "ring_v", "v"]
    assert len(cache["k"]) == 1 and len(cache["ring_k"]) == 2
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_chip)
    table = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32,
                                 sharding=v5e_chip)

    def step(cache, params, table, live, token, pos):
        pages, leaves = State.split(dec, cache)
        kv = Paged(pages, table)
        cache, logits = decode(dec, kv, params, token, pos,
                               state=State(leaves, live))
        return cache, jnp.argmax(logits, -1), kv.counters

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        cache, params, table, live, rows, rows).compile()
    page_leaf = n_pages * 8 * pt * 128 * 2
    ring_leaf = slots * 8 * 128 * 128 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * page_leaf + 4 * ring_leaf
    assert mem.temp_size_in_bytes < ring_leaf, \
        "a ring or arena leaf is copied round its write"
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5, \
        "paged decode attention on the full layer, two grouped products " \
        "an expert layer"


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_latent_steps_write_the_arena_in_place_on_tpu(v5e_chip, monkeypatch,
                                                      program):
    """The A.X-K1 cell's two programs (BENCHMARK.json: published widths, 32
    slots, 12 held experts, 2,048 pages of 256 tokens, a bucket of 16,384),
    the dense layer and one expert layer: the arena's ONE leaf a layer
    ([2048, 256, 640]: a row of 576 values in five whole lane tiles) is
    donated and handed back through a write in place — with rows of 576 the
    TPU gave the leaf another layout than the kernel reads and copied it,
    whole, every call — and the temporaries stay far under one leaf, so
    nothing the size of a bucket's expanded keys and values (2 x 16,384 x
    64 x 256 x 2 B = 1.07 GB a row) is ever formed."""
    import functools

    from easydist_tpu import config as edconfig
    from easydist_tpu.models import axk1
    from easydist_tpu.models.decoder import Latent, chunk, decode
    from easydist_tpu.ops import grouped_matmul as gm

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    # the backend here is the CPU: steer the step onto its TPU path
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(edconfig, "prefill_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    _compile_the_grouped_products(monkeypatch)
    cfg = axk1.AxK1Config(vocab=20480, layers=2, experts_held=(0, 12))
    dec = axk1.decoder(cfg)
    slots, n_pages, pt, max_pages = 32, 2048, 256, 64
    rows = slots if program == "decode" else 1     # ONE prefill row

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: axk1.axk1_init(cfg, key), jax.random.PRNGKey(0)))
    cache = _described(v5e_chip, jax.eval_shape(
        lambda: Latent.init(dec, n_pages, pt)))
    assert sorted(cache) == ["latent"] and len(cache["latent"]) == 2
    assert cache["latent"][0].shape == (n_pages, pt, 640)
    ints = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip)
    table = jax.ShapeDtypeStruct((rows, max_pages), jnp.int32,
                                 sharding=v5e_chip)

    if program == "decode":
        def step(cache, params, table, token, pos):
            kv = Latent(cache, table)
            cache, logits = decode(dec, kv, params, token, pos)
            return cache, jnp.argmax(logits, -1), kv.counters
        args = (cache, params, table, ints, ints)
    else:
        def step(cache, params, table, tokens, start, lengths):
            kv = Latent(cache, table)
            cache, logits = chunk(dec, kv, params, tokens, start, lengths)
            return cache, jnp.argmax(logits, -1), kv.counters
        args = (cache, params, table, jax.ShapeDtypeStruct(
            (rows, pt), jnp.int32, sharding=v5e_chip), ints, ints)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(*args).compile()
    leaf = n_pages * pt * 640 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * leaf
    assert mem.temp_size_in_bytes < leaf // 4, \
        "a latent leaf is copied round its write, or a bucket is expanded"
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4, \
        "a latent kernel a layer, two grouped products on the expert layer"


_ENTRY_LINE = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?)\s([a-z][a-z0-9\-]*)\(")
_ARRAY = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def _entry_results(hlo_text):
    """[(opcode, [(dtype, elements) of each array of its result])] for the
    instructions of the compiled module's entry computation."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    out = []
    for line in entry.splitlines()[2:]:
        m = _ENTRY_LINE.match(line)
        assert m, line
        out.append((m.group(2), [
            (dt, int(np.prod([int(d) for d in dims.split(",") if d])))
            for dt, dims in _ARRAY.findall(m.group(1))]))
    return out


@pytest.mark.parametrize("rows", [1024, 64], ids=["chunk", "round"])
def test_expert_combine_makes_no_float32_copy_of_the_pairs_on_tpu(
        v5e_chip, monkeypatch, rows):
    """`expert_ffn` at the Granite cell's widths (36 held experts, top-10,
    bf16) for a chunk call's 4 x 256 rows and a round's 64: nothing the
    size of the `[10 * rows, 4096]` pair slots exists in float32, and no
    pass only moves that much.  Token-major the gathered products were
    viewed `[rows, 10, 4096]`, and a second-minor 10 pads to the tile's 16:
    XLA wrote 268 MB of float32 a layer and read it back (PR 32: 10 ms of
    a 58.6 ms chunk call); a cast written on the whole slot-major view was
    a pass of 168 MB all the same.  Since PR 43 the products are summed
    inside the second kernel (the witness below): this one guards the way
    back."""
    import functools

    from easydist_tpu.models import granite_hybrid as gh
    from easydist_tpu.ops import grouped_matmul as gm

    _compile_the_grouped_products(monkeypatch)
    cfg = gh.GraniteHybridConfig(vocab=50176, layer_types=("mamba",),
                                 experts_held=(0, 36))

    blk = _described(v5e_chip, jax.eval_shape(
        lambda key: gh.granite_init(cfg, key),
        jax.random.PRNGKey(0)))["blocks"][0]
    u = jax.ShapeDtypeStruct((rows, cfg.dim), jnp.bfloat16,
                             sharding=v5e_chip)
    valid = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=v5e_chip)
    compiled = jax.jit(functools.partial(gh.expert_ffn, cfg)).lower(
        blk, u, valid).compile()

    pairs = rows * cfg.top_k * cfg.dim
    results = _entry_results(compiled.as_text())
    assert sum(op == "custom-call" for op, _ in results) == 2
    for op, arrays in results:
        for dt, n in arrays:
            assert not (dt == "f32" and n >= pairs), \
                f"`{op}` holds the pairs in float32 ({n} elements)"
            assert not (n >= pairs and op.split("-")[0] in
                        ("reshape", "copy", "convert", "transpose")), \
                f"`{op}` only moves the pairs ({dt}, {n} elements)"
    if rows == 1024:    # 269 MB token-major: the float32 view
        assert compiled.memory_analysis().temp_size_in_bytes < 140e6


EXPERT_CELLS = {   # cell -> (dim, an expert's width, held, top_k, the rows
    #                         of a chunk call)
    "granite": (4096, 768, 36, 10, 1024),
    "kexaone": (6144, 2048, 16, 8, 512),
    "axk1": (7168, 2048, 12, 8, 512),
}


@pytest.mark.parametrize("cell", list(EXPERT_CELLS))
def test_expert_ffn_holds_nothing_as_wide_as_the_pair_slots_on_tpu(
        v5e_chip, monkeypatch, cell):
    """`models/experts.py::expert_ffn` at each expert cell's widths and its
    chunk call's rows, compiled for the v5e: the sum back to tokens follows
    the places of the blocked layout (the pairs of HELD experts) inside the
    second product, so NO array of k x rows x dim elements exists in any
    type — the parent gathered one 8-14 KB row for every (token, choice)
    slot, half to fifteen sixteenths of them for experts held elsewhere,
    and read it back as k slices (PR 43: 6.35 + 1.1 ms of granite's 47.3 ms
    chunk call) — and no gather or scatter is that wide either.  (The
    blocked layout's own arrays stay: a row a place.)"""
    from easydist_tpu.models import experts

    _compile_the_grouped_products(monkeypatch)
    dim, width, held, k, rows = EXPERT_CELLS[cell]
    bf16 = jnp.bfloat16

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    compiled = jax.jit(lambda u, idx, gate, w1, w2, valid: experts.expert_ffn(
        u, idx, gate, w1, w2, (0, held), bf16, valid)).lower(
            aval((rows, dim), bf16), aval((rows, k), jnp.int32),
            aval((rows, k), jnp.float32), aval((held, dim, 2 * width), bf16),
            aval((held, width, dim), bf16), aval((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # nowhere, a fusion's body included, the pair slots by the model's width
    for shape in ((k * rows, dim), (k, rows, dim), (rows, k, dim)):
        assert "[" + ",".join(map(str, shape)) + "]" not in text, shape
    # and of the program's own instructions ONE result is that tall and
    # `dim` wide: the dispatch gather's, a row a PLACE of the blocked
    # layout (whole blocks: `ceil(k * rows / tm) + held` of 128), which the
    # first product reads; nothing that wide is gathered or scattered after
    entry = text[text.index("\nENTRY "):]
    tall = [(m.group(2), dims) for m in map(
        _ENTRY_LINE.match, entry[:entry.index("\n}")].splitlines()[2:])
        for _, dims in _ARRAY.findall(m.group(1))
        if dims.endswith(f",{dim}")
        and int(dims.split(",")[0]) >= k * rows]
    places = (-(-k * rows // 128) + held) * 128
    assert tall == [("fusion", f"{places},{dim}")], tall
    assert not [op for op, _ in _entry_results(text)
                if op in ("gather", "scatter")]


def test_delta_rule_decode_step_writes_arena_and_state_in_place_on_tpu(
        v5e_chip, monkeypatch):
    """The Olmo Hybrid cell's decode round (BENCHMARK.json: published
    widths, 40 slots, 288 pages of 256 tokens), one delta-rule and one full
    layer, with its two Pallas kernels: the arena's two leaves (30 KV heads
    of 128, ONE query row a KV head) and the state's two are donated and
    handed back through writes in place — the delta leaf (88 MB: two heads'
    [96, 192] matrices to a row of 384 lanes, stored as large as it is) by
    the update kernel's own alias — so the temporaries stay far under one
    delta leaf."""
    import functools

    from easydist_tpu import config as edconfig
    from easydist_tpu.models import olmo_hybrid as oh
    from easydist_tpu.models.decoder import Paged, State, decode
    from easydist_tpu.ops import delta_rule

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    # the backend here is the CPU: steer the step onto its TPU path
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    monkeypatch.setattr(delta_rule, "delta_decode_update", functools.partial(
        delta_rule.delta_decode_update, backend="pallas", interpret=False))
    cfg = oh.OlmoHybridConfig(vocab=50176, layer_types=(
        "linear_attention", "full_attention"))
    dec = oh.decoder(cfg)
    slots, n_pages, pt, max_pages = 40, 288, 256, 16

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: oh.olmo_hybrid_init(cfg, key), jax.random.PRNGKey(0)))
    cache = _described(v5e_chip, jax.eval_shape(
        lambda: {**Paged.init(dec, n_pages, pt), **State.init(dec, slots)}))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_chip)
    table = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32,
                                 sharding=v5e_chip)

    def step(cache, params, table, live, token, pos):
        pages, leaves = State.split(dec, cache)
        cache, logits = decode(dec, Paged(pages, table), params, token, pos,
                               state=State(leaves, live))
        return cache, jnp.argmax(logits, -1)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        cache, params, table, live, rows, rows).compile()
    page_leaf = n_pages * 30 * pt * 128 * 2
    delta_leaf = slots * 15 * 96 * 384 * 4
    conv_leaf = slots * 3 * 11520 * 4
    assert delta_leaf == slots * 30 * 96 * 192 * 4       # nothing padded
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * page_leaf + delta_leaf + conv_leaf
    assert mem.temp_size_in_bytes < delta_leaf // 2, \
        "a state or arena leaf is copied round its write"
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2, \
        "the state update and the paged decode attention"


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_selective_step_writes_arena_and_state_in_place_on_tpu(
        v5e_chip, monkeypatch, program):
    """The Jamba2 cell's two programs (BENCHMARK.json: published widths,
    128 slots, 2,048 pages of 256 tokens, two prefill rows), one selective
    and one attention layer, with their Pallas kernels compiled by Mosaic
    for a v5e: the arena's two leaves (ONE KV head of 128 under 20 query
    heads — the chunk call in blocks of 5 heads' 256 rows, which whole are
    over what a kernel may scope) and the state's two are donated and
    handed back through writes in place — the selective leaf (42 MB: [16,
    5120] float32 a slot, stored as large as it is) by the kernels' own
    alias — so the temporaries stay under one selective leaf."""
    import functools

    from easydist_tpu import config as edconfig
    from easydist_tpu.models import jamba
    from easydist_tpu.models.decoder import Paged, State, chunk, decode
    from easydist_tpu.ops import ssm

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    # the backend here is the CPU: steer the step onto its TPU path
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(edconfig, "prefill_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    for name in ("selective_chunk_scan", "selective_decode_update"):
        monkeypatch.setattr(ssm, name, functools.partial(
            getattr(ssm, name), backend="pallas", interpret=False))
    cfg = jamba.JambaConfig(vocab=8192, layers=2, attn_period=2,
                            attn_offset=1)
    dec = jamba.decoder(cfg)
    assert dec.kinds == ("state", "attention")
    slots, n_pages, pt, max_pages, c_rows = 128, 2048, 256, 16, 2

    def aval(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: jamba.jamba_init(cfg, key), jax.random.PRNGKey(0)))
    cache = _described(v5e_chip, jax.eval_shape(
        lambda: {**Paged.init(dec, n_pages, pt), **State.init(dec, slots)}))

    def decode_step(cache, params, table, live, token, pos):
        pages, leaves = State.split(dec, cache)
        cache, logits = decode(dec, Paged(pages, table), params, token, pos,
                               state=State(leaves, live))
        return cache, jnp.argmax(logits, -1)

    def chunk_step(cache, params, table, at, tokens, start, lengths):
        pages, leaves = State.split(dec, cache)
        st = State(leaves, at < slots, at, fresh=start == 0)
        cache, logits = chunk(dec, Paged(pages, table), params, tokens,
                              start, lengths, state=st)
        return cache, jnp.argmax(logits, -1)

    if program == "decode":
        compiled = jax.jit(decode_step, donate_argnums=(0,)).lower(
            cache, params, aval((slots, max_pages)),
            aval((slots,), jnp.bool_), aval((slots,)),
            aval((slots,))).compile()
    else:
        compiled = jax.jit(chunk_step, donate_argnums=(0,)).lower(
            cache, params, aval((c_rows, max_pages)), aval((c_rows,)),
            aval((c_rows, pt)), aval((c_rows,)), aval((c_rows,))).compile()
    page_leaf = n_pages * 1 * pt * 128 * 2
    selective_leaf = slots * 16 * 5120 * 4
    conv_leaf = slots * 3 * 5120 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes \
        == 2 * page_leaf + selective_leaf + conv_leaf
    assert mem.temp_size_in_bytes < selective_leaf, \
        "a state or arena leaf is copied round its write"
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2, \
        "the selective kernel and the paged attention kernel"


@pytest.mark.parametrize("leaf", ["lane_dense", "as_is"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_lfm2_steps_move_no_whole_leaf_of_narrow_heads_on_tpu(
        v5e_chip, monkeypatch, program, leaf):
    """The LFM2-8B-A1B cell's two programs (BENCHMARK.json: published
    widths, 256 slots, 1,536 pages of 256 tokens, four prefill rows, 16
    held experts), two conv and two attention layers with a dense and three
    expert FFNs, their Pallas kernels compiled by Mosaic for a v5e.  With
    the arena's leaves LANE-DENSE ([pages, 8, 128, 128]: 8 KV heads of 64,
    two positions to a row) no op of either program gives a whole K or V
    leaf — no copy, no transpose, no pad, no `while` that carries one — and
    the temporaries stay far under a leaf.  With the leaves as they were
    ([pages, 8, 256, 64], which a v5e keeps pages-minor) the decode round
    copies each leaf FOUR times and the chunk call twice: what heads of 64
    cost before (PERF.md section 6, PR 48).  (Held here and not in
    tests/test_ops/test_tpu_lowering.py, which cross-lowers and cannot
    compile: one file alone may load libtpu.)"""
    from easydist_tpu import config as edconfig
    from easydist_tpu.kv import arena as arena_mod
    from easydist_tpu.models import lfm2_moe
    from easydist_tpu.models.decoder import Paged, State, chunk, decode

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    monkeypatch.setattr(edconfig, "decode_attention_backend", "paged")
    monkeypatch.setattr(edconfig, "prefill_attention_backend", "paged")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    _compile_the_grouped_products(monkeypatch)
    if leaf == "as_is":
        monkeypatch.setattr(arena_mod, "lane_parts", lambda hd, pt: 1)
    cfg = lfm2_moe.Lfm2MoeConfig(
        vocab=8192, dense_layers=1, experts_held=(0, 16),
        layer_types=("conv", "full_attention", "conv", "full_attention"))
    dec = lfm2_moe.decoder(cfg)
    slots, n_pages, pt, max_pages, c_rows = 256, 1536, 256, 16, 4

    def aval(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = _described(v5e_chip, jax.eval_shape(
        lambda key: lfm2_moe.lfm2_init(cfg, key), jax.random.PRNGKey(0)))
    cache = _described(v5e_chip, jax.eval_shape(
        lambda: {**Paged.init(dec, n_pages, pt), **State.init(dec, slots)}))
    assert cache["k"][0].shape == ((n_pages, 8, 128, 128)
                                   if leaf == "lane_dense"
                                   else (n_pages, 8, 256, 64))

    def decode_step(cache, params, table, live, token, pos):
        pages, leaves = State.split(dec, cache)
        kv = Paged(pages, table)
        cache, logits = decode(dec, kv, params, token, pos,
                               state=State(leaves, live))
        return cache, jnp.argmax(logits, -1), kv.counters

    def chunk_step(cache, params, table, at, tokens, start, lengths):
        pages, leaves = State.split(dec, cache)
        st = State(leaves, at < slots, at, fresh=start == 0)
        kv = Paged(pages, table)
        cache, logits = chunk(dec, kv, params, tokens, start, lengths,
                              state=st)
        return cache, jnp.argmax(logits, -1), kv.counters

    if program == "decode":
        compiled = jax.jit(decode_step, donate_argnums=(0,)).lower(
            cache, params, aval((slots, max_pages)),
            aval((slots,), jnp.bool_), aval((slots,)),
            aval((slots,))).compile()
    else:
        compiled = jax.jit(chunk_step, donate_argnums=(0,)).lower(
            cache, params, aval((c_rows, max_pages)), aval((c_rows,)),
            aval((c_rows, pt)), aval((c_rows,)), aval((c_rows,))).compile()
    page_leaf = n_pages * 8 * pt * 64 * 2
    tail_leaf = slots * 2 * 2048 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * page_leaf + 2 * tail_leaf
    text = compiled.as_text()
    # the paged kernel on two layers, two grouped products on three
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 6
    moves = _leaf_sized_moves(text, n_pages * 8 * pt * 64)
    carried = len(re.findall(
        r"= \([^)]*bf16\[1536,8,\d+,\d+\][^)]*\) while\(", text))
    if leaf == "lane_dense":
        assert moves == [] and carried == 0, moves
        assert mem.temp_size_in_bytes < page_leaf // 8
    else:
        per_leaf = 4 if program == "decode" else 2
        assert len(moves) == 4 * per_leaf, moves
        assert mem.temp_size_in_bytes > 2 * page_leaf


# the three cells whose state layers carry a conv tail: (state slots, the
# conv's channels); four taps in all three
CONV_TAIL_CELLS = {"jamba2": (128, 5120), "olmo": (40, 11520),
                   "granite": (64, 8448)}
_MOVES = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = \(?([a-z]+[0-9]*)\[([0-9,]*)\](\{[^ ]*\})?"
    r".*? (copy|copy-start|transpose)\(", re.M)


def _leaf_sized_moves(hlo_text, elements):
    """[(opcode, layout)] of every copy or transpose, anywhere in the
    module, whose (first) result holds `elements` values; the layout
    without its memory space (`S(1)` is the chip's fast memory)."""
    return [(m.group(4), re.sub(r"S\(\d+\)", "", m.group(3) or ""))
            for m in _MOVES.finditer(hlo_text)
            if np.prod([int(d) for d in m.group(2).split(",") if d])
            == elements]


def _compile_conv_tail(chip, form, conv, leaf_shape, c):
    """A state layer's conv through `State`, alone, compiled for `chip`
    with the leaf donated: `round` on the whole leaf (the rows ARE the
    slots), `chunk` as `State.read` -> conv -> `State.write` for two rows
    of 256.  fn(leaf, x, w, bias, *ints) -> (leaf, conv out)."""
    from easydist_tpu.models.decoder import State

    def round_(leaf, x, w, bias, live):
        st = State({"conv": (leaf,)}, live != 0)
        out, new = conv(st.read()["conv"], x, w, bias, st.live[:, None])
        st.write({"conv": new})
        return st.cache()["conv"][0], out

    def chunk_(leaf, x, w, bias, slots, start, lengths):
        st = State({"conv": (leaf,)}, slots < leaf.shape[0], slots,
                   fresh=start == 0)
        valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
        out, new = conv(st.read()["conv"], x, w, bias, valid)
        st.write({"conv": new})
        return st.cache()["conv"][0], out

    def aval(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn, rows, s, n_ints = (round_, leaf_shape[0], 1, 1) if form == "round" \
        else (chunk_, 2, 256, 3)
    return jax.jit(fn, donate_argnums=(0,)).lower(
        aval(leaf_shape, jnp.float32), aval((rows, s, c), jnp.float32),
        aval((4, c), jnp.float32), aval((c,), jnp.float32),
        *(aval((rows,)),) * n_ints).compile()


@pytest.mark.parametrize("form", ["round", "chunk"])
@pytest.mark.parametrize("cell", list(CONV_TAIL_CELLS))
def test_conv_tail_is_shifted_where_it_lies_on_tpu(v5e_chip, cell, form):
    """A state layer's conv tail at the three cells' sizes, compiled for
    the v5e: the leaf `[slots, 3 x channels]` float32 — the slots on the
    sublanes, each of the three carried inputs a run of whole lane tiles —
    is donated, handed back aliased, and NOTHING lays it out again: no
    transpose, and no copy of the leaf's size in another layout than the
    one it came in (`{1,0:T(8,128)}`).  As `[slots, 3, channels]` the three
    rows tiled `T(4,128)` and every shift or row write along them cost a
    copy of the whole leaf to `{2,0,1:T(8,128)}` and one back, twice a
    layer in both serving programs (the test below holds that reading).

    What MAY stay is a copy in the leaf's own layout to or from the fast
    memory (`S(1)`), in the round alone: a shift along the lanes cannot be
    written into the buffer it is read from, so XLA reads the leaf from a
    copy — in a program this small one it keeps in fast memory (no
    temporary in HBM: `temp_size_in_bytes` stays under a leaf), in the
    whole decode program one same-layout copy a layer in HBM (PERF.md
    section 7)."""
    from easydist_tpu.ops.ssm import causal_conv_tail

    slots, c = CONV_TAIL_CELLS[cell]
    compiled = _compile_conv_tail(v5e_chip, form, causal_conv_tail,
                                  (slots, 3 * c), c)
    text = compiled.as_text()
    header = text.split("\n", 1)[0]
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}, "
                     r"(?:may|must)-alias\)", header), header
    leaf_bytes = slots * 3 * c * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == leaf_bytes
    assert mem.temp_size_in_bytes < leaf_bytes, \
        "the conv tail's leaf is copied round its write"
    moves = _leaf_sized_moves(text, slots * 3 * c)
    assert not [m for m in moves if m[0] == "transpose"], moves
    assert {layout for _, layout in moves} <= {"{1,0:T(8,128)}"}, \
        f"the leaf is laid out again: {moves}"
    if form == "chunk":      # two rows gathered, two rows scattered
        assert not moves, moves
    else:                    # at most the one read-from copy
        assert len(moves) <= 1, moves


def test_conv_tail_in_rows_was_laid_out_again_on_tpu(v5e_chip):
    """The reading the test above guards against, kept so that it is known
    to SEE it: the body `causal_conv_tail` had with the tail as `[slots, 3,
    channels]` (frozen in `tests/test_ops/test_conv_tail.py`), the Jamba2
    cell's round: the v5e's compiler copies the whole leaf into another
    layout."""
    from tests.test_ops.test_conv_tail import _frozen

    slots, c = CONV_TAIL_CELLS["jamba2"]
    text = _compile_conv_tail(v5e_chip, "round", _frozen, (slots, 3, c),
                              c).as_text()
    moves = _leaf_sized_moves(text, slots * 3 * c)
    assert ("copy", "{2,0,1:T(8,128)}") in moves, moves
