"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments.  Drives the two main paths once through the entry
points a user calls — `easydist_compile` over the GPT-2 small train step, and
`GenerationSession.for_gpt` over an exact and an int8 page arena — at the
model's published width and depth, with random weights made from a seed, and
checks every result against a reference computed beside it.  Phases, in order:

  clock    a chained bf16 matmul timed with plain `jax.block_until_ready` must
           land under the chip's datasheet peak (so the wait is real)
  kernels  the Pallas kernels of ops/flash_attention.py, compiled
           (`interpret=False`), against the float32 XLA paths in that file;
           the paged ones at GPT-2 small's heads and at a GQA shape
  probe    the five paged kernels timed ALONE, each at its benchmark cell's
           shapes and live share (microseconds a call: notes, not claims);
           the expert FFN's fused second product and sum beside the three
           ops it replaced, at the three expert cells' shapes; the three
           flash training kernels at the train cell's call on one chip
  trainer  `make_gpt_train_step` + `easydist_compile` over all local chips,
           state threaded and donated; loss trajectory against a plain
           `jax.jit` of the einsum-attention step
  server   `submit` / `step` / `run_until_drained`: paged, paged int8;
           every token teacher-forced against one full `gpt_apply`
  arena    the paged server again through `for_llama` at the chat cell's
           widths, slots and 576-page arena but two layers: same checks, and
           the compiled decode program holds no copy of an arena leaf

It needs a TPU: without one it exits 2 before compiling anything.  No phase's
exception is caught — a failure anywhere is a traceback and a nonzero exit.
The last line of stdout is the one JSON object the driver reads,
`{"ok": true, "device": {"platform", "kind", "count"}}`; the line before it
and `chiprun_out/chip_smoke/summary.json` carry the run's set-up notes (no
number in them is a performance claim).  It writes the XLA compile cache
(`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`), the discovery
rule store under `<checkout>/.easydist_cache`, and `chiprun_out/chip_smoke/`.
"""

import collections
import functools
import importlib
import json
import os
import re
import shutil
import sys
import time

_T0 = time.perf_counter()
_HERE = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_HERE, "chiprun_out", "chip_smoke")

# GPT-2 small as published (vocab padded to a multiple of 128): the shape
# bench.py measures
GPT2_SMALL = dict(vocab=50304, seq=1024, dim=768, heads=12, layers=12,
                  dtype="bfloat16")
# the chat cell of BENCHMARK.json (chipbench/configs/mistral-7b-v0.3.json and
# its serve_config), cut to two layers
CELL_2_LAYERS = dict(vocab=32768, seq=2048, dim=4096, heads=32, kv_heads=8,
                     layers=2, ffn_dim=14336, rope_theta=1e6,
                     dtype="bfloat16")
CELL_SERVE = dict(max_decode_slots=32, kv_arena_pages=576)
TRAIN_BATCH = 8
TRAIN_STEPS = 6
# flash-attention trajectory vs the einsum-attention jit: both compute in
# bf16 and differ in summation order only
LOSS_RTOL = 2e-2
# kernel vs float32 reference, relative to the reference's largest element:
# the kernels read bf16 inputs, accumulate in f32 and round the result to
# bf16 (2^-8 relative) — 2e-2 leaves room for the backward's three matmuls
KERNEL_RTOL = 2e-2
# how far below the float32 reference's best logit a served token's reference
# logit may sit.  Random weights give flat logits (std ~0.55 over 50k tokens),
# so an exact argmax match is not the contract: a tie inside the margin is
# bf16 noise, a token read from a corrupted cache lands ~2.5 below the top.
# First run on a v5e: deficit 0.000 with 175/175 exact on the bf16 caches,
# 0.008 with 174/175 on int8.
LOGIT_MARGIN = {"paged": 0.05, "paged_int8": 0.1}
MIN_EXACT_MATCH = 0.9


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:6.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------- clock


def phase_clock(peak_flops: float, n: int = 4096, chain: int = 64):
    """`chain` dependent n x n bf16 matmuls in one program; the achieved
    rate must sit under the datasheet peak, or `block_until_ready` returned
    before the device finished."""
    import jax
    import jax.numpy as jnp

    from easydist_tpu.utils.timer import time_per_call

    @jax.jit
    def chained(a, b):
        def body(_, x):
            return jnp.dot(x, b, preferred_element_type=jnp.bfloat16)
        return jax.lax.fori_loop(0, chain, body, a)

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    b = (jax.random.normal(jax.random.fold_in(key, 1), (n, n), jnp.float32)
         / jnp.sqrt(n)).astype(jnp.bfloat16)
    secs = time_per_call(chained, (a, b), iters=5, warmup=2)
    rate = 2.0 * n ** 3 * chain / secs
    out = chained(a, b)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all()), \
        "clock: chained matmul produced non-finite values"
    assert rate < peak_flops, (
        f"clock: {rate / 1e12:.1f} TFLOP/s is over the {peak_flops / 1e12:.0f}"
        f" TFLOP/s peak — block_until_ready did not wait for the device")
    assert rate > 0.02 * peak_flops, (
        f"clock: {rate / 1e12:.2f} TFLOP/s is under 2% of peak — the matmul "
        f"did not run on the MXU")
    log(f"PASS clock: {rate / 1e12:.1f} TFLOP/s bf16 on a {chain}-deep "
        f"{n}^3 chain, under the {peak_flops / 1e12:.0f} TFLOP/s peak")
    return {"tflops": round(rate / 1e12, 1)}


# -------------------------------------------------------------- kernels


def _close(name, got, want, rtol=KERNEL_RTOL):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite kernel output"
    err = float(np.abs(got - want).max())
    ref = float(np.abs(want).max())
    assert err <= rtol * ref, \
        f"{name}: max |kernel - reference| {err:.3e} > {rtol} * {ref:.3e}"
    return err / ref


def phase_kernels(batch=TRAIN_BATCH, heads=12, d=64, seq=1024, slots=8,
                  page_tokens=64, gqa_kv_heads=8, gqa_head_dim=128,
                  interpret=False):
    """Each Pallas entry point at the shapes the trainer and the server use,
    on ragged lengths, against the float32 XLA path beside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    scale = 1.0 / float(np.sqrt(d))
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(11), 8)
    errs = {}

    def reference(f):
        # a float32 reference: on a TPU the default matmul precision is
        # one bf16 pass, which is no reference at all
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return jax.jit(f)(*a)
        return run

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    # ---- training forward + fused backward (causal)
    q, k, v = (jax.random.normal(keys[i], (batch, heads, seq, d), bf)
               for i in range(3))
    g = jax.random.normal(keys[3], (batch, heads, seq, d), bf)

    def flash_loss(q, k, v):
        out = fa.flash_attention(q, k, v, True, interpret=interpret)
        return (out.astype(jnp.float32) * g.astype(jnp.float32)).sum(), out

    def ref_loss(q, k, v):
        out = fa._reference_attention(q, k, v, True, scale)
        return (out * g.astype(jnp.float32)).sum(), out

    (_, out_k), grads_k = jax.jit(
        jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True))(
        q, k, v)
    (_, out_r), grads_r = reference(
        jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True))(
        *f32(q, k, v))
    errs["forward"] = _close("flash forward", out_k, out_r)
    errs["backward"] = max(
        _close(f"flash backward d{n}", a, b)
        for n, a, b in zip("qkv", grads_k, grads_r))
    del q, k, v, g, out_k, out_r, grads_k, grads_r

    # ---- bucketed decode: one query row per slot, ragged cache lengths
    # (1 = a fresh slot, seq = a full bucket, the rest mid-block)
    lengths = jnp.asarray(
        ([1, seq, seq // 13, seq // 4, seq // 4 + 44, 5, seq - 1,
          seq // 2 + 1] * slots)[:slots], jnp.int32)
    qd = jax.random.normal(keys[4], (slots, heads, d), bf)
    kc = jax.random.normal(keys[5], (slots, heads, seq, d), bf)
    vc = jax.random.normal(keys[6], (slots, heads, seq, d), bf)
    got = jax.jit(lambda *a: fa.flash_decode_attention(
        *a, interpret=interpret))(qd, kc, vc, lengths)
    want = reference(lambda q, k, v, l: fa._decode_attention_xla(
        q, k, v, l, scale))(*f32(qd, kc, vc), lengths)
    errs["decode"] = _close("bucketed decode", got, want)

    # ---- paged decode: the same rows scattered over an arena through a
    # shuffled page table, dead windows on the sentinel; then int8 pages
    # with one and two scale blocks per row
    def paged(tag, qd, kc, vc, lengths):
        slots, kv_heads, _, hd = kc.shape
        max_pages = seq // page_tokens
        n_pages = slots * max_pages + 3
        perm = np.random.RandomState(0).permutation(n_pages)
        table = np.full((slots, max_pages), n_pages, np.int32)
        kp = np.zeros((n_pages, kv_heads, page_tokens, hd), np.float32)
        vp = np.zeros_like(kp)
        kc_h, vc_h = np.asarray(kc, np.float32), np.asarray(vc, np.float32)
        for row in range(slots):
            for j in range(-(-int(lengths[row]) // page_tokens)):
                pid = int(perm[row * max_pages + j])
                table[row, j] = pid
                win = slice(j * page_tokens, (j + 1) * page_tokens)
                kp[pid] = kc_h[row, :, win]
                vp[pid] = vc_h[row, :, win]
        table = jnp.asarray(table)
        kp, vp = jnp.asarray(kp, bf), jnp.asarray(vp, bf)
        scale = 1.0 / float(np.sqrt(hd))
        got = jax.jit(lambda *a: fa.flash_paged_decode_attention(
            *a, interpret=interpret))(qd, kp, vp, table, lengths)
        want_p = reference(
            lambda q, k, v, t, l: fa._paged_decode_attention_xla(
                q, k, v, t, l, scale))(*f32(qd, kp, vp), table, lengths)
        errs[f"paged_decode{tag}"] = _close(f"paged decode{tag}", got, want_p)
        # a chunk of queries at each row's last positions reads the same
        # pages through the table (the chunk-prefill and verify programs)
        start = jnp.maximum(lengths - page_tokens, 0)
        pos = start[:, None] + jnp.arange(page_tokens, dtype=jnp.int32)
        qc = jax.random.normal(jax.random.fold_in(keys[4], hd),
                               (slots, qd.shape[1], page_tokens, hd), bf)
        got = jax.jit(lambda *a: fa.flash_paged_chunk_attention(
            *a, interpret=interpret))(qc, kp, vp, table, start + page_tokens)
        want_c = reference(lambda *a: fa.paged_chunk_attention(
            *a, backend="xla"))(*f32(qc, kp, vp), table, pos)
        errs[f"paged_chunk{tag}"] = _close(f"paged chunk{tag}", got, want_c)
        for nb in (1, 2):
            kq, ks = fa.kv_quantize(kp.astype(jnp.float32), nb)
            vq, vs = fa.kv_quantize(vp.astype(jnp.float32), nb)
            got = jax.jit(lambda *a: fa.flash_paged_decode_quant_attention(
                *a, interpret=interpret))(qd, kq, vq, ks, vs, table, lengths)
            want_q = reference(
                lambda q, k, v, a, b, t, l:
                fa._paged_decode_attention_quant_xla(
                    q, k, v, a, b, t, l, scale))(
                qd.astype(jnp.float32), kq, vq, ks, vs, table, lengths)
            errs[f"paged_decode_int8_nb{nb}{tag}"] = _close(
                f"int8 paged decode nb={nb}{tag}", got, want_q)
        return want_p

    want_p = paged("", qd, kc, vc, lengths)
    # the page table is an indirection, not arithmetic: the paged reference
    # equals the contiguous one
    _close("paged reference vs contiguous", want_p, want, rtol=1e-5)
    del kc, vc

    # ---- the same at a GQA shape (four query heads a KV head, as the chat
    # cell's Mistral widths), lengths on both sides of the boundaries of a
    # grid step's block of pages
    kv_heads, gd = gqa_kv_heads, gqa_head_dim
    block = fa._paged_step_shape(
        seq // page_tokens,
        (jax.ShapeDtypeStruct((1, kv_heads, page_tokens, gd), bf),) * 2
    )[1] * page_tokens
    lengths = jnp.asarray(np.clip(
        ([1, block - 1, block, block + 1, 2 * block - 1, 2 * block + 1,
          seq - block + 7, seq] * slots)[:slots], 1, seq), jnp.int32)
    qg = jax.random.normal(keys[7], (slots, 4 * kv_heads, gd), bf)
    kg, vg = (jax.random.normal(jax.random.fold_in(keys[7], i),
                                (slots, kv_heads, seq, gd), bf)
              for i in (1, 2))
    paged("_gqa", qg, kg, vg, lengths)

    log("PASS kernels (interpret=%s): max error / max |reference| — %s"
        % (interpret, ", ".join(f"{k} {v:.1e}" for k, v in errs.items())))
    return {k: float(f"{v:.2e}") for k, v in errs.items()}


# ---------------------------------------------------------------- probe

# The five paged kernels at their cells' shapes (BENCHMARK.json's serving
# cells) and live shares (PERF.md section 5): slots or prefill rows, query
# heads, KV heads (0: latent pages, which have none), the pages' minor dim,
# page_tokens, pages a bucket, arena pages, chunk (0: a decode round), and
# the rows' lengths — the rest of the rows hold no sequence (an all-sentinel
# table row; length 1 in a decode round, as `_decode_operand` gives it).
PROBE_CASES = {
    "mistral.paged_decode": (32, 32, 8, 128, 64, 32, 576, 0, [380] * 5),
    "mistral.paged_decode.all_dead": (32, 32, 8, 128, 64, 32, 576, 0, []),
    "mistral.paged_decode.one_dead_row": (1, 32, 8, 128, 64, 32, 576, 0, []),
    "mistral.paged_decode.all_live": (32, 32, 8, 128, 64, 32, 576, 0,
                                      [1100] * 32),
    "mistral.paged_chunk": (4, 32, 8, 128, 64, 32, 576, 64, [256, 448]),
    "olmo.paged_decode": (40, 30, 30, 128, 256, 16, 288, 0, [1300] * 25),
    "olmo.paged_chunk": (1, 30, 30, 128, 256, 16, 288, 256, [1280]),
    "kexaone.paged_decode": (64, 64, 8, 128, 256, 32, 2048, 0, [900] * 28),
    "kexaone.paged_chunk": (2, 64, 8, 128, 256, 32, 2048, 256, [1024, 3584]),
    "granite.paged_decode": (64, 32, 8, 128, 256, 16, 1024, 0, [700] * 29),
    "axk1.latent_decode": (32, 64, 0, 640, 256, 64, 2048, 0, [6000] * 6),
    "axk1.latent_chunk": (2, 64, 0, 640, 256, 64, 2048, 256, [3072, 6144]),
    # 8 KV heads of SIXTY-FOUR: the leaf lane-dense, [pages, 8, 128, 128], as
    # the arena stores it (`kv/arena.py`), and (`.as_is`) plain, [pages, 8,
    # 256, 64], which the call reshapes — on the chip a copy of both leaves
    "lfm2.paged_decode": (256, 32, 8, 64, 256, 16, 1536, 0, [900] * 160),
    "lfm2.paged_decode.as_is": (256, 32, 8, 64, 256, 16, 1536, 0,
                                [900] * 160),
    "lfm2.paged_chunk": (4, 32, 8, 64, 256, 16, 1536, 256,
                         [256, 512, 1024, 2048]),
    "lfm2.paged_chunk.as_is": (4, 32, 8, 64, 256, 16, 1536, 256,
                               [256, 512, 1024, 2048]),
}


def phase_paged_probe(cases=None, iters=200, interpret=False):
    """Microseconds a call of each paged kernel ALONE: `iters` calls in one
    `fori_loop` of one program (each call's lengths hang on the call before
    it, so none is hoisted or merged), timed once after a warm-up run.
    What the loop itself costs reads in the `all_dead` case, where the call
    walks no page.  Narrow heads (the `lfm2.*` cases) are probed on the leaf
    the arena stores, lane-dense, and on a plain one beside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from easydist_tpu.kv.arena import lane_parts

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")
    bf = jnp.bfloat16
    out = {}
    for name in cases or PROBE_CASES:
        rows, heads, kvh, width, pt, max_pages, n_pages, chunk, live = \
            PROBE_CASES[name]
        rs = np.random.RandomState(len(name))
        perm = iter(rs.permutation(n_pages))
        table = np.full((rows, max_pages), n_pages, np.int32)
        lengths = np.full((rows,), 0 if chunk else 1, np.int32)
        for row, n in zip(rs.permutation(rows)[:len(live)], live):
            lengths[row] = n
            table[row, :-(-n // pt)] = [next(perm) for _ in range(-(-n // pt))]
        key = jax.random.PRNGKey(len(name))
        q = jax.random.normal(
            key, (rows, heads) + ((chunk,) if chunk else ()) + (width,), bf)
        shape = (n_pages, kvh, pt, width) if kvh else (n_pages, pt, width)
        if kvh and not name.endswith(".as_is"):
            parts = lane_parts(width, pt)      # 1 at heads of 128 and wider
            shape = (n_pages, kvh, pt // parts, parts * width)
        pages = [jax.jit(lambda k: jax.random.normal(k, shape, bf))(
            jax.random.fold_in(key, i)) for i in range(2 if kvh else 1)]
        if kvh:
            call = fa.flash_paged_chunk_attention if chunk \
                else fa.flash_paged_decode_attention
        else:
            call = functools.partial(
                fa.flash_latent_chunk_attention if chunk
                else fa.flash_latent_decode_attention, values=512)

        @jax.jit
        def many(q, pages, table, lengths):
            def one(_, carry):
                n, acc = carry
                o = call(q, *pages, table, n, interpret=interpret)
                first = o.reshape(-1)[0].astype(jnp.float32)
                return n + jnp.isnan(first).astype(jnp.int32), acc + first
            return jax.lax.fori_loop(0, iters, one,
                                     (lengths, jnp.float32(0)))

        args = (q, pages, jnp.asarray(table), jnp.asarray(lengths))
        jax.block_until_ready(many(*args))
        t0 = time.perf_counter()
        jax.block_until_ready(many(*args))
        out[name] = round((time.perf_counter() - t0) / iters * 1e6, 2)
        del pages, args
    log("paged kernels alone, us a call: "
        + ", ".join(f"{k} {v}" for k, v in out.items()))
    return out


# The three flash training kernels at the train cell's own call on one chip
# (BENCHMARK.json's train-gpt2xl-4chip: 100 rows of batch x heads over four
# chips), at the whole call every chip ran before PR 30 sharded the rows, and
# at two shapes beside them: rows, positions, head_dim, operands' dtype,
# causal.
FLASH_TRAIN_CASES = {
    "gpt2xl.cell": (25, 1024, 64, "bfloat16", True),
    "gpt2xl.cell.f32": (25, 1024, 64, "float32", True),
    "gpt2xl.whole": (100, 1024, 64, "bfloat16", True),  # before PR 30
    "d128.t1024": (16, 1024, 128, "bfloat16", True),
    "d128.t8192.streamed": (4, 8192, 128, "bfloat16", True),
}


def phase_flash_train_probe(cases=None, iters=20, fa=None, blocks=(256, 256)):
    """Microseconds a call of each flash training kernel ALONE — forward,
    dq, dk/dv, each a program of its own (the backward's other kernel is
    dead code in it) — read off the device trace of `iters` calls: the
    mean duration of the program's Mosaic op.  `fa`: the module that holds
    the kernels (a builder's way to probe another commit's beside this
    one's); `blocks`: the (block_q, block_k) asked for."""
    import jax
    import jax.numpy as jnp

    from chipbench import trace_reduce

    fa = fa or importlib.import_module("easydist_tpu.ops.flash_attention")
    trace_dir = os.path.join(_OUT, "flash_probe_trace")
    pallas = re.compile(trace_reduce.PALLAS_KERNEL)
    out = {}
    for name in cases or FLASH_TRAIN_CASES:
        rows, t, d, dtype, causal = FLASH_TRAIN_CASES[name]
        scale = 1.0 / float(d) ** 0.5
        q, k, v, g = (jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(len(name)), i),
            (1, rows, t, d), jnp.dtype(dtype)) for i in range(4))

        def fwd(q, k, v):
            return fa._flash_forward(q, k, v, causal, scale, *blocks, False)

        def bwd(q, k, v, o, lse, g):
            return fa._flash_backward(q, k, v, o, lse, g, causal, scale,
                                      *blocks, False)

        o, lse = jax.jit(fwd)(q, k, v)
        programs = {
            "fwd": (jax.jit(fwd), (q, k, v)),
            "dq": (jax.jit(lambda *a: bwd(*a)[0]), (q, k, v, o, lse, g)),
            "dkv": (jax.jit(lambda *a: bwd(*a)[1:]), (q, k, v, o, lse, g)),
        }
        for fn, args in programs.values():
            jax.block_until_ready(fn(*args))
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        for fn, args in programs.values():
            for _ in range(iters):
                jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        calls = sorted((s, dur) for plane in trace_reduce.device_planes(trace)
                       for n, s, dur in trace_reduce.op_events(plane)
                       if pallas.search(n))
        if len(calls) != len(programs) * iters:
            raise RuntimeError(
                f"{name}: {len(calls)} Mosaic ops in the trace of "
                f"{len(programs)} x {iters} calls")
        for i, which in enumerate(programs):
            durs = [dur for _, dur in calls[i * iters:(i + 1) * iters]]
            out[f"{name}.{which}"] = round(sum(durs) / iters / 1e3, 2)
        del programs, q, k, v, g, o, lse
    log("flash training kernels alone, us a call: "
        + ", ".join(f"{k} {v}" for k, v in out.items()))
    return out


EXPERT_CASES = {   # (dim, an expert's width, experts, held, top_k, rows)
    "granite.chunk": (4096, 768, 72, 36, 10, 1024),
    "granite.round": (4096, 768, 72, 36, 10, 64),
    "kexaone.chunk": (6144, 2048, 128, 16, 8, 512),
    "kexaone.round": (6144, 2048, 128, 16, 8, 64),
    "axk1.chunk": (7168, 2048, 192, 12, 8, 512),
    "axk1.round": (7168, 2048, 192, 12, 8, 32),
}


def phase_expert_probe(cases=None, iters=200, interpret=False):
    """Microseconds a call of the expert FFN's way back to tokens ALONE, at
    each expert cell's chunk and round shape with routing drawn at the
    cell's held share: `sum` is `grouped_matmul_sum` (the second grouped
    product with the gate-weighted sum inside it: what `expert_ffn` runs),
    `gather` the three ops it replaced — the second product written out,
    `out[dest]` over all k x rows pair slots, the k-slice combine — kept
    here as the reference the fused call is checked against.  `iters`
    calls in one `fori_loop` (each call's live blocks hang on the call
    before it), timed once after a warm-up run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    gm = importlib.import_module("easydist_tpu.ops.grouped_matmul")
    bf, f32 = jnp.bfloat16, jnp.float32
    pallas = dict(backend="pallas", interpret=interpret)

    def gather(act, w2, g, gate, mine):
        rows = gate.shape[1]
        out = gm.grouped_matmul(act, w2, g.block_expert, g.live_blocks,
                                g.block_rows, **pallas)
        pairs = jnp.take(out, g.dest, axis=0, mode="clip")
        return sum(jnp.where(mine[j, :, None],
                             pairs[j * rows:(j + 1) * rows].astype(f32)
                             * gate[j, :, None], 0.0)
                   for j in range(gate.shape[0])).astype(bf)

    def fused(act, w2, g, gate, mine):
        k, rows = gate.shape
        token_at = jnp.where(g.source < k * rows, g.source % rows, rows)
        gate_at = jnp.take(gate.reshape(-1), g.source, mode="fill",
                           fill_value=0.0)
        return gm.grouped_matmul_sum(act, w2, g, token_at, gate_at, rows,
                                     **pallas)

    out = {}
    for name in cases or EXPERT_CASES:
        dim, width, experts, held, k, rows = EXPERT_CASES[name]
        rs = np.random.RandomState(len(name))
        idx = np.argsort(rs.rand(rows, experts), axis=1)[:, :k].T
        mine = jnp.asarray(idx < held)
        tm = 128 if rows * k >= 64 * held else 32    # `expert_ffn`'s rule
        g = gm.group_rows(jnp.asarray(
            np.where(idx < held, idx, held).reshape(-1), jnp.int32), held, tm)
        key = jax.random.PRNGKey(len(name))
        act = jax.random.normal(key, (g.source.shape[0], width), bf)
        w2 = jax.jit(lambda k_: jax.random.normal(k_, (held, width, dim), bf)
                     / width ** 0.5)(jax.random.fold_in(key, 1))
        gate = jnp.asarray(rs.rand(k, rows), f32)
        results = {}
        for tag, fn in (("gather", gather), ("sum", fused)):
            @jax.jit
            def once(act, w2, g, gate, mine, live):
                # a jit's argument is traced: the block's rows are a shape
                return fn(act, w2, g._replace(live_blocks=live,
                                              block_rows=tm), gate, mine)

            @jax.jit
            def many(act, w2, g, gate, mine):
                def one(_, carry):
                    live, acc = carry
                    first = once(act, w2, g, gate, mine,
                                 live)[0, 0].astype(f32)
                    return (live + jnp.isnan(first).astype(jnp.int32),
                            acc + first)
                return jax.lax.fori_loop(0, iters, one,
                                         (g.live_blocks, f32(0)))

            args = (act, w2, g, gate, mine)
            jax.block_until_ready(many(*args))
            t0 = time.perf_counter()
            jax.block_until_ready(many(*args))
            out[f"{name}.{tag}"] = round(
                (time.perf_counter() - t0) / iters * 1e6, 1)
            results[tag] = once(*args, g.live_blocks)
        _close(f"expert probe {name}", results["sum"], results["gather"])
        out[f"{name}.live_pct"] = round(
            100.0 * int(jnp.sum(g.sizes)) / (rows * k), 1)
        del act, w2, args, results
    log("expert FFN's way back to tokens alone, us a call: "
        + ", ".join(f"{k} {v}" for k, v in out.items()))
    return out


# -------------------------------------------------------------- trainer


def _leaf_bytes_on(leaf, device) -> int:
    return sum(s.data.nbytes for s in leaf.addressable_shards
               if s.device == device)


def phase_trainer(cfg_kw=None, batch=TRAIN_BATCH, steps=TRAIN_STEPS,
                  devices=None, large_leaf=2 ** 20):
    """GPT-2 small through `easydist_compile` on all local chips, beside a
    plain `jax.jit` of the einsum-attention step."""
    import dataclasses

    import jax
    import numpy as np

    from easydist_tpu.jaxfront import easydist_compile, make_device_mesh
    from easydist_tpu.models import GPTConfig, make_gpt_train_step

    devices = list(devices or jax.devices())
    n = len(devices)
    cfg = GPTConfig(**(cfg_kw or GPT2_SMALL), attention="flash")
    if n >= 4:
        devices = devices[:4]
        mesh = make_device_mesh((2, 2), ("dp", "tp"), devices=devices)
    else:
        devices = devices[:1]
        mesh = make_device_mesh((1,), ("d",), devices=devices)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.seq), 0,
                                cfg.vocab)
    targets = jax.random.randint(jax.random.PRNGKey(2), (batch, cfg.seq), 0,
                                 cfg.vocab)

    # ---- the reference beside it: plain jit, einsum attention, one device
    ref_step, ref_init = make_gpt_train_step(
        dataclasses.replace(cfg, attention="einsum"))
    ref_jit = jax.jit(ref_step, donate_argnums=(0,))
    state = ref_init(jax.random.PRNGKey(0))
    ref_losses = []
    for _ in range(steps):
        state, loss = ref_jit(state, tokens, targets)
        ref_losses.append(float(loss))
    del state

    # ---- the system: trace -> discovery -> solve -> emission -> jit
    step, init_state = make_gpt_train_step(cfg)
    compiled = easydist_compile(step, mesh=mesh)
    state = init_state(jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    result = compiled.get_compiled(state, tokens, targets)
    plan_s = time.perf_counter() - t0
    losses, step_s, sharding_notes = [], [], None
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = compiled(state, tokens, targets)
        losses.append(float(loss))  # waits for the step
        step_s.append(time.perf_counter() - t0)
        if len(losses) == 1 and n >= 4:
            sharding_notes = _assert_plan_is_real(state, devices, large_leaf)
    log(f"trainer losses   {[round(x, 4) for x in losses]}")
    log(f"reference losses {[round(x, 4) for x in ref_losses]}")

    assert all(np.isfinite(losses)), f"trainer: non-finite loss {losses}"
    assert all(b < a for a, b in zip(losses, losses[1:])), \
        f"trainer: loss not decreasing {losses}"
    np.testing.assert_allclose(
        losses, ref_losses, rtol=LOSS_RTOL,
        err_msg="trainer: flash/easydist trajectory left the einsum jit's")

    max_dev = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    notes = {
        "mesh": dict(mesh.shape),
        "losses": [round(x, 4) for x in losses],
        "max_rel_dev_vs_einsum_jit": float(f"{max_dev:.2e}"),
        "compile_s": {k: round(v, 2)
                      for k, v in result.phase_seconds.items()},
        "plan_s": round(plan_s, 2),
        "first_step_s_incl_xla": round(step_s[0], 2),
        "later_step_s": round(min(step_s[1:]), 4),
        "replicated_flops_share": round(result.replicated_flops_fraction, 4),
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices],
    }
    if sharding_notes:
        notes["sharding"] = sharding_notes
    log(f"PASS trainer: {cfg.layers} layers x {cfg.dim} wide on mesh "
        f"{dict(mesh.shape)}, {steps} steps, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}, within {LOSS_RTOL} of the einsum jit; "
        f"notes {json.dumps(notes)}")
    return notes


def _assert_plan_is_real(state, devices, large_leaf):
    """After step 1 on the (2, 2) mesh: the large parameter and Adam leaves
    are not fully replicated, and no device holds the whole state."""
    import jax

    leaves = jax.tree_util.tree_leaves(state)
    large = [x for x in leaves if x.size >= large_leaf]
    replicated = [x.shape for x in large if x.sharding.is_fully_replicated]
    assert large and not replicated, (
        f"trainer: {len(replicated)} of {len(large)} large state leaves are "
        f"fully replicated on the mesh: {replicated[:6]}")
    total = sum(x.nbytes for x in leaves)
    held = [sum(_leaf_bytes_on(x, d) for x in leaves) for d in devices]
    assert max(held) < total, (
        f"trainer: a device holds the whole state ({max(held)} of {total} "
        f"bytes)")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    assert all(b < total for b in in_use if b is not None), (
        f"trainer: a device has {max(in_use)} bytes in use, the whole state "
        f"is {total}")
    return {"state_bytes": total, "state_bytes_per_device": held,
            "large_leaves": len(large), "bytes_in_use": in_use}


# --------------------------------------------------------------- server


def _requests(vocab, bucket, chunk):
    """(prompt, max_new) in two waves.  Mixed lengths: shorter than one
    prefill chunk, longer than one, longer than two; one that runs into the
    end of the bucket; and in the second wave a prompt sharing its first two
    chunks with one of the first wave, so the trie restores them."""
    import numpy as np

    rng = np.random.RandomState(7)

    def toks(n):
        return [int(t) for t in rng.randint(1, vocab, size=n)]

    shared = toks(2 * chunk)
    wave1 = [(toks(5), 24), (toks(chunk + 6), 32),
             (shared + toks(9), 28), (toks(2 * chunk + 22), 24),
             (toks(bucket - 10), 24)]
    wave2 = [(shared + toks(13), 24), (toks(chunk // 2), 32)]
    return wave1, wave2


def _decode_temporaries(sess):
    """(temporary bytes of the session's compiled paged decode program,
    bytes of one arena leaf).  The arena is donated leaf by leaf and each
    leaf written in place (kv/arena.py), so the program's temporaries must
    stay under ONE leaf: a layer sliced out of a stacked arena, a stack, or
    a write that changes a leaf's layout each cost at least that."""
    import numpy as np

    pool = next(iter(sess._pools.values()))
    # the program's one operand: a slot's table row, token and position
    rows = np.zeros((pool.n_slots, pool.max_pages + 2), np.int32)
    exe = sess._paged_c("decode").executable_for(
        pool.arena, sess.params, rows)
    leaf = min(int(x.nbytes) for x in pool.arena["k"])
    return int(exe.memory_analysis().temp_size_in_bytes), leaf


def phase_server(layout, params, cfg_kw=None, device=None, config_kw=None,
                 family="gpt", in_place=False):
    """`GenerationSession.for_gpt` (or `.for_llama`) on one chip over one
    kind of arena; every served token teacher-forced against one full float32
    forward of the model.  `in_place` also holds the compiled paged decode
    program to `_decode_temporaries`' bound."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.models import GPTConfig
    from easydist_tpu.models.gpt import gpt_apply
    from easydist_tpu.models.llama import LlamaConfig, llama_apply
    from easydist_tpu.serve import GenerationSession, ServeConfig

    Config, apply, for_model = {
        "gpt": (GPTConfig, gpt_apply, GenerationSession.for_gpt),
        "llama": (LlamaConfig, llama_apply, GenerationSession.for_llama),
    }[family]
    cfg_kw = cfg_kw or GPT2_SMALL
    cfg = Config(**cfg_kw)
    device = device or jax.devices()[0]
    mesh = make_device_mesh((1,), ("d",), devices=[device])
    layout_kw = {"paged": {},
                 "paged_int8": {"kv_quant_dtype": "int8"}}[layout]
    config = ServeConfig(decode_buckets=(cfg.seq,), **layout_kw,
                         **(config_kw or {}))
    bucket, chunk = cfg.seq, min(config.prefill_chunk, cfg.seq)
    sess = for_model(params, cfg, config=config, mesh=mesh)

    t0 = time.perf_counter()
    served = []
    for wave in _requests(cfg.vocab, bucket, chunk):
        futs = [sess.submit(p, max_new_tokens=m) for p, m in wave]
        sess.step()
        sess.run_until_drained(max_steps=4 * bucket)
        served += [(p, m, f.result(timeout=0)) for (p, m), f in
                   zip(wave, futs)]
    wall = time.perf_counter() - t0

    # ---- every future resolved with the right count and reason
    for prompt, max_new, res in served:
        room = bucket - len(prompt) + 1
        want_n, want_why = (max_new, "length") if max_new <= room \
            else (room, "bucket_full")
        assert (len(res["ids"]), res["finish_reason"]) == (want_n, want_why), (
            f"server[{layout}]: prompt of {len(prompt)} finished with "
            f"{len(res['ids'])} tokens / {res['finish_reason']!r}, expected "
            f"{want_n} / {want_why!r}")
    reused = sess.metrics.counter("prefix_tokens_reused")
    assert reused >= 2 * chunk, (
        f"server[{layout}]: the trie restored {reused} prompt tokens, "
        f"expected the {2 * chunk}-token shared prefix")

    # ---- teacher forcing: one full forward over prompt + served ids in
    # float32; token i must be (within the margin) the reference's pick at
    # position len(prompt) + i - 1
    ref_cfg = Config(**{**cfg_kw, "dtype": "float32"})

    # the weights are an argument, not a closure: closed over, they would be
    # baked into the executable as ~500 MB of constants (slow to compile, and
    # too large a file for the compile cache)
    @jax.jit
    def ref_logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return apply(params, ref_cfg, tokens)[0]

    worst, exact, total = 0.0, 0, 0
    for prompt, _, res in served:
        ids = res["ids"]
        seq = np.zeros((1, bucket), np.int32)
        full = (prompt + ids)[:bucket]
        seq[0, :len(full)] = full
        logits = np.asarray(ref_logits(params, jnp.asarray(seq)))
        for i, tok in enumerate(ids):
            row = logits[len(prompt) + i - 1]
            worst = max(worst, float(row.max() - row[tok]))
            exact += int(row.argmax() == tok)
            total += 1
    margin = LOGIT_MARGIN[layout]
    assert worst <= margin, (
        f"server[{layout}]: a served token sits {worst:.3f} below the "
        f"float32 reference's best logit (margin {margin}) — the cached "
        f"path and the full forward disagree")
    assert exact >= MIN_EXACT_MATCH * total, (
        f"server[{layout}]: only {exact}/{total} served tokens are the "
        f"reference's argmax")

    counters = sess.metrics.snapshot()["counters"]
    notes = {"requests": len(served), "tokens": total,
             "exact_argmax": f"{exact}/{total}",
             "worst_logit_deficit": round(worst, 4),
             "prefix_tokens_reused": reused,
             "prefill_chunks": counters.get("prefill_chunks"),
             "decode_steps": counters.get("decode_steps"),
             "wall_s_incl_compile": round(wall, 1)}
    if in_place:
        temp, leaf = _decode_temporaries(sess)
        assert temp < leaf, (
            f"server[{layout}]: the compiled decode program holds {temp} "
            f"bytes of temporaries, an arena leaf is {leaf}: some leaf is "
            f"copied instead of written in place")
        notes.update(decode_temp_bytes=temp, arena_leaf_bytes=leaf)
    sess.close()
    log(f"PASS server[{layout}]: {len(served)} requests, {total} tokens, "
        f"counts and finish reasons right, {exact}/{total} tokens are the "
        f"float32 argmax, worst deficit {worst:.3f} <= {margin}; notes "
        f"{json.dumps(notes)}")
    return notes


# a small Granite 4.0-H: one period [mamba, attention, mamba], 8 experts
# top-2 of which 4 are held; widths the TPU kernels tile (d_state 128, expert
# widths in 128s), far under the cell's
HYBRID_SMALL = dict(
    hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=1.0 / 64, mamba_n_heads=8, mamba_d_head=64,
    mamba_d_state=128, mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
    mamba_chunk_size=64, router_experts=8, experts_held=[0, 4],
    num_local_experts=4, num_experts_per_tok=2, intermediate_size=128,
    shared_intermediate_size=256,
    layer_types=["mamba", "attention", "mamba"], num_hidden_layers=3,
    vocab_size=512, embedding_multiplier=12, residual_multiplier=0.22,
    logits_scaling=16, rms_norm_eps=1e-5)


def phase_hybrid(sizes=None, device=None):
    """A model with state layers through `GenerationSession`: chunked
    prefill and decode through the page arena AND the state pool (the
    state-update and grouped-matmul kernels compile natively here), every
    served token teacher-forced against the benchmark's plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import weights_granite
    from chipbench.reference import granite_hybrid as reference
    from chipbench.runners.serve_hybrid import model_config
    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.models import granite_hybrid
    from easydist_tpu.serve import GenerationSession, ServeConfig

    sizes = sizes or HYBRID_SMALL
    device = device or jax.devices()[0]
    params = weights_granite.granite_params(sizes,
                                            weights_granite.seed_key(5))
    bucket, chunk = 256, 64
    sess = GenerationSession(
        params, model=granite_hybrid.decoder(model_config(sizes)),
        config=ServeConfig(decode_buckets=(bucket,),
                           max_decode_slots=8, prefill_chunk=chunk,
                           prefill_batch=2, enable_prefix_cache=False,
                           speculate_k=0),
        mesh=make_device_mesh((1,), ("d",), devices=[device]))
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, sizes["vocab_size"], size=n).tolist(), m)
            for n, m in ((5, 6), (chunk, 9), (chunk + 3, 12),
                         (2 * chunk + 17, 8), (33, 5))]
    t0 = time.perf_counter()
    futs = [sess.submit(p, max_new_tokens=m) for p, m in reqs]
    sess.run_until_drained(max_steps=4 * bucket)
    wall = time.perf_counter() - t0
    worst, spread, exact, total = 0.0, 0.0, 0, 0
    for (prompt, max_new), fut in zip(reqs, futs):
        res = fut.result(timeout=0)
        assert (len(res["ids"]), res["finish_reason"]) == (max_new, "length")
        seq = np.zeros((bucket,), np.int32)
        seq[:len(prompt) + max_new] = prompt + res["ids"]
        logits = np.asarray(reference.logits(params, sizes,
                                             jnp.asarray(seq)))
        for i, tok in enumerate(res["ids"]):
            row = logits[len(prompt) + i - 1]
            worst = max(worst, float(row.max() - row[tok]))
            spread = max(spread, float(row.std()))
            exact += int(row.argmax() == tok)
            total += 1
    pool = next(iter(sess._pools.values()))
    assert pool.state.in_use == 0 and pool.pool.in_use == 0
    counters = sess.metrics.snapshot()["counters"]
    assert counters["moe_pairs_routed"] > 0
    assert worst <= 0.5 * spread and exact >= MIN_EXACT_MATCH * total, (
        f"hybrid: a served token sits {worst:.5f} below the float32 "
        f"reference's best logit (a row's spread is {spread:.5f}); "
        f"{exact}/{total} are its argmax")
    sess.close()
    notes = {"requests": len(reqs), "tokens": total,
             "exact_argmax": f"{exact}/{total}",
             "worst_logit_deficit": round(worst, 6),
             "logit_spread": round(spread, 6),
             "moe_pairs_routed": counters["moe_pairs_routed"],
             "wall_s_incl_compile": round(wall, 1)}
    log(f"PASS hybrid: state pool beside the arena, {exact}/{total} tokens "
        f"are the float32 argmax; notes {json.dumps(notes)}")
    return notes


# ----------------------------------------------------------------- main


def _versions():
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main() -> int:
    import jax

    from easydist_tpu import config as edconfig
    from easydist_tpu.utils.jax_cache import configure_jax_cache

    cache_dir = configure_jax_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update([event.rsplit("/", 1)[-1]]))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX reports platform="
              f"{dev.platform!r} ({dev.device_kind!r} x{len(devices)})",
              file=sys.stderr)
        return 2

    # a PerfDB left by another program changes solver decisions from outside
    # the tree: this run reads none and keeps its own beside its outputs
    shutil.rmtree(_OUT, ignore_errors=True)
    os.makedirs(_OUT)
    edconfig.prof_db_path = os.path.join(_OUT, "perf.db")

    from easydist_tpu import native
    from easydist_tpu.models import GPTConfig, gpt_init
    from easydist_tpu.runtime.calibrate import detect_device_constants

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    versions = _versions()
    log(f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={len(devices)} " +
        " ".join(f"{k}={v}" for k, v in versions.items()) +
        f" native={'loaded' if native.available() else 'python fallback'} "
        f"jax_cache={cache_dir}")
    peak = detect_device_constants(dev.device_kind)["peak_flops"]

    notes = {"clock": phase_clock(peak)}
    notes["kernels"] = phase_kernels()
    notes["paged_probe_us"] = phase_paged_probe()
    notes["expert_probe_us"] = phase_expert_probe()
    notes["flash_train_probe_us"] = phase_flash_train_probe()
    notes["trainer"] = phase_trainer()
    params = gpt_init(GPTConfig(**GPT2_SMALL), jax.random.PRNGKey(0))
    for layout in ("paged", "paged_int8"):
        notes[f"server_{layout}"] = phase_server(layout, params)
    del params
    from easydist_tpu.models.llama import LlamaConfig, llama_init

    # bf16 weights as the cell holds them; the reference upcasts the same
    cell_params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype("bfloat16"),
        llama_init(LlamaConfig(**CELL_2_LAYERS), key)))(jax.random.PRNGKey(1))
    notes["arena_cell_widths"] = phase_server(
        "paged", cell_params, cfg_kw=CELL_2_LAYERS, config_kw=CELL_SERVE,
        family="llama", in_place=True)

    notes["hybrid"] = phase_hybrid()

    summary = {
        "ok": True, "device": device, "versions": versions,
        "native": native.available(),
        "jax_cache": {"dir": cache_dir,
                      "hits": cache_events["cache_hits"],
                      "misses": cache_events["cache_misses"]},
        "wall_s": round(time.perf_counter() - _T0, 1),
        "phases": notes, "claim": None}
    with open(os.path.join(_OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    log("summary " + json.dumps(summary))
    # the driver's contract: the last line is this object and nothing more
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
