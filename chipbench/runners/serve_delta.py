"""Serving cells of models with delta-rule layers (Olmo Hybrid,
`olmo_hybrid`): one `GenerationSession` on one chip under the open loop of
`runners/serve.py` (`_Loop`), with the window, ramp, tail, traced part and
ONE replayed order of arrivals of `serve_latent.py`.

The run itself is `serve_family`, which knows no model: a `Family` brings
the weights, the decoder, the reference and its controls, the gauges and
counters to read and the invariant of its pools.  This file's family is
`OLMO`; a later cell of another family writes its `Family` and calls
`serve_family` (the four older runners each carry a copy of the run and are
the benchmark's to merge: PERF.md section 7 (c))."""

import dataclasses
import gc
import importlib
import statistics
import sys
import time
from typing import Callable

import numpy as np

from chipbench import compare, kernel_costs_delta, weights_olmo
from chipbench.runners.serve import _Loop, _percentile
from chipbench.runners.serve_latent import (_numbers, arrival_trace,
                                            sample_requests)


@dataclasses.dataclass(frozen=True)
class Family:
    """What one model family brings to `serve_family`."""
    weights: Callable     # (sizes, key) -> the parameters, on the device
    decoder: Callable     # (sizes) -> (the `Decoder` served, its vocabulary)
    reference: str        # module of `chipbench.reference`: `logits(params,
    #                       sizes, tokens, rows=, quant=)`, plain float32
    controls: tuple       # ((label, `quant`), ...) that `--control` reads
    #                       too; the first is `check.control`, the others
    #                       lie in it under their `quant`
    gauges: tuple         # the session's gauges read after every step
    counters: tuple       # its counters, differenced over window and trace
    unlisted: tuple       # readers `run.py` does not call for the cell: a
    #                       traced run logs each as `not reported`
    pools: Callable       # (sizes, pool, gauge_steps, (w_from, w_to), log)
    #                       -> what the per-layer readers take of the
    #                       family's pools; RAISES where their invariant
    #                       does not hold


class _PoolLoop(_Loop):
    """`_Loop`, reading the family's gauges after every step too."""

    def __init__(self, gauges, *a):
        super().__init__(*a)
        self.gauges = gauges
        self.gauge_steps = []    # (t_end, {gauge: value})
        self.held = []           # (t_end, sequences decoding, not admitted)

    def turn(self) -> None:
        n = len(self.steps)
        super().turn()
        if len(self.steps) > n:
            gauges = self.sess.metrics.snapshot()["gauges"]
            self.gauge_steps.append((self.steps[-1][0],
                                     {k: gauges.get(k) for k in self.gauges}))
            self.held.append((self.steps[-1][0], sum(
                p.n_active for p in self.sess._pools.values()),
                sum(r["queued_until"] is None for r in self.live.values())))


def served_tokens(logits, params, sizes, sample, *, spec, pad_to, controls,
                  log) -> dict:
    """For each sampled request, one float32 forward of the reference
    (`logits`) over prompt + served tokens; at each served position, how far
    the served token's reference logit lies below the reference's best
    (`_numbers`).  Each of `controls` reads the same positions at its lower
    precision: the gap of the token IT puts first, held to the same limits
    (its `correct`, which has to come out false; the run's own `correct`
    stays the program's)."""
    import jax.numpy as jnp

    n_rows = int(spec["rows"])
    deficits, exact, spread = [], 0, []
    lowered = {quant: [] for _, quant in controls}
    for rec in sample:
        prompt, ids = rec["req"]["prompt"], rec["ids"]
        toks = np.zeros((pad_to,), np.int32)
        full = (prompt + ids)[:pad_to]
        toks[:len(full)] = full
        at = np.minimum(len(prompt) - 1 + np.arange(n_rows), pad_to - 1)
        rows = np.asarray(logits(params, sizes, jnp.asarray(toks),
                                 rows=at))[:len(ids)]
        served = np.asarray(ids[:len(rows)])
        best = rows.max(axis=-1)
        spread.append(float(rows.std(axis=-1).mean()))
        deficits += list(best - rows[np.arange(len(rows)), served])
        exact += int((rows.argmax(axis=-1) == served).sum())
        for quant, gaps in lowered.items():
            pick = np.asarray(logits(params, sizes, jnp.asarray(toks), rows=at,
                                     quant=quant))[:len(ids)].argmax(-1)
            gaps += list(best - rows[np.arange(len(rows)), pick])
    if not deficits:
        log("correct: no finished request to compare")
        return {"correct": False, "numbers": {}, "tokens": 0}
    numbers = _numbers(deficits)
    distinct = len({t for rec in sample for t in rec["ids"]})
    log(f"correct: {len(sample)} requests, {len(deficits)} served tokens "
        f"({distinct} distinct), {exact} of them the reference's first "
        f"choice; deficits' 99th percentile "
        f"{np.quantile(deficits, 0.99):.4g}; the reference's logits spread "
        f"(std over the vocabulary, mean over rows) "
        f"{[round(s, 3) for s in spread]}")
    out = {"correct": compare._verdict(numbers, spec["limits"], log),
           "numbers": numbers, "tokens": len(deficits), "exact": exact}
    for i, (label, quant) in enumerate(controls):
        # a control's numbers against the cell's own limits: one that
        # passes them all means the limits have no power over it
        low = _numbers(lowered[quant])
        low["correct"] = compare._verdict(
            low, spec["limits"],
            lambda line, label=label: log(f"control ({label}) " + line))
        if i == 0:
            out["control"] = low
        else:
            out["control"][quant] = low
    return out


def log_unlisted(ctx, raw: dict, names) -> None:
    """Each reader of `names` on what `run.py` would hand it."""
    from chipbench import trace_reduce

    trace = raw["trace"]["trace"]
    run = dict(raw, cell=ctx.cell, mix=ctx.mix, chips=1,
               rehearse=ctx.rehearse, device_kind=trace["device_kind"],
               busy=trace_reduce.busy(trace, 1))
    for name in names:
        reader = importlib.import_module("chipbench.metrics." + name)
        print(f"[chipbench] not reported: {name} = {reader.read(run)}",
              file=sys.stderr, flush=True)


def serve_family(ctx, fam: Family) -> dict:
    """ctx: see run.py.  Returns the raw material of the last line."""
    import jax

    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.serve import GenerationSession, ServeConfig

    sizes, cell, mix = ctx.sizes, ctx.cell, ctx.mix
    model, vocab = fam.decoder(sizes)    # a program without it fails here
    with ctx.span("chipbench.make_weights"):
        params = fam.weights(sizes, weights_olmo.seed_key(ctx.seed))
        jax.block_until_ready(params)
    ctx.log(f"weights on the device: "
            f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.2f} GB")

    serve_kw = dict(cell["serve_config"])
    for k in ("decode_buckets", "batch_buckets"):
        if k in serve_kw:
            serve_kw[k] = tuple(serve_kw[k])
    config = ServeConfig(**serve_kw)
    mesh = make_device_mesh((1,), ("d",), devices=[ctx.devices[0]])
    sess = GenerationSession(params, model=model, config=config, mesh=mesh)
    # ---- warm-up: the chunk-prefill and the decode program, the only two
    # shapes this kind of cell's traffic drives
    t0 = time.perf_counter()
    rng = np.random.default_rng([ctx.seed, 0xA])
    for n in (5, config.prefill_chunk + 3):
        sess.submit(rng.integers(1, vocab, size=n).tolist(), max_new_tokens=3)
    sess.run_until_drained()
    ctx.log(f"warm-up (two requests, compiles or cache loads) "
            f"{time.perf_counter() - t0:.1f} s")

    # ONE drawn order of lengths and arrival gaps (the mix's `order_seed`),
    # the ids the run's seed's: a round's time follows how many sequences
    # are live, which the order decides (PERF.md section 4)
    schedule = arrival_trace(mix, ctx.seed, ctx.seconds, vocab)
    w_from, w_to = schedule["window_from_s"], schedule["window_to_s"]
    loop = _PoolLoop(fam.gauges, sess, schedule, time.perf_counter, ctx.span)
    loop.start()
    while loop.now() < w_from:          # ramp: part of set-up
        loop.turn()

    def counters():
        return {k: sess.metrics.counter(k) for k in fam.counters}

    ctx.window_opens()
    c_open = counters()
    while loop.now() < w_to:
        loop.turn()
    ctx.window_closed()
    c_close = counters()
    trace = None
    if ctx.trace:
        trace_from = loop.now()
        n_steps0, c0 = len(loop.steps), counters()
        with ctx.profile() as prof:
            until = loop.now() + float(cell.get("trace_s", 4.0))
            while loop.now() < until:
                loop.turn()
        trace = prof.result
        trace["decode_calls"] = [s[1] for s in loop.steps[n_steps0:] if s[1]]
        trace["counted"] = {k: v - c0[k] for k, v in counters().items()}
        trace["prefill_chunks"] = trace["counted"]["prefill_chunks"]
        ctx.log(f"traced {trace['window_s']:.2f} s from t={trace_from:.1f}: "
                f"{trace['counted']}")

    n_window = sum(r["phase"] == "window" for r in schedule["requests"])

    def window_records():
        return [r for r in loop.records if r["req"]["phase"] == "window"]

    # the last request due in the window may be submitted after it closes
    drain_s = float(mix.get("drain_s", ctx.seconds))
    while loop.now() < w_to + drain_s and not (
            len(window_records()) == n_window
            and all(r["done"] for r in window_records())):
        loop.turn()
    window = window_records()
    t_end = loop.now()
    peak = ctx.memory_peak()
    pool = next(iter(sess._pools.values()))

    # ---- the numbers
    finished = [r for r in window if r["done"] and r["error"] is None
                and r.get("finish_reason") == "length"
                and len(r["ids"]) == r["req"]["max_new"]]
    failed = n_window - len(finished)
    ttft = [(r["stamps"][0] if r["stamps"] else t_end) - r["due_s"]
            for r in window]
    submitted = {id(r["req"]) for r in window}
    ttft += [t_end - q["due_s"] for q in schedule["requests"]   # never begun
             if q["phase"] == "window" and id(q) not in submitted]
    gaps, tokens_in_window = [], 0
    for r in loop.records:
        st = r["stamps"]
        gaps += [b - a for a, b in zip(st, st[1:]) if w_from <= b < w_to]
        tokens_in_window += sum(1 for s in st if w_from <= s < w_to)
        if st and w_from <= st[0] < w_to:
            tokens_in_window += len(r["req"]["prompt"])
    admit = [r["queued_until"] - r["due_s"] for r in window
             if r["queued_until"] is not None]
    in_window = [s for s in loop.steps if w_from <= s[0] < w_to]
    decoding = [n for t, n, _ in loop.held if w_from <= t < w_to] or [0]
    queued = [n for t, _, n in loop.held if w_from <= t < w_to] or [0]
    third = (w_to - w_from) / 3
    thirds = [[n for t, n, _ in loop.held
               if w_from + i * third <= t < w_from + (i + 1) * third] or [0]
              for i in range(3)]
    e2e = {"serve_tokens_per_s": tokens_in_window / ctx.seconds,
           "token_gap_p95_ms": 1e3 * _percentile(gaps, 0.95)}
    late = sorted(loop.late_s) or [0.0]
    step_ms = 1e3 * np.diff([s[0] for s in in_window])
    in_win = {k: c_close[k] - c_open[k] for k in fam.counters}
    ended = sum(1 for r in loop.records if r["done"] and r["stamps"]
                and w_from <= r["stamps"][-1] < w_to)

    def ms(values, qs):
        return ", ".join(f"p{round(100 * q)} "
                         f"{1e3 * _percentile(values or [0], q):.1f}"
                         for q in qs)

    ctx.log(f"ttft ms: mean {1e3 * statistics.mean(ttft):.1f}, "
            f"{ms(ttft, (0.5, 0.9))}, max {1e3 * max(ttft):.1f}; gap ms: "
            f"mean {1e3 * statistics.mean(gaps or [0]):.1f}, "
            f"{ms(gaps, (0.5, 0.9, 0.95, 0.99))}")
    if len(step_ms):
        ctx.log("step ms (end to end of consecutive steps) deciles: "
                + " ".join(f"{_percentile(step_ms, q / 10):.1f}"
                           for q in range(1, 10)))
    ctx.log(f"window {w_from:.1f}-{w_to:.1f} s: {n_window} attempted, "
            f"{failed} failed, {ended / ctx.seconds:.2f} finished/s inside "
            f"it, {len(gaps)} token gaps, {len(in_window)} steps, a chunk "
            f"call in "
            f"{100.0 * in_win['prefill_chunks'] / max(1, len(in_window)):.1f}"
            f" % of them; sequences decoding mean "
            f"{statistics.mean(decoding):.1f} max {max(decoding)} (thirds "
            f"{[round(statistics.mean(t), 1) for t in thirds]}), waiting "
            f"for admission mean {statistics.mean(queued):.1f} at the end "
            f"{queued[-1]}; admission wait mean "
            f"{1e3 * statistics.mean(admit or [0]):.0f} ms; run ended at "
            f"{t_end:.1f} s; generator late: median "
            f"{1e3 * statistics.median(late):.1f} ms, max "
            f"{1e3 * late[-1]:.1f} ms; counters in the window {in_win}")

    serve = {   # what the per-layer readers take
        "admit_wait_s": admit,
        "kv_pages_in_use": [s[2] for s in in_window if s[2] is not None],
        "arena_pages": pool.pool.n_pages,
        "padding_ratio": sess.metrics.prefill_padding_ratio(),
        "ttft_p90_ms": 1e3 * _percentile(ttft, 0.90),
        **fam.pools(sizes, pool, loop.gauge_steps, (w_from, w_to), ctx.log),
    }

    # ---- correct: the served tokens against the plain reference, after
    # the session's pools are freed
    sess.close()
    del sess, loop.sess, pool
    gc.collect()
    t0 = time.perf_counter()
    spec = cell["check"]
    reference = importlib.import_module("chipbench.reference."
                                        + fam.reference)
    check = served_tokens(
        reference.logits, params, sizes,
        sample_requests(finished, ctx.seed, spec, ctx.log), spec=spec,
        pad_to=max(config.decode_buckets),
        controls=fam.controls if ctx.control else (), log=ctx.log)
    ctx.log(f"reference check took {time.perf_counter() - t0:.1f} s")
    raw = {"correct": check["correct"] and failed == 0,
           "attempted": n_window, "failed": failed, "e2e": e2e,
           "trace": trace, "serve": serve, "memory_peak_bytes": peak,
           "check": check, "sizes": sizes}
    if trace:
        log_unlisted(ctx, raw, fam.unlisted)
    return raw


# ---- Olmo Hybrid


def model_config(sizes: dict):
    from easydist_tpu.models.olmo_hybrid import OlmoHybridConfig

    d = weights_olmo.dims(sizes)
    return OlmoHybridConfig(
        vocab=d["vocab"], dim=d["hidden"], layer_types=d["kinds"],
        heads=d["q"], kv_heads=d["kv"], ffn_dim=d["ffn"],
        linear_heads=d["heads"], key_dim=d["dk"], value_dim=d["dv"],
        conv_kernel=d["taps"],
        allow_neg_eigval=bool(sizes["linear_allow_neg_eigval"]),
        eps=float(sizes["rms_norm_eps"]), dtype="bfloat16")


def _decoder(sizes: dict):
    from easydist_tpu.models import olmo_hybrid

    cfg = model_config(sizes)
    return olmo_hybrid.decoder(cfg), cfg.vocab


def _delta_pools(sizes, pool, gauge_steps, window, log) -> dict:
    """A delta-rule layer keeps one matrix a head a SLOT, whatever the
    sequences' lengths: the gauge, read off the leaves after every round,
    is one number all run long, and it is what the shapes say."""
    n_slots = pool.state.n_slots
    seen = {g["delta_state_bytes"] for _, g in gauge_steps
            if g["delta_state_bytes"] is not None}
    want = kernel_costs_delta.stored_state_bytes(n_slots, sizes)
    used = [g["state_slots_in_use"] for t, g in gauge_steps
            if window[0] <= t < window[1]
            and g["state_slots_in_use"] is not None]
    log(f"delta_state_bytes over the run: {sorted(seen)} (the shapes give "
        f"{want}: {n_slots} slots x {kernel_costs_delta.state_layers(sizes)} "
        f"layers x {kernel_costs_delta.state_bytes(sizes)} bytes, stored as "
        f"they are needed); a sequence also holds "
        f"{kernel_costs_delta.conv_tail_bytes(sizes)} bytes of conv tail a "
        f"layer and {pool.page_bytes // pool.chunk} bytes of K/V a token "
        f"over the full layers; state slots in use mean "
        f"{statistics.mean(used or [0]):.1f} max {max(used or [0])} of "
        f"{n_slots}")
    if seen != {want}:
        raise RuntimeError("the delta states moved, or hold another size "
                           "than one matrix a head a slot a layer")
    return {"state_slots_in_use": used, "state_slots": n_slots}


OLMO = Family(
    weights=weights_olmo.olmo_params, decoder=_decoder,
    reference="olmo_hybrid",
    controls=(("fp8 operands", "fp8_operands"),
              ("bf16 recurrence", "bf16_recurrence")),
    gauges=("delta_state_bytes", "state_slots_in_use", "kv_tokens_live"),
    counters=("tokens_generated", "decode_steps", "prefill_chunks",
              "delta_rows_updated", "delta_chunk_positions",
              "prefill_pages_walked", "prefill_pages_bucket",
              "prefill_attn_pairs"),
    # the pool's share is the Granite cell's, unlisted; the host's share of
    # a step is listed for the Mistral cell alone and the seven of the
    # session's timeline for the three serving cells a test of the
    # benchmark's holds their lists to (PERF.md section 7 (a)): this cell's
    # name waits for a `benchmark` PR.  The last is this PR's: the paged
    # decode kernel at ONE query row a KV head
    unlisted=("state_pool_use_pct", "session_host_ms_per_step",
              "session_empty_pct", "decode_gap_host_ms",
              "prefill_gap_host_ms", "step_caller_ms",
              "decode_launch_readback_ms", "serve_compile_s",
              "serve_xla_compiles", "mha_paged_decode_roofline"),
    pools=_delta_pools)
UNLISTED = OLMO.unlisted


def run(ctx) -> dict:
    return serve_family(ctx, OLMO)
