"""Serving cells of models with latent attention (A.X-K1, `axk1`): one
`GenerationSession` on one chip, built from `models/axk1.py`, under the open
loop of `runners/serve.py` — the same loop (`_Loop`), window, ramp, tail and
traced part as `serve_window.py`; what differs is the model it builds, the
gauges it reads (the latent arena's bytes, the positions each round
attended), the sample the check draws (two requests past the original
context, where a wrong YaRN blend or scale shows) and the reference the
served tokens are held to (`reference/axk1.py`, the expanded form)."""

import gc
import importlib
import statistics
import sys
import time

import numpy as np

from chipbench import compare, kernel_costs_latent, traffic_gen, weights_axk1
from chipbench.runners import serve_window
from chipbench.runners.serve import _Loop, _percentile
from chipbench.runners.serve_hybrid import MOE_COUNTERS

GAUGES = ("latent_cache_bytes", "kv_tokens_live")
COUNTERS = MOE_COUNTERS + ("prefill_pages_walked", "prefill_pages_bucket",
                           "prefill_attn_pairs")

# readers under `chipbench/metrics/` that `run.py` does not call for this
# cell; a traced run reads them here and logs each on stderr as `not
# reported`.  The three expert readers are the Granite cell's, unlisted, fed
# this config's sizes; the host's share of a step is listed for the Mistral
# cell alone and the seven of the session's timeline for the three serving
# cells a test of the benchmark's holds their lists to (PERF.md section 7
# (a)): this cell's name waits for a `benchmark` PR.
UNLISTED = ("expert_ffn_share_pct", "expert_ffn_roofline",
            "expert_load_max_over_mean", "session_host_ms_per_step",
            "session_empty_pct", "decode_gap_host_ms", "prefill_gap_host_ms",
            "step_caller_ms", "decode_launch_readback_ms", "serve_compile_s",
            "serve_xla_compiles")


class _LatentLoop(_Loop):
    """`_Loop`, reading the latent arena's gauges after every step too."""

    def __init__(self, *a):
        super().__init__(*a)
        self.gauge_steps = []    # (t_end, {gauge: value})
        self.held = []           # (t_end, sequences decoding, all it holds)

    def turn(self) -> None:
        n = len(self.steps)
        super().turn()
        if len(self.steps) > n:
            gauges = self.sess.metrics.snapshot()["gauges"]
            self.gauge_steps.append((self.steps[-1][0],
                                     {k: gauges.get(k) for k in GAUGES}))
            self.held.append((self.steps[-1][0], sum(
                p.n_active for p in self.sess._pools.values()),
                self.sess.queue_depth))


def model_config(sizes: dict):
    from easydist_tpu.models.axk1 import AxK1Config

    d, rs = weights_axk1.dims(sizes), sizes["rope_scaling"]
    return AxK1Config(
        vocab=d["vocab"], dim=d["hidden"], layers=d["layers"],
        dense_layers=sizes["first_k_dense_replace"], heads=d["heads"],
        q_rank=d["q_rank"], kv_rank=d["kv_rank"], nope_dim=d["nope"],
        rope_dim=d["rope"], v_dim=d["v"],
        rope_theta=float(sizes["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_original=int(rs["original_max_position_embeddings"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        ffn_dim=d["dense"], experts=d["experts"], top_k=d["top_k"],
        experts_held=(d["first"], d["held"]), expert_dim=d["expert"],
        shared_dim=d["expert"] * sizes["n_shared_experts"],
        routed_scale=float(sizes["routed_scaling_factor"]),
        eps=float(sizes["rms_norm_eps"]), dtype="bfloat16")


def arrival_trace(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """`traffic_gen.serve_schedule` under the mix's `order_seed` — ONE draw
    of the ORDER of the lengths and of the gaps between arrivals, replayed
    in every run — with every prompt's ids from `seed`.  A chunk call's
    time follows the sum of its rows' depths, so which long prompts share a
    call (the order alone) moves this cell's p95 by ~3 % (sd) from seed to
    seed in a 50 s window, over the bound; replayed in one order, runs
    differ in weights and ids and read what the program costs (PERF.md
    section 4)."""
    schedule = traffic_gen.serve_schedule(mix, int(mix["order_seed"]),
                                          seconds, vocab)
    rng = np.random.default_rng([int(seed), 0x1D5])
    for req in schedule["requests"]:
        req["prompt"] = rng.integers(
            1, vocab, size=len(req["prompt"])).tolist()
    return schedule


def sample_requests(finished: list, seed: int, spec: dict, log) -> list:
    """`long_requests` of the finished requests LONGER than `longer_than`
    tokens (the longest, and the others drawn from the seed:
    `serve_window.sample_requests`) and the rest of `requests` from all the
    others (`compare.sample_requests`)."""
    longs = serve_window.sample_requests(
        finished, seed, int(spec["long_requests"]),
        int(spec["longer_than"]), log)
    taken = {id(r) for r in longs}
    rest = [r for r in finished if id(r) not in taken]
    return longs + compare.sample_requests(
        rest, seed, int(spec["requests"]) - len(longs))


def _numbers(deficits) -> dict:
    """What `check.limits` holds a run to.  The widest gap guards against a
    plainly wrong token and cannot tell bf16 from fp8 (one near-tie among
    the router's scores flips a HELD expert and moves a logit by ~1 under
    either); the mean can, and so can the share of served tokens that are
    not the reference's first choice, which hardly moves with the seed."""
    deficits = np.asarray(deficits)
    return {"deficit_max": float(deficits.max()),
            "deficit_mean": float(deficits.mean()),
            "not_first_choice_pct": 100.0 * float((deficits > 0).mean())}


def served_tokens(params, sizes, sample, *, spec, pad_to, control,
                  log) -> dict:
    """`serve_window.served_tokens` against this family's reference: for
    each sampled request, one float32 forward over prompt + served tokens;
    at each served position, how far the served token's reference logit
    lies below the reference's best (`_numbers`).  With `control`, the same
    positions under fp8 operands: the gap of the token IT puts first, held
    to the same limits (`control["correct"]`, which has to come out false;
    the run's own `correct` stays the program's)."""
    import jax.numpy as jnp

    from chipbench.reference import axk1

    n_rows = int(spec["rows"])
    deficits, control_deficits, exact, spread = [], [], 0, []
    for rec in sample:
        prompt, ids = rec["req"]["prompt"], rec["ids"]
        toks = np.zeros((pad_to,), np.int32)
        full = (prompt + ids)[:pad_to]
        toks[:len(full)] = full
        at = np.minimum(len(prompt) - 1 + np.arange(n_rows), pad_to - 1)
        rows = np.asarray(axk1.logits(
            params, sizes, jnp.asarray(toks), rows=at))[:len(ids)]
        served = np.asarray(ids[:len(rows)])
        best = rows.max(axis=-1)
        spread.append(float(rows.std(axis=-1).mean()))
        deficits += list(best - rows[np.arange(len(rows)), served])
        exact += int((rows.argmax(axis=-1) == served).sum())
        if control:
            low = np.asarray(axk1.logits(
                params, sizes, jnp.asarray(toks), rows=at, quant=True))
            pick = low[:len(ids)].argmax(-1)
            control_deficits += list(best - rows[np.arange(len(rows)), pick])
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
    if control:
        # the control's numbers against the cell's own limits: a control
        # that passes them all means the limits have no power
        low = _numbers(control_deficits)
        low["correct"] = compare._verdict(
            low, spec["limits"],
            lambda line: log("control (fp8 operands) " + line))
        out["control"] = low
    return out


def log_unlisted(ctx, raw: dict) -> None:
    """Each reader of `UNLISTED` on what `run.py` would hand it; the expert
    readers take an expert's width under the Granite config's key."""
    from chipbench import trace_reduce

    trace = raw["trace"]["trace"]
    sizes = dict(raw["sizes"],
                 intermediate_size=raw["sizes"]["moe_intermediate_size"])
    run = dict(raw, sizes=sizes, cell=ctx.cell, mix=ctx.mix, chips=1,
               rehearse=ctx.rehearse, device_kind=trace["device_kind"],
               busy=trace_reduce.busy(trace, 1))
    for name in UNLISTED:
        reader = importlib.import_module("chipbench.metrics." + name)
        print(f"[chipbench] not reported: {name} = {reader.read(run)}",
              file=sys.stderr, flush=True)


def run(ctx) -> dict:
    """ctx: see run.py.  Returns the raw material of the last line."""
    import jax

    # a program without this model fails here, at once
    from easydist_tpu.models import axk1
    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.serve import GenerationSession, ServeConfig

    sizes, cell, mix = ctx.sizes, ctx.cell, ctx.mix
    dev = ctx.devices[0]
    key = weights_axk1.seed_key(ctx.seed)
    with ctx.span("chipbench.make_weights"):
        params = weights_axk1.axk1_params(sizes, key)
        jax.block_until_ready(params)
    ctx.log(f"weights on the device: "
            f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.2f} GB")

    serve_kw = dict(cell["serve_config"])
    for k in ("decode_buckets", "batch_buckets"):
        if k in serve_kw:
            serve_kw[k] = tuple(serve_kw[k])
    config = ServeConfig(**serve_kw)
    cfg = model_config(sizes)
    mesh = make_device_mesh((1,), ("d",), devices=[dev])
    sess = GenerationSession(params, model=axk1.decoder(cfg), config=config,
                             mesh=mesh)
    # ---- warm-up: the chunk-prefill and the decode program, the only two
    # shapes this cell's traffic drives
    t0 = time.perf_counter()
    rng = np.random.default_rng([ctx.seed, 0xA])
    for n in (5, config.prefill_chunk + 3):
        sess.submit(rng.integers(1, cfg.vocab, size=n).tolist(),
                    max_new_tokens=3)
    sess.run_until_drained()
    ctx.log(f"warm-up (two requests, compiles or cache loads) "
            f"{time.perf_counter() - t0:.1f} s")

    schedule = arrival_trace(mix, ctx.seed, ctx.seconds, cfg.vocab)
    w_from, w_to = schedule["window_from_s"], schedule["window_to_s"]
    loop = _LatentLoop(sess, schedule, time.perf_counter, ctx.span)
    drain_s = float(mix.get("drain_s", ctx.seconds))
    loop.start()
    while loop.now() < w_from:          # ramp: part of set-up
        loop.turn()
    ctx.window_opens()
    while loop.now() < w_to:
        loop.turn()
    ctx.window_closed()

    def counters():
        return {k: sess.metrics.counter(k) for k in COUNTERS}

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
    while loop.now() < w_to + drain_s and not (
            len(window_records()) == n_window
            and all(r["done"] for r in window_records())):
        loop.turn()
    window = window_records()
    t_end = loop.now()
    peak = ctx.memory_peak()
    pool = next(iter(sess._pools.values()))
    arena_pages, n_slots = pool.pool.n_pages, pool.n_slots
    page_bytes = pool.page_bytes

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
    gauges = [g for t, g in loop.gauge_steps if w_from <= t < w_to]
    live_tokens = [g["kv_tokens_live"] for g in gauges
                   if g["kv_tokens_live"] is not None]
    decoding = [n for t, n, _ in loop.held if w_from <= t < w_to]
    held = [n for t, _, n in loop.held if w_from <= t < w_to]
    cache_bytes = {g["latent_cache_bytes"] for _, g in loop.gauge_steps
                   if g["latent_cache_bytes"] is not None}
    e2e = {"serve_tokens_per_s": tokens_in_window / ctx.seconds,
           "token_gap_p95_ms": 1e3 * _percentile(gaps, 0.95)}
    late = sorted(loop.late_s) or [0.0]
    step_ms = 1e3 * np.diff([s[0] for s in in_window])
    ctx.log(f"ttft ms: mean {1e3 * statistics.mean(ttft):.1f}, p50 "
            f"{1e3 * _percentile(ttft, 0.5):.1f}, p90 "
            f"{1e3 * _percentile(ttft, 0.9):.1f}, max "
            f"{1e3 * max(ttft):.1f}; gap ms: mean "
            f"{1e3 * statistics.mean(gaps or [0]):.1f}, p50 "
            f"{1e3 * _percentile(gaps or [0], 0.5):.1f}, p90 "
            f"{1e3 * _percentile(gaps or [0], 0.9):.1f}, p95 "
            f"{1e3 * _percentile(gaps or [0], 0.95):.1f}, p99 "
            f"{1e3 * _percentile(gaps or [0], 0.99):.1f}")
    if len(step_ms):
        ctx.log("step ms (end to end of consecutive steps) deciles: "
                + " ".join(f"{_percentile(step_ms, q / 10):.1f}"
                           for q in range(1, 10)))
    ctx.log(f"window {w_from:.1f}-{w_to:.1f} s: {n_window} attempted, "
            f"{failed} failed, {len(gaps)} token gaps, {len(in_window)} "
            f"steps; sequences decoding mean "
            f"{statistics.mean(decoding or [0]):.1f} max "
            f"{max(decoding or [0])} of {n_slots}, held (queued and "
            f"prefilling too) mean {statistics.mean(held or [0]):.1f} max "
            f"{max(held or [0])}; positions attended a "
            f"round mean {statistics.mean(live_tokens or [0]):.0f} max "
            f"{max(live_tokens or [0])}; admission wait mean "
            f"{1e3 * statistics.mean(admit or [0]):.0f} ms; run ended at "
            f"{t_end:.1f} s; generator late: median "
            f"{1e3 * statistics.median(late):.1f} ms, max "
            f"{1e3 * late[-1]:.1f} ms; counters {counters()}")
    # the arena holds one row a position a layer and nothing a head: the
    # gauge, read off the leaves after every round, is one number all run
    # long, and it is what the shapes say (the row stored in whole tiles)
    want_cache = kernel_costs_latent.stored_cache_bytes(
        arena_pages, pool.chunk, sizes)
    want_page = pool.chunk * sizes["num_hidden_layers"] \
        * kernel_costs_latent.stored_token_bytes(sizes)
    ctx.log(f"latent_cache_bytes over the run: {sorted(cache_bytes)} (the "
            f"shapes give {want_cache}: {arena_pages} pages of {page_bytes} "
            f"bytes, {kernel_costs_latent.stored_token_bytes(sizes)} a "
            f"token a layer stored for the "
            f"{kernel_costs_latent.token_bytes(sizes)} needed; every "
            f"head's own keys and values would be "
            f"{kernel_costs_latent.expanded_token_bytes(sizes)})")
    if cache_bytes != {want_cache} or page_bytes != want_page:
        raise RuntimeError("the latent arena moved, or holds another size "
                           "than one row a position a layer")

    serve = {   # what the per-layer readers take
        "admit_wait_s": admit,
        "kv_pages_in_use": [s[2] for s in in_window if s[2] is not None],
        "arena_pages": arena_pages,
        "padding_ratio": sess.metrics.prefill_padding_ratio(),
        "ttft_p90_ms": 1e3 * _percentile(ttft, 0.90),
    }

    # ---- correct: the served tokens against the plain reference, after
    # the session's pools are freed
    sess.close()
    del sess, loop.sess, pool
    gc.collect()
    t0 = time.perf_counter()
    spec = cell["check"]
    sample = sample_requests(finished, ctx.seed, spec, ctx.log)
    check = served_tokens(
        params, sizes, sample, spec=spec,
        pad_to=max(config.decode_buckets), control=ctx.control, log=ctx.log)
    ctx.log(f"reference check took {time.perf_counter() - t0:.1f} s")
    raw = {"correct": check["correct"] and failed == 0,
           "attempted": n_window, "failed": failed, "e2e": e2e,
           "trace": trace, "serve": serve, "memory_peak_bytes": peak,
           "check": check, "sizes": sizes}
    if trace:
        log_unlisted(ctx, raw)
    return raw
