"""Serving cells: one `GenerationSession` on one chip under open-loop (or
backlog) traffic.

The session has no per-token callback, so the runner owns the loop: it
submits what is due, calls `sess.step()`, and after each step reads
`sess.snapshot_inflight()` (what `FleetRouter` does), stamping every new
token with the time `step()` returned.  Every request is timed from when it
was DUE, not from when the loop got round to submitting it."""

import gc
import statistics
import time

import numpy as np

from chipbench import compare, traffic_gen, weights


def _percentile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; values must be non-empty."""
    return float(np.quantile(np.asarray(values, np.float64), q))


class _Loop:
    """The open loop: submit what is due, step, stamp new tokens."""

    def __init__(self, sess, schedule, clock, span):
        self.sess, self.clock, self.span = sess, clock, span
        self.todo = list(schedule["requests"])
        self.next = 0
        self.live = {}          # id(future) -> record
        self.records = []
        self.late_s = []
        self.t_start = None     # absolute time of schedule time 0
        self.steps = []         # (t_end, live decode tokens, kv pages in use)

    def start(self):
        self.t_start = self.clock()

    def now(self) -> float:
        return self.clock() - self.t_start

    def _submit_due(self):
        now = self.now()
        while self.next < len(self.todo) \
                and self.todo[self.next]["due_s"] <= now:
            req = self.todo[self.next]
            self.next += 1
            rec = {"req": req, "due_s": req["due_s"], "stamps": [],
                   "queued_until": None, "done": False, "ids": None,
                   "error": None}
            self.late_s.append(now - req["due_s"])
            try:
                rec["future"] = self.sess.submit(
                    req["prompt"], max_new_tokens=req["max_new"])
                self.live[id(rec["future"])] = rec
            except Exception as e:  # refused: counts as failed
                rec["error"], rec["done"] = repr(e), True
            self.records.append(rec)

    def idle(self) -> bool:
        return not self.live

    def turn(self) -> None:
        """One turn of the loop: submit, step, stamp.  Sleeps to the next
        due time when the session has nothing to do."""
        self._submit_due()
        if self.idle():
            if self.next < len(self.todo):
                time.sleep(max(0.0, min(
                    0.05, self.todo[self.next]["due_s"] - self.now())))
            else:
                time.sleep(0.005)
            return
        with self.span("chipbench.session_step"):
            self.sess.step()
        t = self.now()
        with self.span("chipbench.snapshot_inflight"):
            snap = self.sess.snapshot_inflight()
        with self.span("chipbench.stamp_tokens"):
            seen = set()
            decode_tokens = 0
            for entry in snap:
                rec = self.live.get(id(entry["future"]))
                if rec is None:
                    continue
                seen.add(id(entry["future"]))
                if entry["stage"] != "queued" and rec["queued_until"] is None:
                    rec["queued_until"] = t
                decode_tokens += self._stamp(rec, len(entry["ids"]), t)
            for key in [k for k in self.live if k not in seen]:
                rec = self.live.pop(key)   # retired in this step
                res = rec["future"].result(timeout=0)
                rec["ids"], rec["done"] = list(res["ids"]), True
                rec["finish_reason"] = res["finish_reason"]
                if rec["queued_until"] is None:
                    rec["queued_until"] = t
                decode_tokens += self._stamp(rec, len(rec["ids"]), t)
            pages = self.sess.metrics.snapshot()["gauges"].get(
                "kv_pages_in_use")
            self.steps.append((t, decode_tokens, pages))

    @staticmethod
    def _stamp(rec, n_now: int, t: float) -> int:
        """Stamp tokens len(stamps)..n_now-1 with t; returns the cached
        tokens the decode call that made the newest one attended to (0
        where the only new token came from prefill)."""
        n_before = len(rec["stamps"])
        rec["stamps"] += [t] * (n_now - n_before)
        if n_now > n_before and n_now >= 2:
            return len(rec["req"]["prompt"]) + n_now - 1
        return 0


def run(ctx) -> dict:
    """ctx: see run.py.  Returns the raw material of the last line."""
    import jax

    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.models.llama import LlamaConfig
    from easydist_tpu.serve import GenerationSession, ServeConfig

    sizes, cell, mix = ctx.sizes, ctx.cell, ctx.mix
    dev = ctx.devices[0]
    key = weights.seed_key(ctx.seed)
    with ctx.span("chipbench.make_weights"):
        params = jax.jit(lambda k: weights.mistral_params(sizes, k))(key)
        jax.block_until_ready(params)
    ctx.log(f"weights on the device: "
            f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.2f} GB")

    serve_kw = dict(cell["serve_config"])
    for k in ("decode_buckets", "batch_buckets"):
        if k in serve_kw:
            serve_kw[k] = tuple(serve_kw[k])
    config = ServeConfig(**serve_kw)
    cfg = LlamaConfig(
        vocab=sizes["vocab_size"], seq=max(config.decode_buckets),
        dim=sizes["hidden_size"], heads=sizes["num_attention_heads"],
        kv_heads=sizes["num_key_value_heads"],
        layers=sizes["num_hidden_layers"],
        ffn_dim=sizes["intermediate_size"],
        rope_theta=float(sizes["rope_theta"]), dtype="bfloat16")
    assert sizes["head_dim"] * cfg.heads == cfg.dim
    mesh = make_device_mesh((1,), ("d",), devices=[dev])
    sess = GenerationSession.for_llama(params, cfg, config=config, mesh=mesh)
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

    schedule = traffic_gen.serve_schedule(mix, ctx.seed, ctx.seconds,
                                          cfg.vocab)
    w_from, w_to = schedule["window_from_s"], schedule["window_to_s"]
    loop = _Loop(sess, schedule, time.perf_counter, ctx.span)
    drain_s = float(mix.get("drain_s", ctx.seconds))
    loop.start()
    while loop.now() < w_from:          # ramp: part of set-up
        loop.turn()
    ctx.window_opens()
    while loop.now() < w_to:
        loop.turn()
    ctx.window_closed()

    trace = None
    if ctx.trace:
        trace_from = loop.now()
        n_steps0 = len(loop.steps)
        chunks0 = sess.metrics.counter("prefill_chunks")
        with ctx.profile() as prof:
            until = loop.now() + float(cell.get("trace_s", 4.0))
            while loop.now() < until:
                loop.turn()
        trace = prof.result
        trace["decode_calls"] = [s[1] for s in loop.steps[n_steps0:] if s[1]]
        trace["prefill_chunks"] = \
            sess.metrics.counter("prefill_chunks") - chunks0
        ctx.log(f"traced {trace['window_s']:.2f} s from t={trace_from:.1f}: "
                f"{len(trace['decode_calls'])} decode rounds, "
                f"{trace['prefill_chunks']} prefill chunks")

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
    arena_pages = sum(p.pool.n_pages for p in sess._pools.values()
                      if hasattr(p, "pool"))

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
    e2e = {"serve_tokens_per_s": tokens_in_window / ctx.seconds}
    if mix["arrivals"]["process"] != "backlog":
        e2e["token_gap_p95_ms"] = 1e3 * _percentile(gaps, 0.95)
    late = sorted(loop.late_s) or [0.0]
    ctx.log(f"ttft ms: mean {1e3 * statistics.mean(ttft):.1f}, p50 "
            f"{1e3 * _percentile(ttft, 0.5):.1f}, p75 "
            f"{1e3 * _percentile(ttft, 0.75):.1f}, p90 "
            f"{1e3 * _percentile(ttft, 0.9):.1f}, max "
            f"{1e3 * max(ttft):.1f}; gap ms: mean "
            f"{1e3 * statistics.mean(gaps or [0]):.1f}, p99 "
            f"{1e3 * _percentile(gaps or [0], 0.99):.1f}")
    ctx.log(f"window {w_from:.1f}-{w_to:.1f} s: {n_window} attempted, "
            f"{failed} failed, {len(gaps)} token gaps, {len(in_window)} "
            f"steps; run ended at {t_end:.1f} s; generator late: median "
            f"{1e3 * statistics.median(late):.1f} ms, max "
            f"{1e3 * late[-1]:.1f} ms; ttft median "
            f"{1e3 * statistics.median(ttft):.0f} ms, gap median "
            f"{1e3 * statistics.median(gaps or [0]):.0f} ms")

    serve = {   # what the per-layer readers take
        "admit_wait_s": admit,
        "kv_pages_in_use": [s[2] for s in in_window if s[2] is not None],
        "arena_pages": arena_pages,
        "padding_ratio": sess.metrics.prefill_padding_ratio(),
        "ttft_p90_ms": 1e3 * _percentile(ttft, 0.90),
    }

    # ---- correct: the served tokens against the plain reference, after
    # the session's arena is freed
    sess.close()
    del sess, loop.sess
    gc.collect()
    t0 = time.perf_counter()
    check = compare.served_tokens(
        params, sizes, finished, seed=ctx.seed, spec=cell["check"],
        pad_to=max(config.decode_buckets), control=ctx.control, log=ctx.log)
    ctx.log(f"reference check took {time.perf_counter() - t0:.1f} s")
    return {"correct": check["correct"] and failed == 0,
            "attempted": n_window, "failed": failed, "e2e": e2e,
            "trace": trace, "serve": serve, "memory_peak_bytes": peak,
            "check": check, "sizes": sizes}
