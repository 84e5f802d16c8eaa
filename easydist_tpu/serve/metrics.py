"""Serving observability: counters, gauges, latency histograms.

Everything a dashboard needs to judge a serving deployment — queue depth,
batch occupancy (real rows / bucket rows), executable-cache hit rate,
p50/p95/p99 latency — collected lock-cheap in-process and exported through
the existing runtime plumbing (`runtime.perfdb.PerfDB`).  Where a step's time
went, and each finished request's timeline, are in `runtime/spans.py`.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

# log-spaced bucket upper bounds, 0.1ms .. ~107s (x2 per bucket)
_DEFAULT_BOUNDS = tuple(1e-4 * (2 ** i) for i in range(21))

# A model's recurrent layers by the key of their leaves in a pool, and the
# counter their chunked scans' real positions are summed under.  Each kind
# also has a gauge <kind>_state_bytes and a counter <kind>_rows_updated
# (`record_layer_states`): the next recurrence is one more entry.
RECURRENT_KINDS = {"delta": "delta_chunk_positions",
                   "selective": "selective_scan_positions",
                   "shortconv": "shortconv_chunk_positions"}


class LatencyHistogram:
    """Fixed log-spaced histogram over seconds.  Percentiles resolve to the
    upper bound of the bucket containing the rank — a <=2x overestimate by
    construction, stable under any traffic shape, O(1) memory."""

    def __init__(self, bounds=_DEFAULT_BOUNDS):
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # +1 overflow bucket
        self.total = 0
        self.sum = 0.0

    def observe(self, seconds: float) -> None:
        idx = len(self.bounds)
        for i, b in enumerate(self.bounds):
            if seconds <= b:
                idx = i
                break
        self.counts[idx] += 1
        self.total += 1
        self.sum += seconds

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 100] -> seconds (bucket upper bound), None when empty."""
        if self.total == 0:
            return None
        rank = max(1, int(round(p / 100.0 * self.total)))
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank:
                return self.bounds[i] if i < len(self.bounds) \
                    else self.bounds[-1] * 2
        return self.bounds[-1] * 2

    def mean(self) -> Optional[float]:
        return self.sum / self.total if self.total else None

    def snapshot(self) -> Dict[str, float]:
        out = {"count": self.total}
        if self.total:
            out.update(mean_s=self.mean(),
                       p50_s=self.percentile(50),
                       p95_s=self.percentile(95),
                       p99_s=self.percentile(99))
        return out


class ServeMetrics:
    """Thread-safe counters/gauges/histograms for one `ServeEngine`.

    Counter names (all monotonically increasing):
      requests_submitted / completed / failed / timed_out / rejected /
      shed (circuit open), batches_executed, batch_rows_real,
      batch_rows_padded, compile_cache_hits, compile_cache_misses,
      oom_degradations, transient_retries, exec_timeouts (watchdog),
      tokens_generated (decode steps x active slots).
    Chunked-prefill counters: prefills (admissions), prefill_chunks
      (batched chunk calls), prefill_pages_walked / prefill_pages_bucket
      (paged: the K/V pages under the live rows' extents, of those their
      buckets hold — the share of a bucket the chunk kernel reads),
      prefill_tokens_real (prompt tokens actually
      needing prefill, prefix reuse already deducted),
      prefill_tokens_padded (executed token slots = rows x chunk per
      call), prefix_tokens_reused / prefix_tokens_total,
      prefix_cache_hits / misses / evictions (trie chunk events).
    Speculative-decoding counters (serve/speculate.py): verify_steps,
      draft_tokens_proposed / draft_tokens_accepted,
      speculative_rollback_pages_released (paged rollback returns);
      gauge acceptance_rate (lifetime accepted / proposed).
    Expert-FFN counters (a model whose ffn routes; summed over layers):
      per decode round moe_rounds, moe_pairs_routed, moe_experts_hit,
      moe_max_expert_pairs, moe_pair_slots (the (token, choice) slots
      offered: rows x top_k x expert layers); per chunk call the same
      under moe_prefill_* (moe_prefill_calls).  Gauges state_slots_in_use / state_slots: the
      recurrent-state pool beside kv_pages_in_use.
    Selective-state (Mamba-1) layers: gauge selective_state_bytes (the
      `selective` leaves' bytes: a constant), counters
      selective_rows_updated (live rows x selective layers, every decode
      round) and selective_scan_positions (real positions x selective
      layers, every chunk call).
    Gauges: decode_slot_occupancy (active slots / total slots at the last
      decode step), prefill_padding_ratio (executed token slots per real
      prefill token, 1.0 = zero waste), prefix_cache_hit_rate (fraction
      of prompt tokens restored from the prefix trie).
    Histograms: queue_wait (submit->drain), execute (device time incl.
    host roundtrip), e2e (submit->future resolution), per_token (one
    decode-step wall time, all slots), ttft (submit->first token)."""

    def __init__(self, replica_id: Optional[str] = None):
        # fleet label: stamped into every snapshot and the default PerfDB
        # sub_key so N replicas' histories never collide under one key
        self.replica_id = replica_id
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        self._prompt_hist: Dict[int, int] = {}  # prompt_len -> admissions
        self.queue_wait = LatencyHistogram()
        self.execute = LatencyHistogram()
        self.e2e = LatencyHistogram()
        self.per_token = LatencyHistogram()
        self.ttft = LatencyHistogram()

    # ------------------------------------------------------------- recording
    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, hist_name: str, seconds: float) -> None:
        with self._lock:
            getattr(self, hist_name).observe(seconds)

    def record_batch(self, n_real: int, bucket: int,
                     execute_s: float) -> None:
        with self._lock:
            self._counters["batches_executed"] = \
                self._counters.get("batches_executed", 0) + 1
            self._counters["batch_rows_real"] = \
                self._counters.get("batch_rows_real", 0) + n_real
            self._counters["batch_rows_padded"] = \
                self._counters.get("batch_rows_padded", 0) + bucket
            self.execute.observe(execute_s)

    def record_decode_step(self, n_active: int, n_slots: int,
                           step_s: float, pages_walked: int = 0,
                           pages_bucket: int = 0) -> None:
        """One token step across the whole slot pool: `n_active` slots
        produced a real token, `n_slots` rows executed either way.  A paged
        round also says how many K/V pages lie under its live rows'
        positions (`pages_walked`) of how many the pool's rows could hold
        (`pages_bucket`: every slot's bucket) — both PER FULL-ATTENTION
        LAYER: the paged decode kernel copies that many pages in each such
        layer, however many the model has (Olmo Hybrid's 4 of 16,
        K-EXAONE's one; a sliding or a state layer walks none).  Their
        ratio is the share of a bucket's windows the kernel's loop
        visits."""
        with self._lock:
            self._counters["tokens_generated"] = \
                self._counters.get("tokens_generated", 0) + n_active
            self._counters["decode_steps"] = \
                self._counters.get("decode_steps", 0) + 1
            if pages_bucket:
                self._counters["decode_pages_walked"] = \
                    self._counters.get("decode_pages_walked", 0) \
                    + pages_walked
                self._counters["decode_pages_bucket"] = \
                    self._counters.get("decode_pages_bucket", 0) \
                    + pages_bucket
            self._gauges["decode_slot_occupancy"] = \
                (n_active / n_slots) if n_slots else 0.0
            self.per_token.observe(step_s)

    def record_speculation(self, proposed: int, accepted: int,
                           committed: int, n_ran: int, n_slots: int,
                           step_s: float,
                           pages_released: int = 0) -> None:
        """One speculative verify round (serve/speculate.py): `proposed`
        draft tokens entered the verify step, `accepted` of them were
        ratified, `committed` tokens were emitted in total (accepted
        drafts + one correction/bonus per slot — these count toward
        `tokens_generated` exactly like decode-step tokens, since they
        ARE the plain-greedy tokens).  `n_ran` slots rode the verify
        program out of `n_slots` rows; `pages_released` arena pages were
        returned by the paged rollback.  `acceptance_rate` is the
        lifetime accepted/proposed ratio — the drafter-quality signal
        (speedup ~ committed tokens per verify step)."""
        with self._lock:
            self._counters["verify_steps"] = \
                self._counters.get("verify_steps", 0) + 1
            self._counters["draft_tokens_proposed"] = \
                self._counters.get("draft_tokens_proposed", 0) + proposed
            self._counters["draft_tokens_accepted"] = \
                self._counters.get("draft_tokens_accepted", 0) + accepted
            self._counters["tokens_generated"] = \
                self._counters.get("tokens_generated", 0) + committed
            if pages_released:
                self._counters["speculative_rollback_pages_released"] = \
                    self._counters.get(
                        "speculative_rollback_pages_released", 0) \
                    + pages_released
            total = self._counters["draft_tokens_proposed"]
            if total:
                self._gauges["acceptance_rate"] = \
                    self._counters["draft_tokens_accepted"] / total
            self._gauges["decode_slot_occupancy"] = \
                (n_ran / n_slots) if n_slots else 0.0
            self.per_token.observe(step_s)

    def record_admission(self, prompt_len: int, prefix_len: int) -> None:
        """One prompt admitted into the chunked-prefill scheduler:
        `prefix_len` of its `prompt_len` tokens were restored from the
        prefix trie, the rest must run through prefill."""
        with self._lock:
            self._counters["prefills"] = \
                self._counters.get("prefills", 0) + 1
            # prompt-length histogram (exact counts per length) — what
            # sim/capacity.py::TrafficSpec.from_metrics reconstructs its
            # prompt distribution from
            self._prompt_hist[prompt_len] = \
                self._prompt_hist.get(prompt_len, 0) + 1
            self._counters["prefill_tokens_real"] = \
                self._counters.get("prefill_tokens_real", 0) \
                + (prompt_len - prefix_len)
            self._counters["prefix_tokens_reused"] = \
                self._counters.get("prefix_tokens_reused", 0) + prefix_len
            total = self._counters["prefix_tokens_total"] = \
                self._counters.get("prefix_tokens_total", 0) + prompt_len
            self._gauges["prefix_cache_hit_rate"] = \
                self._counters["prefix_tokens_reused"] / total

    def record_prefill_chunk(self, n_rows: int, chunk: int,
                             chunk_s: float, pages_walked: int = 0,
                             pages_bucket: int = 0,
                             attn_pairs: int = 0,
                             scan_positions: Optional[Dict[str, int]] = None
                             ) -> None:
        """One batched chunk call: `n_rows` rows executed `chunk`
        token slots each (idle rows and padded tails included — that IS
        the waste the padding-ratio gauge measures).  The call also
        says how many K/V pages its live rows' extents cover
        (`pages_walked`: what the chunk kernel reads a layer) of how many
        their buckets hold (`pages_bucket`: what the gather path reads),
        and how many (query, visible key) pairs its REAL positions make
        (`attn_pairs`: the attention the model asks of it, a head a
        layer), and a model with recurrent layers how many REAL positions
        its chunked scans took, summed over the layers of each kind
        (`scan_positions`: kind -> positions, counted under
        `RECURRENT_KINDS[kind]`)."""
        with self._lock:
            self._counters["prefill_chunks"] = \
                self._counters.get("prefill_chunks", 0) + 1
            if pages_bucket:
                self._counters["prefill_pages_walked"] = \
                    self._counters.get("prefill_pages_walked", 0) \
                    + pages_walked
                self._counters["prefill_pages_bucket"] = \
                    self._counters.get("prefill_pages_bucket", 0) \
                    + pages_bucket
                self._counters["prefill_attn_pairs"] = \
                    self._counters.get("prefill_attn_pairs", 0) + attn_pairs
            for kind, positions in (scan_positions or {}).items():
                name = RECURRENT_KINDS[kind]
                self._counters[name] = \
                    self._counters.get(name, 0) + positions
            padded = self._counters["prefill_tokens_padded"] = \
                self._counters.get("prefill_tokens_padded", 0) \
                + n_rows * chunk
            real = self._counters.get("prefill_tokens_real", 0)
            if real:
                self._gauges["prefill_padding_ratio"] = padded / real
            self.execute.observe(chunk_s)

    def record_kv_pool(self, pages_in_use: int, mapped_tokens: int,
                       page_tokens: int,
                       quant_bytes_saved: Optional[int] = None) -> None:
        """Paged-KV pool occupancy: `pages_in_use` arena pages are live
        (slot-mapped or trie-held) holding `mapped_tokens` real tokens of
        `pages_in_use * page_tokens` capacity.  `kv_page_utilization` is
        the intra-page fill fraction — 1.0 means zero fragmentation, and
        (1 - it) is the only padding waste the pool CAN have.
        `quant_bytes_saved` is HBM the live pages did NOT spend versus
        model-precision storage (block-scaled int8 payload + scales vs
        model dtype) — the quantized arena's density win, exported to
        the PerfDB with every snapshot."""
        with self._lock:
            self._gauges["kv_pages_in_use"] = pages_in_use
            cap = pages_in_use * page_tokens
            self._gauges["kv_page_utilization"] = \
                (mapped_tokens / cap) if cap else 1.0
            if quant_bytes_saved is not None:
                self._gauges["kv_quant_bytes_saved"] = quant_bytes_saved

    def record_state_pool(self, slots_in_use: int, n_slots: int) -> None:
        """Recurrent-state pool occupancy (a model with state layers): a
        slot per admitted sequence, prefilling or decoding."""
        with self._lock:
            self._gauges["state_slots_in_use"] = slots_in_use
            self._gauges["state_slots"] = n_slots

    def record_window_rings(self, slots_in_use: int, ring_bytes: int) -> None:
        """The rings of a model's window layers: a slot per admitted
        sequence, and the bytes of ALL the rings as the last program handed
        them back — a constant, whatever the sequences' lengths."""
        with self._lock:
            self._gauges["window_ring_slots_in_use"] = slots_in_use
            self._gauges["window_ring_bytes"] = ring_bytes

    def record_latent_cache(self, nbytes: int) -> None:
        """The bytes of a latent arena's leaves as the last program handed
        them back: one row a position a layer, whatever the heads — a
        constant, like the arena itself."""
        with self._lock:
            self._gauges["latent_cache_bytes"] = nbytes

    def record_layer_states(self, kind: str, nbytes: int,
                            rows_updated: int) -> None:
        """The states of a model's recurrent layers of one `kind` — "delta"
        (the delta rule's linear-attention layers) or "selective" (Mamba-1
        layers), the key of their leaves in the pool: the bytes of ALL
        those leaves as the last program handed them back (gauge
        <kind>_state_bytes: a constant, whatever the sequences' lengths)
        and the states this decode round updated in place (counter
        <kind>_rows_updated: live rows x layers of that kind)."""
        with self._lock:
            self._gauges[f"{kind}_state_bytes"] = nbytes
            self._counters[f"{kind}_rows_updated"] = \
                self._counters.get(f"{kind}_rows_updated", 0) + rows_updated

    def record_moe(self, step: str, pairs_routed: int, experts_hit: int,
                   max_expert_pairs: int, pair_slots: int = 0) -> None:
        """One program's expert routing, summed over the layers on the
        device and read back with the program's tokens: (token, expert)
        pairs routed to held experts, held experts that got at least one,
        and the busiest held expert's pairs; and, known on the host, the
        (token, choice) slots the program offered (`pair_slots`: its rows x
        top_k x expert layers), so that pairs_routed / pair_slots is the
        share of them that were for an expert held here.  `step` is
        "decode" (a round: `moe_rounds`, `moe_pairs_routed`, ...) or
        "prefill" (a chunk call: `moe_prefill_calls`,
        `moe_prefill_pairs_routed`, ...)."""
        pre = "moe_" if step == "decode" else f"moe_{step}_"
        calls = "moe_rounds" if step == "decode" else f"moe_{step}_calls"
        with self._lock:
            for name, n in ((pre + "pairs_routed", pairs_routed),
                            (pre + "experts_hit", experts_hit),
                            (pre + "max_expert_pairs", max_expert_pairs),
                            (pre + "pair_slots", pair_slots),
                            (calls, 1)):
                self._counters[name] = self._counters.get(name, 0) + int(n)

    def record_copy_on_restore_saved(self, nbytes: int) -> None:
        """A prefix restore mapped `nbytes` of committed pages into a
        sequence's page table: the bytes a restore by copy would have
        moved — the zero-copy-restore contract, measured."""
        with self._lock:
            self._counters["copy_on_restore_bytes_saved"] = \
                self._counters.get("copy_on_restore_bytes_saved", 0) + nbytes

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    # ------------------------------------------------------------- reporting
    def batch_occupancy(self) -> Optional[float]:
        """Mean fraction of bucket rows carrying real requests — the
        padding waste signal (1.0 = every executed row was real work)."""
        with self._lock:
            padded = self._counters.get("batch_rows_padded", 0)
            real = self._counters.get("batch_rows_real", 0)
        return real / padded if padded else None

    def compile_cache_hit_rate(self) -> Optional[float]:
        with self._lock:
            h = self._counters.get("compile_cache_hits", 0)
            m = self._counters.get("compile_cache_misses", 0)
        return h / (h + m) if (h + m) else None

    def prefill_padding_ratio(self) -> Optional[float]:
        """Executed prefill token slots per real prefill token (>= 1.0;
        1.0 = every executed slot carried a real token)."""
        with self._lock:
            padded = self._counters.get("prefill_tokens_padded", 0)
            real = self._counters.get("prefill_tokens_real", 0)
        return padded / real if real else None

    def prefix_cache_hit_rate(self) -> Optional[float]:
        """Fraction of submitted prompt tokens restored from the prefix
        trie instead of recomputed."""
        with self._lock:
            reused = self._counters.get("prefix_tokens_reused", 0)
            total = self._counters.get("prefix_tokens_total", 0)
        return reused / total if total else None

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            prompt_hist = dict(self._prompt_hist)
            hists = {"queue_wait": self.queue_wait.snapshot(),
                     "execute": self.execute.snapshot(),
                     "e2e": self.e2e.snapshot(),
                     "per_token": self.per_token.snapshot(),
                     "ttft": self.ttft.snapshot()}
        return {"replica_id": self.replica_id,
                "counters": counters, "gauges": gauges,
                "prompt_hist": prompt_hist,
                "latency": hists,
                "batch_occupancy": self.batch_occupancy(),
                "compile_cache_hit_rate": self.compile_cache_hit_rate(),
                "prefill_padding_ratio": self.prefill_padding_ratio(),
                "prefix_cache_hit_rate": self.prefix_cache_hit_rate()}

    def export(self, db=None, key: str = "serving",
               sub_key: Optional[str] = None, persist: bool = True):
        """Record the snapshot into the persistent PerfDB (the same store
        runtime profiling uses), appended to a bounded history list.  The
        default sub_key carries the replica label ("engine[r1]") so fleet
        replicas keep separate histories."""
        if db is None:
            from easydist_tpu.runtime.perfdb import PerfDB

            db = PerfDB()
        if sub_key is None:
            sub_key = (f"engine[{self.replica_id}]" if self.replica_id
                       else "engine")
        db.append_history(key, sub_key, self.snapshot())
        if persist:
            try:
                db.persist()
            except Exception:  # metrics export must never fail serving
                pass
        return db
