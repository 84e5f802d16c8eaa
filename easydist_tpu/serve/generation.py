"""Token-level decode serving: `GenerationSession`.

`ServeEngine` serves request-shaped functions — every call re-runs the
whole forward.  For autoregressive generation that is O(T^2) attention
flops per sequence; the KV cache makes each token O(T).  This module is
the serving half of the cache-carrying model API (models/decoder.py: one
`Decoder` record a model, the `chunk`/`verify`/`decode` steps, and the
`Paged`, `Latent` and `State` cache adapters):

  * **one paged KV pool** — a page-granular pool over a preallocated
    arena (kv/pool.py + kv/table.py on the host; kv/arena.py on the
    device: a pytree of one leaf per layer, each written in place in its
    own donated buffer).  Sequences of any length up to
    `max(ServeConfig.decode_buckets)` share ONE compiled decode step: the
    int32 page table, fixed [max_slots, max_pages], is the only per-step
    state that varies.  Admission reserves every page a sequence can ever
    touch up front, so the table row is static for the slot's life;
    decode always steps ALL slots, slots recycle through a free list;
    analyze rule KV001 audits the refcount/table bookkeeping at first
    decode and every retire;
  * **chunked, batched prefill** — each admitted prompt is processed in
    fixed [prefill_batch, prefill_chunk] windows written straight into
    arena pages through its table row, so ONE compiled prefill signature
    serves every prompt length, and up to `prefill_batch` pending prompts
    share each chunk call;
  * **prefix-reuse KV cache** — finished prefills commit their whole-chunk
    pages into a token trie (serve/prefix_cache.py) as page REFERENCES;
    admission maps the longest cached whole-chunk prefix into the new
    slot's table row (zero copies) and resumes prefill at `prefix_len`
    instead of 0.  Restored and recomputed KV are bitwise identical, so
    the cache is a pure latency optimization
    (`enable_prefix_cache=False` produces bitwise-identical outputs);
  * **bounded prefill pressure** — `step()` interleaves at most
    `prefill_chunks_per_step` chunk calls before the decode round runs,
    so a long prompt cannot stall in-flight decodes for its whole
    prefill (decode p99 stays bounded);
  * **donated cache** — the arena (leaf by leaf, with a model's state
    leaves beside it) is positional arg 0 and output 0 of every compiled
    step, so `infer_state_io` pairs and donates it; XLA updates in place
    instead of copying.  `analyze` rule SERVE001 audits exactly this for
    the decode and the chunk program.

Sharding rides the existing solver: the cache's heads axis is the
tensor-parallel shard dim, matching the attention strategy the solver
picks for the model itself, so tp serving works unchanged —
`kv_cache_specs` names the placement for callers that want to lay the
pool out explicitly.
"""

from __future__ import annotations

import collections
import logging
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from easydist_tpu.kv import (PagePool, PageTable, StatePool, is_host_ref,
                             is_page_ref)
from easydist_tpu.kv.tier import HostTier, TierError
from easydist_tpu.resilience import faultinject
from easydist_tpu.runtime import spans

from .admission import ReplicaDrainingError, RequestTooLargeError
from .batcher import select_bucket
from .engine import ServeConfig
from .metrics import RECURRENT_KINDS, ServeMetrics
from .prefix_cache import PrefixCache
from .speculate import NGramDrafter, accept_length

logger = logging.getLogger(__name__)


# process-level memo of compiled step functions, keyed by (model identity,
# mesh).  Every compiled callable below is pure — cache, params, and tokens
# all cross as arguments — so sessions over the same model/mesh can share
# the traced-and-XLA-compiled programs instead of each replica re-paying
# the compile.  This is the fleet case: N in-process replicas differ only
# in the state they carry, never in the program they run.
_COMPILED_MEMO: Dict[tuple, dict] = {}

# adaptive-speculation throttle: a verify row costs ~1.5x a decode row,
# so drafting pays off only while the stream's recent accepted-tokens-
# per-round stays above the break-even (~0.5).  Each request carries an
# EWMA of its accept counts; below the floor it stops drafting and only
# PROBES every _SPEC_PROBE_EVERY scheduling rounds, so a stream the
# drafter cannot predict decays to plain decode (one speculative probe
# per 12 rounds ~ the whole adversarial overhead) while a stream that
# turns predictable again is rediscovered within one probe interval.
_SPEC_EWMA_ALPHA = 0.3
_SPEC_EWMA_FLOOR = 0.5
_SPEC_PROBE_EVERY = 12
# full-batch verify economics: the verify program is k+1 positions wide
# for EVERY row, drafted or not, so a round beats a decode round only
# when the drafting rows' expected accepts cover the whole batch's share
# of the wider program: sum(ewma) > (cost - 1) * rows.  Rounds that
# close below that line pause speculation for a probe interval.
_SPEC_VERIFY_COST = 2.0


def kv_cache_specs(axis: str = "tp", cache=None):
    """PartitionSpec pytree for a KV cache: heads sharded on `axis`,
    everything else replicated — the placement consistent with a
    tensor-parallel attention strategy.  Without `cache`, the contiguous
    {"k", "v"} of shape [layers, batch/slots, heads, max_len, head_dim].
    With one (any adapter's pytree, arrays or shapes), a spec a leaf, its
    keys and ranks read off it: a contiguous leaf as above, an arena leaf
    [n_pages, heads, page_tokens, *] on its heads, and a leaf without a
    heads axis — a latent arena's [n_pages, page_tokens, width] — whole on
    every device of `axis`: latent attention has no heads to split, and is
    run data-parallel."""
    import jax
    from jax.sharding import PartitionSpec as P

    by_rank = {5: P(None, None, axis, None, None),
               4: P(None, axis, None, None), 3: P()}
    if cache is None:
        return {"k": by_rank[5], "v": by_rank[5]}
    return jax.tree.map(lambda leaf: by_rank[len(leaf.shape)], cache)


@dataclass
class _Slot:
    """Host-side view of one pooled decode row."""
    request_id: int
    future: Future
    pos: int                      # next cache write position
    token: int                    # last generated token (not yet in cache)
    max_new: int
    eos_id: Optional[int]
    generated: List[int] = field(default_factory=list)
    pinned: List[object] = field(default_factory=list)  # trie nodes held
    prompt: List[int] = field(default_factory=list)  # for evacuation
    timing: dict = field(default_factory=dict)  # see `_new_timing`


@dataclass
class _PrefillJob:
    """One prompt mid-prefill: owns a row of the chunk program and a
    reserved pool slot; `start` advances one chunk per batched chunk
    call."""
    request_id: int
    future: Future
    prompt: List[int]
    max_new: int
    eos_id: Optional[int]
    row: int                      # chunk-program row
    slot_idx: int                 # reserved pool slot
    start: int                    # next chunk start (multiple of chunk)
    prefix_nodes: List[object]    # trie nodes restored (pinned)
    timing: dict                  # see `_new_timing`


def _new_timing(prompt_len: int) -> dict:
    """A request's timeline, on the clock of `runtime/spans.py`
    (`time.perf_counter_ns`): stamped in place as the request moves, given
    back under the result's `timing` key and kept in the recorder's request
    ring.  `token_ns` has one stamp per generated id: the first is
    `first_token_ns`; the tokens of one decode round share the stamp taken
    when that round's readback returned."""
    return {"request_id": None, "prompt_len": prompt_len, "prefix_len": 0,
            "submit_ns": time.perf_counter_ns(), "admit_ns": None,
            "first_token_ns": None, "token_ns": [], "finish_ns": None,
            "finish_reason": None}


def _finish_timing(timing: dict, reason: str) -> dict:
    timing["finish_ns"] = time.perf_counter_ns()
    timing["finish_reason"] = reason
    spans.request(timing)
    return timing


def _ring_bytes(arena) -> int:
    """The bytes of the window layers' rings in a pool's pytree
    (`models/decoder.py::Ring`; 0 for a model without window layers)."""
    return sum(int(leaf.nbytes) for key in ("ring_k", "ring_v")
               for leaf in arena.get(key, ()))


class _PagedPool:
    """The session's single pool: one preallocated page arena, a
    refcounted page allocator, and a fixed [n_slots, max_pages] page
    table shared by every request regardless of length (`bucket` is the
    capacity cap — max(decode_buckets) — not a padding granularity).
    Prefill jobs write arena pages directly through the table; a
    restored prefix is table entries pointing at trie-committed pages
    (zero-copy)."""

    def __init__(self, bucket: int, n_slots: int, init_pages,
                 n_rows: int, chunk: int, prefix_bytes: int,
                 n_pages: int, host_tier_bytes: int = 0,
                 export_page: Optional[Callable] = None,
                 model_itemsize: int = 0,
                 init_state: Optional[Callable] = None):
        self.bucket = bucket
        self.n_slots = n_slots
        self.chunk = chunk                       # page_tokens
        self.max_pages = bucket // chunk
        if n_pages < self.max_pages:
            raise ValueError(
                f"kv_arena_pages {n_pages} cannot hold even one "
                f"full-length sequence ({self.max_pages} pages)")
        self.n_rows = n_rows
        # {"k": (one leaf per layer), "v": (...)[, "k_scale", "v_scale"]},
        # or {"latent": (...)} — kv/arena.py; every leaf is donated to each
        # compiled step
        pages = init_pages(n_pages, chunk)
        self.arena = pages
        # a model that keeps something a SEQUENCE (recurrent state, the
        # rings of window layers): it lives in the same donated pytree, a
        # slot per sequence (kv/state.py) — the slot a request is admitted
        # into is its table row AND its row of every such leaf.  Pages,
        # below, are of the layers that cache every position, and of those
        # alone.
        self.state: Optional[StatePool] = None
        self.ring_bytes = 0
        if init_state is not None:
            self.state = StatePool(n_slots)
            self.arena = {**pages, **init_state(n_slots)}
            self.ring_bytes = _ring_bytes(self.arena)
        # size pages from the arena's STORAGE leaves — quantized arenas
        # charge int8 payload + f32 scales, not the model dtype, which is
        # exactly the density win the kv_quant_bytes_saved gauge reports
        self.page_bytes = sum(int(leaf.nbytes) // n_pages
                              for leaves in pages.values()
                              for leaf in leaves)
        # what one page's payload (every key but the scales) would cost at
        # model precision — the baseline the quant-savings gauge subtracts
        # from
        payload_elems = sum(int(leaf.size) // n_pages
                            for key, leaves in pages.items()
                            if not key.endswith("_scale")
                            for leaf in leaves)
        self.model_page_bytes = payload_elems * model_itemsize \
            if model_itemsize else self.page_bytes
        self.pool = PagePool(n_pages, chunk, page_bytes=self.page_bytes)
        self.table = PageTable(n_slots, self.max_pages, n_pages)
        self.free: List[int] = list(range(n_slots))
        self.slots: Dict[int, _Slot] = {}
        self.free_rows: List[int] = list(range(n_rows))
        self.jobs: Dict[int, _PrefillJob] = {}
        self.trie: Optional[PrefixCache] = \
            PrefixCache(chunk, prefix_bytes,
                        on_evict=self._release_evicted) \
            if prefix_bytes else None
        # host tier (kv/tier.py): demotion target for cold trie pages;
        # `export_page(pool, pid)` is the session's compiled single-page
        # arena read (the same program fleet export uses)
        self.tier: Optional[HostTier] = \
            HostTier(host_tier_bytes) \
            if host_tier_bytes and self.trie is not None else None
        self._export_page = export_page
        self._tier_seq = 0
        # {program name: the CompileResult its last launch ran}: the pool's
        # shapes never change, so a step program resolves once and is
        # dispatched directly after (`GenerationSession._held_or_resolved`)
        self.held: Dict[str, object] = {}

    def _release_evicted(self, node) -> None:
        # trie eviction drops the trie's hold on the node's arena page;
        # the page only frees when no live slot still maps it.  A node
        # already demoted to the host tier owns no arena page — evicting
        # it just forgets the host copy.
        if is_host_ref(node.kv):
            if self.tier is not None:
                self.tier.drop(node.kv["host"])
            return
        self.pool.release(node.kv["page"])

    @property
    def n_active(self) -> int:
        return len(self.slots)

    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        """Worst-case pages one sequence touches: prefill writes
        ceil(prompt/chunk) whole pages, decode writes up to
        `max_new - 1` more positions, everything capped at the bucket
        (retirement fires at pos >= bucket)."""
        cap = min(self.bucket, prompt_len + max_new)
        return -(-cap // self.chunk)

    def make_room(self, n_pages: int) -> bool:
        """Free arena pages until `n_pages` are available, evicting
        unpinned trie nodes LRU-first (an eviction only yields a free
        page when no live slot shares it).  Returns availability.

        With a host tier configured, demotion runs FIRST: the coldest
        unpinned device-page node moves its bytes to host and keeps its
        trie position (the prefix survives HBM pressure).  Only when the
        tier refuses (paused after host_oom, budget exhausted, nothing
        demotable) does plain eviction run — and then only against
        device-page nodes, because evicting a host-ref node frees no
        arena page and would pointlessly discard tiered bytes."""
        if self.trie is not None:
            while self.pool.n_free < n_pages:
                if self.tier is not None:
                    if not self.tier.paused and self._demote_one():
                        continue
                    victim = self.trie.lru_node(
                        lambda n: not n.children and is_page_ref(n.kv))
                    if victim is None \
                            or not self.trie.evict_node(victim):
                        break
                elif not self.trie.evict_lru():
                    break
        return self.pool.n_free >= n_pages

    def _demote_one(self) -> bool:
        """Demote the LRU unpinned device-page trie node to the host
        tier: export the page's arrays, `tier.put` (chunked fetch +
        manifest), swap the node's kv to `{"host": key}` at 0 trie
        bytes, release the arena page.  Returns False when nothing is
        demotable or the tier refused the bytes (caller falls back to
        eviction)."""
        node = self.trie.lru_node(lambda n: is_page_ref(n.kv))
        if node is None or self._export_page is None:
            return False
        pid = node.kv["page"]
        key = ("pg", self._tier_seq)
        self._tier_seq += 1
        if not self.tier.put(key, self._export_page(self, pid)):
            return False
        self.trie.reaccount(node, 0, kv={"host": key})
        self.pool.release(pid)
        return True

    def occupancy(self):
        """(pages_in_use, real tokens held) for the kv gauges: slots
        hold `pos` cached tokens, jobs `start` (restored + prefilled so
        far), trie-only pages a whole chunk each; reserved-but-unwritten
        pages count capacity only — that gap IS the fragmentation the
        `kv_page_utilization` gauge measures."""
        tokens = sum(min(s.pos, self.bucket) for s in self.slots.values())
        tokens += sum(j.start for j in self.jobs.values())
        if self.trie is not None:
            mapped = set()
            for idx in self.slots:
                mapped.update(self.table.mapped(idx))
            for job in self.jobs.values():
                mapped.update(self.table.mapped(job.slot_idx))
            for node in self.trie._walk():
                pid = node.kv.get("page") \
                    if isinstance(node.kv, dict) else None
                if pid is not None and pid not in mapped:
                    mapped.add(pid)  # host-ref nodes hold no arena page
                    tokens += self.chunk
        return self.pool.in_use, tokens

    def take_slot(self) -> int:
        """A free slot: its table row and, for a model that keeps slots,
        its state row (zeroed by the first chunk, which starts fresh) and
        its rings (whose rows of an earlier tenant no position reaches)."""
        slot_idx = self.free.pop()
        if self.state is not None:
            self.state.take(slot_idx)
        return slot_idx

    def give_slot(self, slot_idx: int) -> None:
        """Retirement: the slot, its state row and its pages go back
        together (a page frees when no other row or trie node holds it)."""
        self.free.append(slot_idx)
        if self.state is not None:
            self.state.release(slot_idx)
        for pid in self.table.unmap_row(slot_idx):
            self.pool.release(pid)


# The small operands of a paged step program cross to the chip as ONE int32
# array, a row a slot (decode) or a prefill row (chunk), built fresh for
# every launch and handed to the dispatch as numpy: one transfer a launch,
# and no `device_put` of the session's own.  The builders and the cuts the
# programs make (`_columns`) agree on the columns:
#   decode   table row | token | pos (| 1 where the row is a sequence)
#   chunk    table row | start | length (| state slot) | the chunk's tokens
# with the bracketed column for a model that keeps state a sequence.
def _decode_operand(pool: _PagedPool, live: List[int]) -> np.ndarray:
    """Only the rows of `live` expose their table row: a reserved slot that
    is still prefilling holds pages (possibly SHARED prefix pages) that
    must not take the dead-row write a decode step lands at pos 0 —
    sentinel rows drop it instead."""
    k = pool.max_pages
    rows = np.zeros((pool.n_slots, k + 2 + (pool.state is not None)),
                    np.int32)
    rows[:, :k] = pool.pool.sentinel
    rows[live, :k] = pool.table.array[live]
    rows[live, k] = [pool.slots[idx].token for idx in live]
    rows[live, k + 1] = [pool.slots[idx].pos for idx in live]
    if pool.state is not None:
        rows[live, k + 2] = 1
    return rows


def _chunk_operand(pool: _PagedPool, pad_value: int) -> np.ndarray:
    """Idle rows keep an all-sentinel table row (and state slot), so their
    writes drop and their logits are garbage nobody reads."""
    k, c, state = pool.max_pages, pool.chunk, pool.state
    at = k + 2 + (state is not None)       # where the tokens start
    rows = np.full((pool.n_rows, at + c), pad_value, np.int32)
    rows[:, :k] = pool.pool.sentinel
    rows[:, k], rows[:, k + 1] = 0, 1
    if state is not None:
        rows[:, k + 2] = state.sentinel
    for row, job in pool.jobs.items():
        seg = job.prompt[job.start:job.start + c]
        rows[row, :k] = pool.table.array[job.slot_idx]
        rows[row, k], rows[row, k + 1] = job.start, len(job.prompt)
        if state is not None:
            rows[row, k + 2] = job.slot_idx
        rows[row, at:at + len(seg)] = seg
    return rows


def _columns(rows, fixed: int, tokens: int = 0):
    """What a program cuts its operand into: the table rows, the `fixed`
    single columns after them and, of a chunk's, the last `tokens` columns
    (a chunk fills one page, so the arena says how many)."""
    at = rows.shape[1] - tokens
    k = at - fixed
    cut = (rows[:, :k], *(rows[:, j] for j in range(k, at)))
    return (*cut, rows[:, at:]) if tokens else cut


class GenerationSession:
    """Continuous-batching token generation over a cache-carrying model.

    `model` is the model's `Decoder` record (models/decoder.py;
    `gpt.decoder(cfg)`, `llama.decoder(cfg)`): the session builds every
    program it runs from it — chunked prefill, decode and verify, against
    the page arena.

    Greedy decoding (argmax inside the compiled step, so only int32 token
    ids cross the host boundary per token).  `submit` returns a Future
    resolving to {"ids": [...generated ids...], "finish_reason":
    "eos"|"length"|"bucket_full", "timing": the request's timeline (see
    `_new_timing`)}; drive with `step()` (admit + bounded prefill chunks +
    decode + harvest, each an `easydist.serve.*` span of
    `runtime/spans.py`) or `run_until_drained()`.

    `compile_key` (any hashable; `for_gpt`/`for_llama` derive one from the
    model config) opts the session into the process-level compiled-program
    memo: replicas over the same model and mesh share traced/compiled step
    functions instead of each paying the compile — the callables are pure,
    so only host-side state is per-session.
    """

    def __init__(self, params, *, model,
                 drafter: Optional[object] = None,
                 config: Optional[ServeConfig] = None, mesh=None,
                 eos_id: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None,
                 replica_id: Optional[str] = None,
                 compile_key: Optional[object] = None):
        from easydist_tpu.jaxfront import easydist_compile
        from easydist_tpu.models.decoder import (Latent, Paged, chunk,
                                                 decode, verify)

        self.config = config or ServeConfig()
        self.replica_id = replica_id
        if model.max_positions is not None:
            bad = [b for b in self.config.decode_buckets
                   if b > model.max_positions]
            if bad:
                raise ValueError(
                    f"decode_buckets {bad} exceed the model's maximum "
                    f"sequence length {model.max_positions}; set "
                    f"ServeConfig(decode_buckets=...) within it")
        self._per_sequence = model.per_sequence
        self._refuse_unbuilt(model, self.config)
        self._model = model
        # the arena's adapter: K/V rows a KV head, or one latent row
        paged = self._paged_adapter = Latent if model.latent else Paged
        self.params = params
        self.mesh = mesh
        self.eos_id = eos_id
        self.metrics = metrics or ServeMetrics(replica_id=replica_id)
        self._draining = False
        self._closed = False
        self._pending: collections.deque = collections.deque()
        self._pools: Dict[int, _PagedPool] = {}
        self._next_request_id = 0
        self._step_index = 0
        # emptiness, on the recorder's clock (`submit`, `step`): since when
        # the session has had nothing live and nothing queued (None while
        # it has work), and how long it was empty since the last step
        self._empty_since_ns: Optional[int] = time.perf_counter_ns()
        self._empty_ns = 0
        self._audited: set = set()
        self._audited_prefill: set = set()
        self._audited_verify: set = set()

        # speculative decoding (serve/speculate.py): a drafter proposes k
        # tokens, one verify step scores all k+1 positions, the session
        # commits the longest self-validating prefix.  Pure speed knob —
        # committed tokens are exactly the plain-greedy stream.
        self._spec_k = int(self.config.speculate_k or 0)
        self._drafter = None
        if self._spec_k:
            if drafter is not None:
                self._drafter = drafter
            elif self.config.speculate_drafter == "ngram":
                self._drafter = NGramDrafter()
            else:
                raise ValueError(
                    "speculate_drafter='draft_model' needs an explicit "
                    "drafter: pass drafter=..., or draft_model="
                    "(params, cfg) to for_gpt/for_llama")
        # adaptive speculation (module constants above): per-request
        # accept-rate EWMA + probe counter.  Purely a scheduling knob —
        # which rounds verify never changes the committed tokens (the
        # accept rule is self-validating), so parity and crash-resume
        # stay bitwise.
        self._spec_ewma: Dict[int, float] = {}
        self._spec_idle: Dict[int, int] = {}
        self._spec_gate_idle = 0

        # the programs: arena first for donation pairing, the int32 page
        # table crosses as data every call (fixed shape — the signature
        # stays closed over arbitrary per-row lengths), in the step
        # programs as columns of their one operand (`_decode_operand`,
        # `_chunk_operand`).
        # Compiled lazily via `_paged_c`; export/import move single pages
        # for fleet handoff and the host tier.
        # The expert counters of a model whose `ffn` counts ride the token
        # readback (`out[rows:]`), so there is one readback still — with a
        # `State` or, as here, without one.
        def _ids(logits, kv):
            import jax.numpy as jnp

            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if not model.counts:
                return ids
            return jnp.concatenate([ids, kv.counters.astype(jnp.int32)])

        def _prefill_chunk_paged(arena, params, rows):
            table, start, lengths, tokens = _columns(
                rows, 2, paged.page_tokens(arena, model.head_dim))
            kv = paged(arena, table)
            arena, logits = chunk(model, kv, params, tokens, start, lengths)
            return arena, _ids(logits, kv)

        def _decode_paged(arena, params, rows):
            table, token, pos = _columns(rows, 2)
            kv = paged(arena, table)
            arena, logits = decode(model, kv, params, token, pos)
            return arena, _ids(logits, kv)

        # export/import iterate ALL arena keys: a quantized arena ships
        # its scale leaves alongside the int8 payload, so fleet manifests
        # (and host-tier manifests) cover both — a scale/payload desync
        # cannot pass a digest check.  Exact arenas have keys {"k","v"}.
        # The wire format is one page stacked over layers, {key: [layers,
        # heads, page_tokens, *]}, whatever the arena's layout on the
        # device (kv/arena.py).
        def _page_export(arena, page):
            from easydist_tpu.kv.arena import export_page

            return export_page(arena, page, model.head_dim)

        def _page_import(arena, chunk_kv, page):
            from easydist_tpu.kv.arena import import_page

            return import_page(arena, chunk_kv, page)

        # speculative verify: tokens is [slots, k+1] (committed token then
        # k drafts), the program writes K/V at all k+1 positions and
        # returns the greedy pick at EVERY position — the commit walk
        # happens on the host over int32 ids only
        def _verify_paged(arena, params, table, tokens, pos):
            import jax.numpy as jnp

            arena, logits = verify(model, paged(arena, table), params,
                                   tokens, pos)
            return arena, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # a model with state layers: the donated pytree is {arena leaves of
        # the attention layers, state leaves of the state layers}; `slots`
        # names each chunk row's state slot, `live` the decode rows that
        # are sequences.
        def _prefill_chunk_paged_state(cache, params, rows):
            from easydist_tpu.models.decoder import State

            pages, leaves = State.split(model, cache)
            table, start, lengths, slots, tokens = _columns(
                rows, 3, Paged.page_tokens(pages, model.head_dim))
            n_slots = next(iter(leaves.values()))[0].shape[0]
            st = State(leaves, slots < n_slots, slots, fresh=start == 0)
            kv = Paged(pages, table)
            cache, logits = chunk(model, kv, params, tokens, start, lengths,
                                  state=st)
            return cache, _ids(logits, kv)

        def _decode_paged_state(cache, params, rows):
            from easydist_tpu.models.decoder import State

            pages, leaves = State.split(model, cache)
            table, token, pos, live = _columns(rows, 3)
            kv = Paged(pages, table)
            cache, logits = decode(model, kv, params, token, pos,
                                   state=State(leaves, live != 0))
            return cache, _ids(logits, kv)

        self._paged_defs = {
            "chunk": _prefill_chunk_paged, "decode": _decode_paged,
            "export": _page_export, "import": _page_import,
            "verify": _verify_paged}
        if self._per_sequence:
            self._paged_defs.update(chunk_state=_prefill_chunk_paged_state,
                                    decode_state=_decode_paged_state)
        # the keys of the two step programs `step()` launches
        suffix = "_state" if self._per_sequence else ""
        self._chunk_program = "chunk" + suffix
        self._decode_program = "decode" + suffix

        # the arena is arg 0 and output 0 of every mutating compiled
        # callable, so state_io="auto" pairs it and XLA gets the buffer
        # donated; `_page_export`'s output is page-shaped (no pairing, no
        # donation — it must not invalidate the arena it reads).
        # `mesh=None` means "the global mesh at first call", which is
        # sticky process state that can change between sessions — resolve
        # it NOW so every program this session runs (and every session
        # sharing this memo entry) is compiled against the same mesh.
        # Unresolvable (no global installed yet) skips the memo: the
        # session compiles privately under whatever ambient its first
        # call sees, exactly the pre-memo behavior.
        if mesh is None:
            from easydist_tpu.jaxfront.mesh import get_device_mesh

            mesh = get_device_mesh()
            self.mesh = mesh  # `_paged_c` compiles against it
        memo_key = (compile_key, mesh) \
            if compile_key is not None and mesh is not None else None
        # {program name: its CompiledFunction}, filled by `_paged_c`
        shared = _COMPILED_MEMO.get(memo_key) if memo_key else None
        if shared is None:
            shared = {}
            if memo_key:
                while len(_COMPILED_MEMO) >= 32:  # live sessions keep refs
                    _COMPILED_MEMO.pop(next(iter(_COMPILED_MEMO)))
                _COMPILED_MEMO[memo_key] = shared
        self._paged_cs: Dict[str, Callable] = shared

    @staticmethod
    def _refuse_unbuilt(model, cfg: ServeConfig) -> None:
        """A state layer caches one state a sequence, a window layer a ring
        of its last positions, a latent layer one row a position without
        heads: what assumes K/V rows a head for every position is refused
        here, loudly, until it is built.  One row a feature: (asked for, its
        name, the setting that drops it, why it cannot be with state layers,
        with window rings, with latent attention — None where it can)."""
        refusals = (
            (cfg.kv_host_tier_bytes, "the host tier",
             "kv_host_tier_bytes=0",
             "it demotes trie pages, and the trie is refused too",
             "it demotes trie pages, and the trie is refused too", None),
            (cfg.enable_prefix_cache and cfg.prefix_cache_bytes,
             "the prefix trie", "enable_prefix_cache=False",
             "a restored prefix needs the state as it was at that chunk's "
             "boundary, and no snapshot is kept",
             "a restored or resumed prefix needs the ring as it was at "
             "that chunk's boundary, and no snapshot is kept", None),
            (cfg.speculate_k, "speculation", "speculate_k=0",
             "a rejected draft cannot be masked out of a recurrent state, "
             "and there is no roll-back",
             "a rejected draft has overwritten ring rows of positions "
             "still inside the window, and there is no roll-back", None),
            (cfg.kv_quant_dtype not in (None, "none"), "the int8 arena",
             "kv_quant_dtype='none'",
             "not measured against a model whose logits also ride a "
             "float32 state",
             "the rings are kept exact, and a model whose layers read "
             "int8 and exact keys side by side is not measured",
             "the block scales are laid out a head, a latent row has "
             "none, and int8 latents are not built"),
        )
        for prop, has, why in (("state layers", model.state_layers, 3),
                               ("window rings", model.ring_windows, 4),
                               ("latent attention", model.latent, 5)):
            for row in refusals if has else ():
                if row[0] and row[why]:
                    raise ValueError(
                        f"a model with {prop} cannot be served with "
                        f"{row[1]}: {row[why]}; set {row[2]}")

    def _paged_c(self, name: str) -> Callable:
        """Compiled program (a key of `_paged_defs`), built on first use
        and shared through the process memo."""
        fn = self._paged_cs.get(name)
        if fn is None:
            from easydist_tpu.jaxfront import easydist_compile

            fn = easydist_compile(self._paged_defs[name], mesh=self.mesh)
            self._paged_cs[name] = fn
        return fn

    # ------------------------------------------------------------ admission
    def submit(self, prompt_ids: Sequence[int],
               max_new_tokens: int = 16,
               eos_id: Optional[int] = None) -> Future:
        """Queue one prompt; generation interleaves with every other live
        request (continuous batching) as `step()` is driven."""
        with spans.span("easydist.serve.submit") as sp:
            if self._draining or self._closed:
                who = f" {self.replica_id}" if self.replica_id else ""
                raise ReplicaDrainingError(
                    f"session{who} is "
                    f"{'closed' if self._closed else 'draining'}: in-flight "
                    f"work retires but nothing new is admitted")
            prompt = [int(t) for t in prompt_ids]
            sp.set(prompt_len=len(prompt))
            if not prompt:
                raise ValueError("empty prompt")
            if max_new_tokens < 1:
                raise ValueError(f"max_new_tokens must be >= 1, "
                                 f"got {max_new_tokens}")
            if select_bucket(len(prompt) + 1,
                             self.config.decode_buckets) is None:
                raise RequestTooLargeError(
                    f"prompt of {len(prompt)} tokens does not fit any "
                    f"decode bucket {self.config.decode_buckets} with room "
                    f"to generate")
            fut = Future()
            timing = _new_timing(len(prompt))
            self._pending.append(
                (prompt, max_new_tokens,
                 self.eos_id if eos_id is None else eos_id, fut, timing))
            if self._empty_since_ns is not None:    # the emptiness ends here
                spans.record_span("easydist.serve.empty",
                                  self._empty_since_ns, timing["submit_ns"])
                self._empty_ns += timing["submit_ns"] - self._empty_since_ns
                self._empty_since_ns = None
            self.metrics.inc("requests_submitted")
            self.metrics.set_gauge("queue_depth", self.queue_depth)
            return fut

    @property
    def queue_depth(self) -> int:
        """Live requests this session owns: queued + prefilling + decoding
        (the fleet router's occupancy signal)."""
        return len(self._pending) + sum(
            len(p.jobs) + p.n_active for p in self._pools.values())

    # ------------------------------------------------------------- plumbing
    def _pool_for(self, bucket: int) -> _PagedPool:
        """The one pool, built on first use.  Whatever bucket a prompt
        selects, its key is the capacity cap: lengths are a page-table
        concern, not a compile-signature concern, so there is nothing to
        bucket by."""
        cfg = self.config
        bucket = max(cfg.decode_buckets)
        pool = self._pools.get(bucket)
        if pool is None:
            chunk = cfg.kv_page_tokens or min(cfg.prefill_chunk, bucket)
            max_pages = bucket // chunk
            n_pages = cfg.kv_arena_pages or \
                (cfg.max_decode_slots + 1) * max_pages
            pool = self._pools[bucket] = _PagedPool(
                bucket, cfg.max_decode_slots, self._pages_factory,
                n_rows=cfg.prefill_batch, chunk=chunk,
                prefix_bytes=(cfg.prefix_cache_bytes
                              if cfg.enable_prefix_cache else 0),
                n_pages=n_pages,
                host_tier_bytes=cfg.kv_host_tier_bytes,
                export_page=self._export_arena_page,
                model_itemsize=self._model_itemsize(),
                init_state=self._state_factory
                if self._per_sequence else None)
        return pool

    def _pages_factory(self, n_pages: int, page_tokens: int):
        cfg = self.config
        quant = () if self._model.latent else (cfg.kv_quant_dtype,
                                               cfg.kv_quant_block)
        return self._born(self._paged_adapter.init(
            self._model, n_pages, page_tokens, cfg.kv_cache_dtype, *quant))

    def _state_factory(self, n_slots: int):
        from easydist_tpu.models.decoder import State

        return self._born(State.init(self._model, n_slots,
                                     self.config.kv_cache_dtype))

    def _born(self, state):
        """A pool's leaves as the factories hand them to the pool: put
        ONCE, here, in the sharding this session's programs declare for
        them and hand them back in (`jaxfront/mesh.py::put_on_mesh`), so
        that a program's first call sees the pool every later call sees
        and XLA compiles it once.  Left as `jnp.zeros` makes them, on one
        device and uncommitted, the first call compiles for that and the
        second again for the pool the first gave back."""
        from easydist_tpu.jaxfront.mesh import put_on_mesh

        return put_on_mesh(state, self.mesh)

    def _model_itemsize(self) -> int:
        """Bytes per element at model precision (first param leaf) — the
        baseline kv_quant_bytes_saved subtracts the arena's actual
        storage cost from."""
        import jax

        leaves = jax.tree_util.tree_leaves(self.params)
        return int(np.dtype(leaves[0].dtype).itemsize) if leaves else 0

    def _export_arena_page(self, pool, pid: int):
        """Compiled single-page arena read (the fleet-export program) —
        the host tier's demotion source."""
        import jax.numpy as jnp

        return self._paged_c("export")(pool.arena,
                                       jnp.asarray(int(pid), jnp.int32))

    def _admitted(self, timing: dict, prefix_len: int) -> int:
        """A request left the queue: stamp it, feed `queue_wait`, and
        give it its id."""
        timing["admit_ns"] = time.perf_counter_ns()
        timing["prefix_len"] = prefix_len
        timing["request_id"] = self._next_request_id
        self._next_request_id += 1
        self.metrics.observe(
            "queue_wait", (timing["admit_ns"] - timing["submit_ns"]) / 1e9)
        return timing["request_id"]

    def _first_token(self, timing: dict) -> None:
        now = time.perf_counter_ns()
        timing["first_token_ns"] = now
        timing["token_ns"].append(now)
        self.metrics.observe("ttft", (now - timing["submit_ns"]) / 1e9)

    def _resolve(self, pool: _PagedPool, program: str, args):
        """A paged step program's `CompileResult` for `args`, looked up by
        signature (compiled where there is none yet) and from now on held by
        the pool: its first launch, or one whose held result refused the
        operands' shapes."""
        result = pool.held[program] = \
            self._paged_c(program).get_compiled(*args)
        spans.count("serve_launches", fn=result.name, path="resolved")
        return result

    def _held_or_resolved(self, pool: _PagedPool, program: str, args):
        """(the result to launch, `_run`'s `held`): what the pool holds, with
        no flatten of the parameters and no signature, else `_resolve`'s."""
        result = pool.held.get(program)
        if result is not None:
            return result, (pool, program)
        return self._resolve(pool, program, args), None

    def _run(self, span_name: str, result, args, held=None, **attrs):
        """One compiled program dispatched and its int32 readback awaited,
        inside a `.call` span: (new state, readback, the closed span).
        The readback's copy to the host is asked for as soon as the program
        is enqueued, so it follows the program and is not a round trip after
        it; `ready_ns` splits the wait at `block_until_ready`'s return,
        before the result is copied out.  `h2d` counts the host arrays the
        dispatch itself carries to the device.  `held` is the (pool,
        program) whose held `result` this is: no flatten of the parameters
        and no signature found it, and should it meet other shapes it raises
        `SignatureMismatch` while it traces, before anything runs, and is
        resolved again."""
        import jax

        from easydist_tpu.jaxfront.api import SignatureMismatch

        with spans.span(span_name, fn=result.name,
                        h2d=sum(isinstance(a, np.ndarray) for a in args),
                        **attrs) as sp:
            try:
                state, out = result.dispatch(args, {})
            except SignatureMismatch:
                if held is None:
                    raise
                result, held = self._resolve(*held, args), None
                state, out = result.dispatch(args, {})
            out.copy_to_host_async()
            out = jax.block_until_ready(out)
            sp.set(ready_ns=time.perf_counter_ns())
            out = np.asarray(out)
        if held is not None:
            spans.count("serve_launches", fn=result.name, path="held")
        return state, out, sp

    def _admit_one(self) -> bool:
        """Pop one pending request toward generation: reserve a pool slot,
        a chunk-program row and EVERY page the sequence can ever touch up
        front (decode crossing a page boundary must find the page already
        mapped — a sentinel there silently drops the token's K/V), map the
        trie's committed prefix pages into the slot's table row, and
        enqueue a prefill job (chunks run in `step()`).  Returns False
        when nothing is admissible or the arena cannot make room (the
        request stays queued)."""
        if not self._pending:
            return False
        prompt, max_new, eos, fut, timing = self._pending[0]
        bucket = select_bucket(len(prompt) + 1, self.config.decode_buckets)
        pool = self._pool_for(bucket)
        if not pool.free or not pool.free_rows:
            return False
        prefix_len, nodes = 0, []
        if pool.trie is not None:
            # cap below len(prompt): at least one real token must run
            # through prefill so the finishing chunk produces logits
            prefix_len, nodes = pool.trie.match(
                prompt, max_tokens=len(prompt) - 1)
            pool.trie.pin(nodes)  # survive make_room's evictions
            if pool.tier is not None:
                # BEFORE the slot's first decode step: demoted nodes on
                # the matched path come back into arena pages (manifest
                # verified); a tier miss truncates the usable prefix
                nodes, prefix_len = self._promote_path(pool, nodes)
        n_need = pool.pages_needed(len(prompt), max_new)
        if not pool.make_room(n_need - len(nodes)):
            if pool.trie is not None:
                pool.trie.unpin(nodes)
            return False
        self._pending.popleft()
        if fut.set_running_or_notify_cancel() is False:
            if pool.trie is not None:
                pool.trie.unpin(nodes)
            return True  # cancelled while queued; nothing reserved yet
        slot_idx = pool.take_slot()
        row = pool.free_rows.pop()
        # zero-copy restore: the slot's leading windows point at the
        # trie's pages (shared, read-only by construction — writes only
        # land past the prefix)
        for j, node in enumerate(nodes):
            pid = node.kv["page"]
            pool.pool.share(pid)
            pool.table.map(slot_idx, j, pid)
        for j in range(len(nodes), n_need):
            pool.table.map(slot_idx, j, pool.pool.alloc())
        if nodes:
            self.metrics.record_copy_on_restore_saved(
                len(nodes) * pool.page_bytes)
        self.metrics.record_admission(len(prompt), prefix_len)
        pool.jobs[row] = _PrefillJob(
            request_id=self._admitted(timing, prefix_len), future=fut,
            prompt=prompt, max_new=max_new, eos_id=eos, row=row,
            slot_idx=slot_idx, start=prefix_len, prefix_nodes=nodes,
            timing=timing)
        return True

    def _promote_path(self, pool: _PagedPool, nodes):
        """Promote host-tier refs along a matched (pinned) path back
        into arena pages: `tier.get` manifest-verifies the host bytes,
        the compiled import program uploads them into a fresh page, and
        the node's kv swaps back to `{"page": id}` at full byte cost.
        The round trip moves exact storage bytes (payload AND scales),
        so it is bitwise.  A missing/corrupt entry truncates the usable
        prefix at that node — the tail unpins and prefill recomputes it
        (never serves unverified KV).  Returns (nodes, prefix_len)."""
        import jax.numpy as jnp

        for j, node in enumerate(nodes):
            if is_page_ref(node.kv):
                continue
            key = node.kv["host"]
            try:
                host_kv = pool.tier.get(key)
            except (KeyError, TierError) as e:
                logger.warning("[kv.tier] promotion of %r failed (%s); "
                               "prefix truncated, chunk recomputes", key, e)
                host_kv = None
            if host_kv is None or not pool.make_room(1):
                pool.trie.unpin(nodes[j:])
                return nodes[:j], j * pool.chunk
            pid = pool.pool.alloc()
            pool.arena = self._paged_c("import")(
                pool.arena,
                {k: jnp.asarray(v) for k, v in host_kv.items()},
                jnp.asarray(pid, jnp.int32))
            pool.tier.drop(key)
            pool.trie.reaccount(node, pool.page_bytes, kv={"page": pid})
        return nodes, len(nodes) * pool.chunk

    # ----------------------------------------------------- chunked prefill
    def _advance_jobs(self, pool: _PagedPool, first) -> None:
        """After a chunk call: every job moves one chunk on; the jobs whose
        last chunk this was are finished, each in its own span."""
        for row in list(pool.jobs):
            job = pool.jobs[row]
            job.start += pool.chunk
            if job.start >= len(job.prompt):
                with spans.span("easydist.serve.prefill.finish",
                                request_id=job.request_id):
                    self._finish_prefill(pool, row, int(first[row]))

    def _prefill_round(self, pool: _PagedPool, max_chunks: int) -> int:
        """Run up to `max_chunks` batched chunk calls over `pool`'s jobs:
        each chunk writes straight into the arena through the job's table
        row; finished jobs commit to the trie and free their row.  Idle
        rows get an all-sentinel table row so their writes drop and their
        logits are garbage nobody reads — one compiled signature
        regardless of which rows are live.  Returns the number of chunk
        calls executed."""
        calls = 0
        c_len = pool.chunk
        while pool.jobs and calls < max_chunks:
            with spans.span("easydist.serve.prefill.build"):
                args = (pool.arena, self.params,
                        _chunk_operand(pool, int(self.config.pad_value)))
                result, held = self._held_or_resolved(
                    pool, self._chunk_program, args)
                if pool.bucket not in self._audited_prefill:
                    self._audited_prefill.add(pool.bucket)
                    # the chunk's writes go through the table, audited
                    # host-side by KV001; of the program the donation
                    # is audited
                    try:
                        from easydist_tpu.analyze import \
                            check_decode_donation

                        check_decode_donation(
                            result,
                            node=f"prefill_chunk_paged[cap={pool.bucket}]")
                    except ImportError:
                        pass
            pool.arena, first, sp = self._run(
                "easydist.serve.prefill.call", result, args, held,
                rows=pool.n_rows)
            # a chunk fills one page: a live row's extent ends with it; its
            # n real positions start .. start + n - 1 see start + 1 ..
            # start + n keys
            real = [(job.start, min(c_len, len(job.prompt) - job.start))
                    for job in pool.jobs.values()]
            self.metrics.record_prefill_chunk(
                pool.n_rows, c_len, sp.seconds,
                pages_walked=sum(job.start // c_len + 1
                                 for job in pool.jobs.values()),
                pages_bucket=len(pool.jobs) * pool.max_pages,
                attn_pairs=sum(n * start + n * (n + 1) // 2
                               for start, n in real),
                scan_positions={
                    kind: sum(n for _, n in real) * len(pool.arena[kind])
                    for kind in RECURRENT_KINDS if kind in pool.arena})
            if len(first) > pool.n_rows:   # the call's expert counters
                self.metrics.record_moe(
                    "prefill", *first[pool.n_rows:],
                    pair_slots=pool.n_rows * c_len * self._model.pair_slots)
            calls += 1
            self._advance_jobs(pool, first)
        return calls

    def _finish_prefill(self, pool: _PagedPool, row: int,
                        first_token: int) -> None:
        """One job's last chunk ran: commit its whole-chunk pages
        into the trie as page REFERENCES (share + {"page": id} — no
        extraction copy), free the row, open the decode slot."""
        job = pool.jobs.pop(row)
        pinned = list(job.prefix_nodes)
        if pool.trie is not None:
            nodes = list(job.prefix_nodes)
            for j in range(len(nodes), len(job.prompt) // pool.chunk):
                chunk_toks = job.prompt[j * pool.chunk:
                                        (j + 1) * pool.chunk]
                node = pool.trie.lookup_node(nodes, chunk_toks)
                if node is not None and is_host_ref(node.kv):
                    # heal: this prefill just rewrote the chunk's bytes
                    # into a fresh page, so re-point the demoted node at
                    # it (free re-promotion; also recovers nodes whose
                    # tier entry was lost to host LRU eviction)
                    pid = int(pool.table.array[job.slot_idx, j])
                    pool.pool.share(pid)
                    if pool.tier is not None:
                        pool.tier.drop(node.kv["host"])
                    pool.trie.reaccount(node, pool.page_bytes,
                                        kv={"page": pid})
                if node is None:
                    pid = int(pool.table.array[job.slot_idx, j])
                    pool.pool.share(pid)       # the trie's hold
                    node = pool.trie.commit(nodes, chunk_toks,
                                            {"page": pid},
                                            nbytes=pool.page_bytes)
                    if node is None:
                        pool.pool.release(pid)  # budget refused it
                if node is None:
                    break  # byte budget exhausted; partial path is fine
                nodes.append(node)
            pool.trie.unpin(job.prefix_nodes)
            pool.trie.pin(nodes)
            pinned = nodes
            self._audit_prefix_cache(pool)
        pool.free_rows.append(row)
        self._first_token(job.timing)

        slot = _Slot(request_id=job.request_id, future=job.future,
                     pos=len(job.prompt), token=first_token,
                     max_new=job.max_new, eos_id=job.eos_id,
                     pinned=pinned, prompt=job.prompt, timing=job.timing)
        slot.generated.append(slot.token)
        pool.slots[job.slot_idx] = slot
        self._maybe_retire(pool, job.slot_idx)

    # ------------------------------------------------------------- decoding
    def _retire(self, pool: _PagedPool, slot_idx: int, reason: str) -> None:
        slot = pool.slots.pop(slot_idx)
        with spans.span("easydist.serve.retire", reason=reason,
                        request_id=slot.request_id):
            if self._drafter is not None:
                self._drafter.forget(slot.request_id)
                self._spec_ewma.pop(slot.request_id, None)
                self._spec_idle.pop(slot.request_id, None)
            pool.give_slot(slot_idx)
            if pool.trie is not None and slot.pinned:
                pool.trie.unpin(slot.pinned)
            self._audit_kv(pool, f"retire[{reason}]")
            slot.future.set_result({"ids": list(slot.generated),
                                    "finish_reason": reason,
                                    "timing": _finish_timing(slot.timing,
                                                             reason)})
            self.metrics.inc("requests_completed")

    def _maybe_retire(self, pool: _PagedPool, slot_idx: int) -> bool:
        slot = pool.slots[slot_idx]
        if slot.eos_id is not None and slot.token == slot.eos_id:
            self._retire(pool, slot_idx, "eos")
        elif len(slot.generated) >= slot.max_new:
            self._retire(pool, slot_idx, "length")
        elif slot.pos >= pool.bucket:
            self._retire(pool, slot_idx, "bucket_full")
        else:
            return False
        return True

    def _decode_round(self, pool: _PagedPool,
                      only: Optional[set] = None) -> None:
        """One compiled decode step over ALL slots of `pool` (fixed
        shapes: the signature cache stays at ONE entry, whose only
        per-step variation is page-table DATA).

        `only` restricts the round to the given slot indices (excluded
        rows keep a sentinel table row so their dead-row write drops).
        The speculative scheduler uses it to plain-decode the slots a
        verify round could not carry."""
        with spans.span("easydist.serve.decode.build"):
            live = [i for i in pool.slots if only is None or i in only]
            # the positions this round attends, its own included
            attended = sum(pool.slots[idx].pos + 1 for idx in live)
            # ... and the K/V pages under them, which the paged kernel
            # walks in EACH full-attention layer, of the pages the
            # pool's rows could hold
            pages = dict(
                pages_walked=sum(pool.slots[idx].pos // pool.chunk + 1
                                 for idx in live),
                pages_bucket=pool.n_slots * pool.max_pages)
            args = (pool.arena, self.params, _decode_operand(pool, live))
            result, held = self._held_or_resolved(
                pool, self._decode_program, args)
            if pool.bucket not in self._audited:
                self._audited.add(pool.bucket)
                self._audit_donation(result, pool.bucket)
                self._audit_host_aliases(pool)
                self._audit_kv(pool, "first_decode")
                if "k_scale" in pool.arena:
                    self._audit_quant_program(result, "first_decode")
        pool.arena, nxt, sp = self._run("easydist.serve.decode.call", result,
                                        args, held, rows=len(live))
        with spans.span("easydist.serve.decode.harvest"):
            for idx in live:
                slot = pool.slots[idx]
                slot.token = int(nxt[idx])
                slot.pos += 1
                slot.generated.append(slot.token)
                # the round's one stamp: when its readback returned
                slot.timing["token_ns"].append(sp.t1_ns)
                self._maybe_retire(pool, idx)
            self.metrics.record_decode_step(len(live), pool.n_slots,
                                            sp.seconds, **pages)
            self.metrics.set_gauge("kv_tokens_live", attended)
            if len(nxt) > pool.n_slots:   # the round's expert counters
                self.metrics.record_moe(
                    "decode", *nxt[pool.n_slots:],
                    pair_slots=pool.n_slots * self._model.pair_slots)
            self._record_kv_pool(pool, len(live))

    def _record_kv_pool(self, pool: _PagedPool, live_rows: int = 0) -> None:
        in_use, held = pool.occupancy()
        self.metrics.record_kv_pool(
            in_use, held, pool.chunk,
            quant_bytes_saved=(pool.model_page_bytes
                               - pool.page_bytes) * in_use)
        if pool.state is not None:
            self.metrics.record_state_pool(pool.state.in_use,
                                           pool.state.n_slots)
        if pool.ring_bytes:
            # read off the leaves the last program handed back: a window
            # layer's bytes do not grow with its sequences
            self.metrics.record_window_rings(pool.state.in_use,
                                             _ring_bytes(pool.arena))
        if "latent" in pool.arena:
            self.metrics.record_latent_cache(
                sum(int(leaf.nbytes) for leaf in pool.arena["latent"]))
        # a recurrent layer's states of either kind: a leaf a layer, a
        # matrix a slot (a head), each live row's updated in place by the
        # round
        for kind in RECURRENT_KINDS:
            leaves = pool.arena.get(kind, ())
            if leaves:
                self.metrics.record_layer_states(
                    kind, sum(int(leaf.nbytes) for leaf in leaves),
                    rows_updated=live_rows * len(leaves))

    # ------------------------------------------------ speculative decoding
    def _spec_round(self, pool: _PagedPool) -> bool:
        """One speculative draft/verify round over `pool`
        (serve/speculate.py describes the accept rule).  Returns False
        when no slot can ride a verify step this round — the caller
        falls back to a plain decode round, so speculation never stalls
        decode.

        The choice is per slot: sentinel table rows drop excluded rows'
        writes, so eligible slots (draft + headroom + speculative spill
        windows mappable) verify while the rest take a plain decode call
        (`_decode_round(only=...)`)."""
        k = self._spec_k
        if self._spec_gate_idle > 0:
            # pacing after a round that closed below the full-batch
            # break-even (_commit_verify) — plain decode rounds until
            # the next attempt, which doubles as the refresh probe
            self._spec_gate_idle -= 1
            return False
        drafts: Dict[int, List[int]] = {}
        for idx, slot in pool.slots.items():
            rid = slot.request_id
            ewma = self._spec_ewma.get(rid)
            if ewma is not None and ewma < _SPEC_EWMA_FLOOR:
                # throttled: recent acceptance below break-even; only
                # probe once per interval to re-detect predictability
                idle = self._spec_idle.get(rid, 0) + 1
                if idle < _SPEC_PROBE_EVERY:
                    self._spec_idle[rid] = idle
                    continue
            self._spec_idle[rid] = 0
            d = self._drafter.propose(
                slot.request_id, slot.prompt + slot.generated, k)
            if d:
                drafts[idx] = (list(int(t) for t in d) + [0] * k)[:k]
        if not drafts:
            return False
        return self._verify_round(pool, drafts)

    def _verify_round(self, pool: _PagedPool, drafts) -> bool:
        import jax.numpy as jnp

        k = self._spec_k
        with spans.span("easydist.serve.decode.build"):
            eligible: Dict[int, List[int]] = {}
            for idx, d in drafts.items():
                slot = pool.slots[idx]
                if slot.pos + k + 1 > pool.bucket:
                    continue
                # speculative rows may spill past the slot's up-front page
                # reservation; map the spill windows now (the rollback
                # below unconditionally truncates the row back to the
                # reservation, so outside a verify round the invariant
                # "live slots map exactly their reservation" always holds)
                n_need = (slot.pos + k) // pool.chunk + 1
                n_have = pool.table.n_mapped(idx)
                if n_need > n_have:
                    if not pool.make_room(n_need - n_have):
                        continue
                    for j in range(n_have, n_need):
                        pool.table.map(idx, j, pool.pool.alloc())
                eligible[idx] = d
            if not eligible:
                return False
            tokens = np.zeros((pool.n_slots, k + 1), np.int32)
            pos = np.zeros((pool.n_slots,), np.int32)
            tbl = np.full((pool.n_slots, pool.max_pages),
                          pool.pool.sentinel, np.int32)
            for idx, d in eligible.items():
                slot = pool.slots[idx]
                tokens[idx, 0] = slot.token
                tokens[idx, 1:] = d
                pos[idx] = slot.pos
                tbl[idx] = pool.table.array[idx]
            args = (pool.arena, self.params, jnp.asarray(tbl),
                    jnp.asarray(tokens), jnp.asarray(pos))
            result = self._paged_c("verify").get_compiled(*args)
            if pool.bucket not in self._audited_verify:
                self._audited_verify.add(pool.bucket)
                self._audit_verify(result,
                                   f"verify[paged cap={pool.bucket}]")
        pool.arena, nxt, sp = self._run("easydist.serve.decode.call",
                                        result, args, rows=len(eligible))
        with spans.span("easydist.serve.decode.harvest"):
            # reservation sizes BEFORE the commit walk can retire the slots
            reserved = {idx: pool.pages_needed(len(pool.slots[idx].prompt),
                                               pool.slots[idx].max_new)
                        for idx in eligible}
            rest = [i for i in pool.slots if i not in eligible]
            proposed, accepted, committed = self._commit_verify(
                pool, drafts, tokens, nxt, list(eligible), sp.t1_ns)
            # rollback: spill windows past the reservation only ever hold
            # rejected/uncommitted draft rows (committed positions provably
            # fit the reservation — pages_needed covers prompt + max_new),
            # so truncating the table tail releases them.  Retired slots
            # were already fully unmapped by _retire.
            released = 0
            for idx in eligible:
                if idx not in pool.slots:
                    continue
                for pid in pool.table.unmap_tail(idx, reserved[idx]):
                    pool.pool.release(pid)
                    released += 1
            if released:
                self._audit_spec_rollback(pool)
            self.metrics.record_speculation(
                proposed, accepted, committed, len(eligible), pool.n_slots,
                sp.seconds, pages_released=released)
            self._record_kv_pool(pool)
        if rest:
            self._decode_round(pool, only=set(rest))
        return True

    def _commit_verify(self, pool, drafts, tokens, nxt, idxs, t_tok: int):
        """Commit walk for the slots that rode a verify step: accept the
        longest draft prefix the target's own greedy picks ratify, plus
        the target's correction/bonus token.  Every committed token is
        the exact plain-greedy token (the draft row only decides how
        many commit per round), so retire semantics (eos/length/
        bucket_full) are checked token-by-token exactly as a sequence
        of plain decode rounds would.  `t_tok` (when the round's readback
        returned) stamps every token the round commits.  Returns
        (proposed, accepted, committed) counts for the speculation
        metrics."""
        k = self._spec_k
        proposed = accepted = committed = 0
        expect = 0.0
        for idx in idxs:
            d_row = tokens[idx, 1:]
            g_row = nxt[idx]
            # the accept rule is self-validating, so pad drafts on
            # draftless rows are safe — an accidental pad match is a
            # genuine accept; only REAL proposals count toward the rate
            n_acc = accept_length(d_row, g_row[:k])
            self._audit_spec_bookkeeping(d_row, g_row, n_acc,
                                         f"slot={idx}")
            if idx in drafts:
                proposed += k
                accepted += n_acc
                rid = pool.slots[idx].request_id
                prev = self._spec_ewma.get(rid, float(n_acc))
                self._spec_ewma[rid] = ((1 - _SPEC_EWMA_ALPHA) * prev
                                        + _SPEC_EWMA_ALPHA * n_acc)
                expect += self._spec_ewma[rid]
            for i in range(n_acc + 1):
                slot = pool.slots[idx]
                slot.token = int(g_row[i])
                slot.pos += 1
                slot.generated.append(slot.token)
                slot.timing["token_ns"].append(t_tok)
                committed += 1
                if self._maybe_retire(pool, idx):
                    break
        # full-batch economics (see _SPEC_VERIFY_COST): expected accepts
        # from the drafting rows' refreshed EWMAs must cover the pad
        # rows' share of the k+1-wide program, else pace speculation
        if drafts and expect < (_SPEC_VERIFY_COST - 1.0) * max(
                1, len(idxs)):
            self._spec_gate_idle = _SPEC_PROBE_EVERY - 1
        return proposed, accepted, committed

    def _audit_verify(self, result, node: str) -> None:
        """SERVE003 (program arm): the verify step must donate its cache
        and length-mask attention past the committed positions —
        audited once per compiled verify signature."""
        try:
            from easydist_tpu.analyze import check_speculative_rewind

            check_speculative_rewind(result=result, node=node)
        except ImportError:  # analyze is an optional layer at runtime
            pass

    def _audit_spec_bookkeeping(self, draft, target, n_accepted: int,
                                node: str) -> None:
        """SERVE003 (bookkeeping arm): the accepted prefix must never
        advance past the first draft/target mismatch."""
        try:
            from easydist_tpu.analyze import check_speculative_rewind

            check_speculative_rewind(
                draft=[int(t) for t in draft],
                target=[int(t) for t in target],
                n_accepted=n_accepted, node=f"verify[{node}]")
        except ImportError:
            pass

    def _audit_spec_rollback(self, pool: _PagedPool) -> None:
        """SERVE003 (paged arm): after a rollback released spill pages,
        no table row may still point at a released page."""
        try:
            from easydist_tpu.analyze import check_speculative_rewind

            check_speculative_rewind(pool=pool.pool, table=pool.table,
                                     trie=pool.trie,
                                     node="verify[rollback]")
        except ImportError:
            pass

    def _audit_donation(self, result, bucket: int) -> None:
        try:
            from easydist_tpu.analyze import check_decode_donation

            check_decode_donation(result, node=f"decode[bucket={bucket}]")
        except ImportError:  # analyze is an optional layer at runtime
            pass

    def _audit_prefix_cache(self, pool) -> None:
        try:
            from easydist_tpu.analyze import check_prefix_cache

            check_prefix_cache(pool.trie,
                               node=f"prefix_cache[bucket={pool.bucket}]")
        except ImportError:
            pass

    def _audit_host_aliases(self, pool) -> None:
        """ALIAS004: the buffers the next dispatch donates (the arena)
        must not be reachable from host-held references that outlive the
        step — trie nodes must hold page references
        (`kv.is_page_ref`), never the donated arrays themselves."""
        try:
            from easydist_tpu.analyze import check_host_aliases
        except ImportError:  # analyze is an optional layer at runtime
            return
        holders = {}
        if pool.trie is not None:
            holders["trie"] = [node.kv for node in pool.trie._walk()]
        check_host_aliases({"arena": pool.arena}, holders,
                           node=f"session[bucket={pool.bucket}]")

    def _audit_kv(self, pool: _PagedPool, where: str) -> None:
        """KV001: page-table/refcount audit at the state transitions
        where drift would matter (first decode, every retire).  Layer 13
        rides along: KVQ001 (scale/payload desync) when the arena is
        quantized, KVQ003 (manifest round trip) when a tier is up.

        A consistent pool is decided in a fixed number of array passes
        whatever its slots and pages; the listed walk runs only to word
        a failure.  `kv_audits{where=retire|first_decode,
        path=vector|listed}` counts which ran: a sound run reads `listed`
        0."""
        try:
            from easydist_tpu.analyze import (check_page_table,
                                              check_quant_arena,
                                              check_tier_roundtrip)

            check_page_table(
                pool.pool, pool.table, trie=pool.trie, node=f"kv[{where}]",
                on_path=lambda path: spans.count(
                    "kv_audits", where=where.split("[")[0], path=path))
            if "k_scale" in pool.arena:
                check_quant_arena(pool.arena, node=f"kv.quant[{where}]")
            if pool.tier is not None:
                check_tier_roundtrip(pool.tier, node=f"kv.tier[{where}]")
        except ImportError:  # analyze is an optional layer at runtime
            pass
        if pool.state is not None:
            pool.state.check_invariants(
                list(pool.slots) + [j.slot_idx for j in pool.jobs.values()])

    def _audit_quant_program(self, result, where: str) -> None:
        """KVQ002: the compiled quant step must never feed int8 K/V into
        a dot_general undequantized — run once per program, where the
        donation audit already runs."""
        try:
            from easydist_tpu.analyze import check_quant_program

            check_quant_program(result, node=f"decode.quant[{where}]")
        except ImportError:
            pass

    # ------------------------------------------------------------- driving
    def step(self) -> int:
        """One serving round: admit pending prompts into free slots/rows,
        run at most `prefill_chunks_per_step` prefill chunk calls, then
        one decode (or verify) round over the live slots, harvesting
        retirements.  Returns the number of tokens generated this round
        (decode tokens; prefill first-tokens count via `prefills`)."""
        # the replica-death fault point sits at the step boundary: tokens
        # from completed steps were already streamed/synced, this step's
        # are lost — exactly the state a real mid-decode crash leaves
        faultinject.crash_point("fleet.replica.crash")
        self._step_index += 1
        with spans.span("easydist.serve.step", step=self._step_index,
                        live=sum(p.n_active for p in self._pools.values()),
                        queued=len(self._pending)) as step_span:
            empty_ns = self._empty_ns
            if self._empty_since_ns is not None:    # stepped while empty
                spans.record_span("easydist.serve.empty",
                                  self._empty_since_ns, step_span.t0_ns)
                empty_ns += step_span.t0_ns - self._empty_since_ns
                # recorded: a `submit()` during this step finds no emptiness
                self._empty_since_ns = None
            step_span.set(empty_ns=empty_ns)
            with spans.span("easydist.serve.admit") as sp:
                queued = len(self._pending)
                while self._admit_one():
                    pass
                sp.set(admitted=queued - len(self._pending),
                       deferred=len(self._pending))
            budget = self.config.prefill_chunks_per_step
            for pool in self._pools.values():
                if budget <= 0:
                    break
                if pool.jobs:
                    budget -= self._prefill_round(pool, budget)
            before = self.metrics.counter("tokens_generated")
            for pool in self._pools.values():
                if pool.slots:
                    if self._drafter is not None \
                            and self._spec_round(pool):
                        continue
                    self._decode_round(pool)
            self.metrics.set_gauge("queue_depth", self.queue_depth)
            generated = self.metrics.counter("tokens_generated") - before
        self._empty_ns = 0
        self._empty_since_ns = step_span.t1_ns if self.is_drained else None
        return generated

    def run_until_drained(self, max_steps: int = 100000) -> None:
        """Drive `step()` until no request is live or queued."""
        for _ in range(max_steps):
            if not self._pending and not any(
                    p.slots or p.jobs for p in self._pools.values()):
                return
            self.step()
        raise RuntimeError(f"not drained after {max_steps} steps")

    # ------------------------------------------------------------ lifecycle
    def drain(self, wait: bool = True, max_steps: int = 100000):
        """Stop admitting (submits raise `ReplicaDrainingError`), let
        in-flight work retire, and export the tries' hot pages for
        re-admission elsewhere.  `wait=False` only flips the flag — the
        caller keeps driving `step()` (a fleet router does this so its
        OTHER replicas never stall behind this one's drain) and calls
        `export_hot_pages()` itself once `is_drained`.  Returns the hot
        pages (wait=True) or None (wait=False).  Idempotent."""
        self._draining = True
        if not wait:
            return None
        self.run_until_drained(max_steps=max_steps)
        return self.export_hot_pages()

    @property
    def is_draining(self) -> bool:
        return self._draining

    @property
    def is_drained(self) -> bool:
        """No queued, prefilling, or decoding work left."""
        return not self._pending and not any(
            p.slots or p.jobs for p in self._pools.values())

    def export_hot_pages(self) -> Dict[int, List[List[tuple]]]:
        """Per-bucket root-to-leaf chunk paths from each trie,
        hottest-first (prefix_cache.hot_paths) — what a router re-imports
        into surviving replicas on drain so shared-prefix traffic does
        not re-pay prefill after a scale-down."""
        return {b: [self._materialize_path(p, path)
                    for path in p.trie.hot_paths()]
                for b, p in self._pools.items() if p.trie is not None}

    # ------------------------------------------------- fleet trie access
    def _trie_pool(self, prompt: Sequence[int]) -> Optional[_PagedPool]:
        """The pool whose trie `prompt` would match in: None when the
        prompt fits no bucket, no pool is built yet or it keeps no trie."""
        if select_bucket(len(prompt) + 1,
                         self.config.decode_buckets) is None:
            return None
        pool = self._pools.get(max(self.config.decode_buckets))
        return pool if pool is not None and pool.trie is not None else None

    def _materialize_path(self, pool, path: List[tuple]) -> List[tuple]:
        """Fleet transport of trie entries: replace {"page": id}
        references with the page's actual K/V ({key: [layers, heads,
        chunk, *]} arrays, `kv/arena.py::export_page`), so exported paths
        carry no page id of this session's arena on the wire."""
        import jax.numpy as jnp

        out = []
        for key, kv in path:
            if is_page_ref(kv):
                kv = self._paged_c("export")(
                    pool.arena, jnp.asarray(int(kv["page"]), jnp.int32))
            elif is_host_ref(kv):
                # demoted chunk: serve the manifest-verified host copy
                # (tier entry stays — this is an export, not a promotion)
                try:
                    host_kv = pool.tier.get(kv["host"]) \
                        if pool.tier is not None else None
                except (KeyError, TierError):
                    host_kv = None
                if host_kv is None:
                    break  # keep the exportable prefix contiguous
                kv = {k: jnp.asarray(v) for k, v in host_kv.items()}
            out.append((key, kv))
        return out

    def _import_path(self, pool, path: Sequence[tuple]) -> int:
        """Commit a transported (materialized) chunk path into the
        trie: each chunk lands in a freshly allocated arena page, written
        by the compiled import program and committed as a page
        reference.  First-commit-wins like `PrefixCache.import_path`;
        stops when the arena or the trie budget refuses a page."""
        import jax.numpy as jnp

        nodes: List[object] = []
        for key, kv in path:
            node = pool.trie.lookup_node(nodes, key)
            if node is None:
                if set(kv) != set(pool.arena):
                    # precision/layout mismatch (e.g. a quantized page
                    # offered to an exact arena): recompute locally
                    # rather than coerce payload without its scales
                    break
                if not pool.make_room(1):
                    break
                pid = pool.pool.alloc()
                pool.arena = self._paged_c("import")(
                    pool.arena, kv, jnp.asarray(pid, jnp.int32))
                node = pool.trie.commit(nodes, key, {"page": pid},
                                       nbytes=pool.page_bytes)
                if node is None:
                    pool.pool.release(pid)
                    break
            nodes.append(node)
        return len(nodes)

    def bucket_chunk(self, prompt: Sequence[int]) -> Optional[int]:
        """Trie page size (tokens) `prompt` is cached at, or None when
        the prompt fits no bucket / prefix reuse is off."""
        bucket = select_bucket(len(prompt) + 1, self.config.decode_buckets)
        if bucket is None or not self.config.enable_prefix_cache \
                or not self.config.prefix_cache_bytes:
            return None
        return min(self.config.prefill_chunk,
                   max(self.config.decode_buckets))

    def prefix_affinity(self, prompt: Sequence[int]) -> int:
        """Tokens of `prompt` already committed in this session's trie —
        non-mutating (PrefixCache.peek), so a router can probe every
        replica without disturbing LRU state."""
        pool = self._trie_pool(prompt)
        if pool is None:
            return 0
        return pool.trie.peek(prompt, max_tokens=len(prompt) - 1)

    def export_prefix_path(self, prompt: Sequence[int],
                           max_tokens: Optional[int] = None) -> List[tuple]:
        """Committed chunk path for `prompt`'s longest cached prefix, as
        [(chunk_tokens, kv)] for transport to another replica (page
        references materialized into real arrays)."""
        pool = self._trie_pool(prompt)
        if pool is None:
            return []
        return self._materialize_path(
            pool, pool.trie.export_path(prompt, max_tokens=max_tokens))

    def import_prefix_path(self, prompt: Sequence[int],
                           path: Sequence[tuple]) -> int:
        """Commit a transported chunk path into the trie `prompt` will
        match in (creating the pool if needed).  Returns chunks present
        along the path afterwards."""
        bucket = select_bucket(len(prompt) + 1, self.config.decode_buckets)
        if bucket is None:
            return 0
        pool = self._pool_for(bucket)
        if pool.trie is None:
            return 0
        return self._import_path(pool, path)

    def import_hot_pages(self, pages: Dict[int, List[List[tuple]]]) -> int:
        """Re-admit another replica's exported hot pages (drain
        migration): every path, whatever capacity its exporter keyed it
        by, imports into this session's one trie.  Returns total chunks
        committed."""
        total = 0
        for bucket, paths in pages.items():
            pool = self._pool_for(bucket)
            if pool.trie is None:
                continue
            for path in paths:
                total += self._import_path(pool, path)
        return total

    def snapshot_inflight(self) -> List[Dict[str, object]]:
        """Progress of every live request, keyed by its future (identity
        — the only handle a router shares with this session).  `ids` is
        the tokens already emitted, i.e. what a streaming client has
        already received; a router syncs these into per-request
        `ResumeDescriptor`s after each step so a crash of THIS session
        can be recovered bitwise by resubmitting prompt+ids elsewhere.
        Read-only: no session state changes."""
        with spans.span("easydist.serve.snapshot_inflight") as sp:
            out: List[Dict[str, object]] = []
            for prompt, max_new, eos, fut, _t in self._pending:
                out.append({"future": fut, "prompt": list(prompt),
                            "ids": [], "max_new": max_new, "eos_id": eos,
                            "stage": "queued"})
            for pool in self._pools.values():
                for job in pool.jobs.values():
                    out.append({"future": job.future,
                                "prompt": list(job.prompt), "ids": [],
                                "max_new": job.max_new,
                                "eos_id": job.eos_id, "stage": "prefill"})
                for slot in pool.slots.values():
                    out.append({"future": slot.future,
                                "prompt": list(slot.prompt),
                                "ids": list(slot.generated),
                                "max_new": slot.max_new,
                                "eos_id": slot.eos_id, "stage": "decode"})
            sp.set(n=len(out))
        return out

    def evacuate(self) -> List[Dict[str, object]]:
        """Preemptive drain (SIGTERM grace too short to retire decodes):
        retire EVERY live request immediately with finish_reason
        "evacuated" and partial ids, returning resume descriptors.  A
        router resubmits prompt + ids with the remaining budget elsewhere;
        greedy continuation is a pure function of the token prefix, so the
        concatenated output is bitwise-identical to an uninterrupted run.
        An evacuated partial never contains eos (eos retires the slot the
        step it appears) and is always shorter than max_new (reaching it
        retires as "length"), so the remaining budget is >= 1."""
        self._draining = True
        out: List[Dict[str, object]] = []
        while self._pending:
            prompt, max_new, eos, fut, timing = self._pending.popleft()
            if fut.set_running_or_notify_cancel() is False:
                continue
            fut.set_result({"ids": [], "finish_reason": "evacuated",
                            "timing": _finish_timing(timing, "evacuated")})
            out.append({"prompt": list(prompt), "ids": [],
                        "max_new": max_new, "eos_id": eos})
        for pool in self._pools.values():
            for row in list(pool.jobs):
                job = pool.jobs.pop(row)
                pool.free_rows.append(row)
                pool.give_slot(job.slot_idx)
                if pool.trie is not None:
                    pool.trie.unpin(job.prefix_nodes)
                job.future.set_result(
                    {"ids": [], "finish_reason": "evacuated",
                     "timing": _finish_timing(job.timing, "evacuated")})
                out.append({"prompt": list(job.prompt), "ids": [],
                            "max_new": job.max_new, "eos_id": job.eos_id})
            for idx in list(pool.slots):
                slot = pool.slots[idx]
                desc = {"prompt": list(slot.prompt),
                        "ids": list(slot.generated),
                        "max_new": slot.max_new, "eos_id": slot.eos_id}
                self._retire(pool, idx, "evacuated")
                out.append(desc)
        return out

    def close(self) -> None:
        """Drain, then release the pooled device caches.  Idempotent;
        every submit afterwards raises `ReplicaDrainingError`."""
        if self._closed:
            return
        self.drain(wait=True)
        self._closed = True
        self._pools.clear()

    # ----------------------------------------------------------- reporting
    def stats(self) -> Dict[str, object]:
        return {
            "replica_id": self.replica_id,
            "draining": self._draining,
            "queue_depth": self.queue_depth,
            "pending": len(self._pending),
            "buckets": {
                b: {"active": p.n_active, "free": len(p.free),
                    "prefilling": len(p.jobs),
                    "free_rows": len(p.free_rows),
                    "prefix_cache": (p.trie.stats() if p.trie else None),
                    "kv_pool": p.pool.stats(),
                    "kv_table_mapped": int(
                        (p.table.array != p.table.sentinel).sum())}
                for b, p in self._pools.items()},
            "decode_signatures":
                self._paged_c(self._decode_program).cache_stats(),
            "prefill_signatures":
                self._paged_c(self._chunk_program).cache_stats(),
            "verify_signatures": (self._paged_cs["verify"].cache_stats()
                                  if "verify" in self._paged_cs else None),
            "metrics": self.metrics.snapshot(),
        }

    # --------------------------------------------------------- constructors
    @classmethod
    def _wire_draft_model(cls, kw, dparams, draft) -> None:
        """Turn a draft model (its params and `Decoder`) into a
        `SmallModelDrafter` over the contiguous decode step (in `kw` as
        `drafter`, unless the caller passed one explicitly)."""
        if kw.get("drafter") is not None:
            return
        from easydist_tpu.models.decoder import Contiguous, decode

        from .speculate import SmallModelDrafter

        scfg = kw.get("config") or ServeConfig()
        max_len = max(scfg.decode_buckets)
        if draft.max_positions is not None:
            max_len = min(max_len, draft.max_positions)
        kw["drafter"] = SmallModelDrafter(
            dparams,
            model_decode=lambda p, c, t, pos: decode(
                draft, Contiguous(c), p, t, pos),
            init_cache=lambda b, L: Contiguous.init(draft, b, L),
            max_len=max_len, mesh=kw.get("mesh"))

    @classmethod
    def _for_family(cls, name, family, params, cfg, draft_model, kw):
        """A session over `family`, a model module with `decoder(cfg)`."""
        import dataclasses

        kw.setdefault("compile_key", (name, dataclasses.astuple(cfg)))
        if draft_model is not None:
            cls._wire_draft_model(kw, draft_model[0],
                                  family.decoder(draft_model[1]))
        return cls(params, model=family.decoder(cfg), **kw)

    @classmethod
    def for_gpt(cls, params, cfg, *, draft_model=None, **kw):
        """Session over models/gpt.py; decode_buckets must fit cfg.seq
        (the learned-position-table bound).  `draft_model=(params, cfg)`
        wires a `SmallModelDrafter` over a second (smaller) gpt for
        `speculate_drafter="draft_model"`."""
        from easydist_tpu.models import gpt

        return cls._for_family("gpt", gpt, params, cfg, draft_model, kw)

    @classmethod
    def for_llama(cls, params, cfg, *, draft_model=None, **kw):
        """Session over models/llama.py (RoPE: buckets are not bound by
        cfg.seq).  `draft_model=(params, cfg)` wires a
        `SmallModelDrafter` over a second (smaller) llama for
        `speculate_drafter="draft_model"`."""
        from easydist_tpu.models import llama

        return cls._for_family("llama", llama, params, cfg, draft_model, kw)
