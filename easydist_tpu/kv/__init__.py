"""Paged KV subsystem: a refcounted page-pool allocator over one
preallocated HBM arena plus an int32 page-table indirection per live
sequence.

The slot-pool decode cache (serve/generation.py PR 8-11) pads every
sequence to its bucket's max_len, so HBM per slot is worst-case and
occupancy caps out under mixed-length traffic.  This package makes the
fixed-size `page_tokens`-token KV page — already the unit the prefix trie
commits and the fleet transport ships — THE allocation unit for decode
storage too:

  * `pool.PagePool` — host-side free-list allocator with per-page
    refcounts.  Copy-on-write sharing with the prefix trie: a restored
    prefix MAPS its committed pages into the sequence's page table
    (refcount bump) instead of `dynamic_update_slice`-copying bytes, and
    serving never writes a shared page (writes land at positions past the
    restored prefix, in freshly allocated pages), so the "copy" half of
    COW never runs on the serving path.
  * `table.PageTable` — per-slot int32 page indices, fixed
    [max_slots, max_pages] shape so the compiled decode step's signature
    stays closed over arbitrary sequence lengths.  Unmapped entries hold
    the sentinel `n_pages` (one past the arena): scatter writes through a
    sentinel drop (`mode="drop"`), gathers clip and the garbage row is
    masked to -inf before softmax.

  * `arena` — the device side: the arena is a pytree of per-layer leaves,
    {"k": (leaf_0, ..., leaf_{L-1}), "v": (...)} (int8 arenas add
    "k_scale" / "v_scale" the same way), each leaf [n_pages, (kv_)heads,
    page_tokens, head_dim] in a buffer of its own.  A compiled step
    donates the arena leaf by leaf and writes each layer's rows into that
    layer's own input buffer — nothing the size of a leaf is ever copied,
    sliced out or stacked back.  A page leaves the device in the format
    it always had, {key: [layers, heads, page_tokens, *]} (one page of
    every leaf, stacked), which is what the trie, the host tier and the
    fleet transport hold, hash and ship.

  * `state.StatePool` — the slots of what a model keeps by the SEQUENCE:
    the recurrent state of its state layers and the K/V rings of its
    window layers (one slot per live sequence, beside its pages in the
    same pool).  Pages are of the layers that see every position, only.

Analyze rule KV001 (`analyze/kv_rules.py`) audits the pool/table/trie
bookkeeping; `check_invariants` here is the raw audit it wraps:
`consistent()` decides a sound pool or table in a fixed number of array
passes, `list_problems()` walks one that fails to word what is wrong.
"""

from __future__ import annotations

from .pool import PagePool
from .state import StatePool
from .table import PageTable
from .tier import HostTier, TierError

__all__ = ["HostTier", "PagePool", "PageTable", "StatePool", "TierError",
           "is_host_ref", "is_page_ref"]


def is_page_ref(kv) -> bool:
    """True iff a trie-committed kv value is a page REFERENCE
    (`{"page": id}`) rather than materialized arrays.

    This is the paged layout's aliasing contract: the arena is donated
    to every compiled dispatch, so host bookkeeping (trie nodes, resume
    descriptors, transport manifests) must hold *indices into* the
    arena, never the arena arrays themselves — a retained array
    reference is storage the next donating dispatch invalidates
    (analyze layer 11, ALIAS004)."""
    return isinstance(kv, dict) and set(kv) == {"page"}


def is_host_ref(kv) -> bool:
    """True iff a trie-committed kv value was DEMOTED to the host tier
    (`{"host": key}`, `tier.HostTier` holding the bytes).  Host refs own
    no arena page: the trie node charges 0 bytes against the HBM budget
    and promotion (tier.get + arena import) swaps the value back to a
    `{"page": id}` ref before the slot's first decode step."""
    return isinstance(kv, dict) and set(kv) == {"host"}
