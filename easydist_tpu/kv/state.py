"""The pool of per-sequence slots: a slot per live sequence.

Some layers cache a fixed amount per SEQUENCE, whatever the sequence's
length, where a full attention layer caches rows per position: a state
layer (Mamba-2) an SSM state and the tail of its conv window, a window
attention layer the K/V of its last `window` positions, in a ring.  The
device side is `models.decoder.State` ({name: (a leaf per such layer)},
each leaf [n_slots, ...], donated and written in place like an arena leaf;
the rings ride in it as `Ring`); `StatePool` is the host's book of which
slots hold a sequence.  It lives beside `PagePool` in the session's one
pool: a request is admitted when a slot AND its pages are there, takes
both together, and gives both back when it retires.  A slot is never
shared and never outlives its sequence, so there is no refcount: nothing
restores a state or a ring from anywhere yet (snapshots at chunk
boundaries are what the trie and resume would need).  A slot's leaves are
not cleared between sequences: a first chunk starts from zero state, and
no position of the new sequence reaches a ring row the old one wrote.
"""

from __future__ import annotations

from typing import Set

__all__ = ["StatePool"]


class StatePool:
    """Which of `n_slots` state slots hold a live sequence."""

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.n_slots = n_slots
        self._held: Set[int] = set()

    @property
    def sentinel(self) -> int:
        """The slot id of a row that is no sequence: one past the pool, so
        writes through it drop."""
        return self.n_slots

    @property
    def in_use(self) -> int:
        return len(self._held)

    def take(self, slot: int) -> None:
        if not 0 <= slot < self.n_slots or slot in self._held:
            raise ValueError(f"state slot {slot} is held or out of range")
        self._held.add(slot)

    def release(self, slot: int) -> None:
        if slot not in self._held:
            raise ValueError(f"state slot {slot} is not held")
        self._held.remove(slot)

    def check_invariants(self, live_slots) -> None:
        """The held slots are exactly the sequences the session has (its
        decode slots and its prefill jobs' reserved slots)."""
        live = set(live_slots)
        if live != self._held:
            raise AssertionError(
                f"state slots held {sorted(self._held)} != sequences "
                f"{sorted(live)}")
