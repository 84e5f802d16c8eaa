"""Int32 page-table indirection: which arena page holds each
`page_tokens`-token window of each live sequence.

The table is a host-side numpy array of fixed shape
[max_slots, max_pages] — the compiled decode step takes it (as a device
int32 array) every step, and the FIXED shape is what keeps the step's
signature closed: a sequence at length 37 and one at length 1988 present
the same table shape, only the entries differ.  Unmapped entries hold the
sentinel `n_pages` (one past the arena): compiled scatter writes through
the table use `mode="drop"` so sentinel writes vanish deterministically,
and gathers clip to the last real page whose rows the attention mask
zeroes out before softmax.
"""

from __future__ import annotations

from typing import List

import numpy as np

__all__ = ["PageTable"]


class PageTable:
    """[max_slots, max_pages] int32 page indices, sentinel `n_pages` for
    unmapped entries.  Pure host bookkeeping — callers push `self.array`
    to device each step (a few KiB; the arena itself never moves)."""

    def __init__(self, max_slots: int, max_pages: int, n_pages: int):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if max_pages < 1:
            raise ValueError(f"max_pages must be >= 1, got {max_pages}")
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        self.max_slots = max_slots
        self.max_pages = max_pages
        self.n_pages = n_pages
        self.sentinel = n_pages
        self.array = np.full((max_slots, max_pages), self.sentinel,
                             dtype=np.int32)

    # ------------------------------------------------------------- mapping
    def map(self, slot: int, idx: int, page: int) -> None:
        """Point `slot`'s window `idx` (tokens [idx*pt, (idx+1)*pt)) at
        arena `page`.  Windows must be mapped at most once — remapping a
        live entry would leak its page's refcount."""
        if not 0 <= page < self.n_pages:
            raise ValueError(f"page {page} out of range [0, {self.n_pages})")
        if self.array[slot, idx] != self.sentinel:
            raise ValueError(
                f"slot {slot} window {idx} already maps page "
                f"{int(self.array[slot, idx])} (unmap before remapping)")
        self.array[slot, idx] = page

    def unmap_row(self, slot: int) -> List[int]:
        """Clear `slot`'s row back to sentinel, returning the pages it
        mapped (the caller releases each against the pool)."""
        row = self.array[slot]
        pages = [int(p) for p in row[row != self.sentinel]]
        row[:] = self.sentinel
        return pages

    def unmap_tail(self, slot: int, from_idx: int) -> List[int]:
        """Clear `slot`'s windows >= `from_idx` back to sentinel,
        returning the pages they mapped (the caller releases each
        against the pool).  This is the speculative-decoding rollback
        primitive: windows mapped beyond a sequence's up-front
        reservation only ever hold rejected draft rows, so truncating
        the table tail releases them without touching the committed
        prefix — the hole-free-prefix invariant holds trivially (a
        suffix clear cannot create a hole)."""
        if from_idx < 0:
            raise ValueError(f"from_idx must be >= 0, got {from_idx}")
        tail = self.array[slot, from_idx:]
        pages = [int(p) for p in tail[tail != self.sentinel]]
        tail[:] = self.sentinel
        return pages

    def mapped(self, slot: int) -> List[int]:
        """Pages `slot` currently maps, in window order."""
        row = self.array[slot]
        return [int(p) for p in row[row != self.sentinel]]

    def n_mapped(self, slot: int) -> int:
        return int((self.array[slot] != self.sentinel).sum())

    # ----------------------------------------------------------- reporting
    def check_invariants(self) -> List[str]:
        """Shape/range audit (KV001 cross-checks entries against the
        pool's refcounts; this is the table-local half).

        A consistent table is decided by `consistent()`, a fixed number
        of array passes whatever its size; `list_problems()` walks it
        row by row only to word a failure."""
        return [] if self.consistent() else self.list_problems()

    def consistent(self) -> bool:
        """True exactly where `list_problems()` would return []."""
        if self.array.shape != (self.max_slots, self.max_pages):
            return False
        if ((self.array < 0) | (self.array > self.sentinel)).any():
            return False
        # a row's mapped windows are a contiguous prefix exactly where
        # no mapped window follows an unmapped one
        live = self.array != self.sentinel
        return not (live[:, 1:] & ~live[:, :-1]).any()

    def list_problems(self) -> List[str]:
        """The listed walk: one line per violated invariant, slot by
        slot."""
        problems: List[str] = []
        if self.array.shape != (self.max_slots, self.max_pages):
            problems.append(
                f"table shape drifted to {self.array.shape} (compiled-step "
                f"signature no longer closed)")
        bad = (self.array < 0) | (self.array > self.sentinel)
        if bad.any():
            problems.append(
                f"{int(bad.sum())} entries outside [0, {self.sentinel}]")
        for slot in range(self.max_slots):
            row = self.array[slot]
            live = row != self.sentinel
            # mapped windows must be a contiguous prefix of the row: a
            # hole would mean attention gathers a garbage page INSIDE the
            # live length, where the mask does not cover for it
            if live.any():
                last = int(np.max(np.nonzero(live)[0]))
                if not live[:last + 1].all():
                    problems.append(
                        f"slot {slot} has unmapped window before window "
                        f"{last} (hole inside the live prefix)")
        return problems
