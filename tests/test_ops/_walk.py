"""Page tables for the paged kernels' walk (`_paged_decode_steps`: a loop over
a row's LIVE windows, pages copied by hand), shared by the three files that
hold the kernels to their XLA fallbacks.  A bucket of 6 pages of 8 tokens,
walked 2 pages a window (`pages_per_step=2`): a window is 16 tokens."""

import numpy as np

PT, MAX_PAGES, N_PAGES, PAGES_PER_STEP = 8, 6, 30, 2
WINDOW, BUCKET = PT * PAGES_PER_STEP, PT * MAX_PAGES

# name -> (the rows' lengths, the rows whose table names no page at all)
CASES = {
    "length-0-and-unmapped-rows-among-live": ([13, 0, 9, 1, BUCKET], {3}),
    "every-row-dead": ([0, 1, 0], {1}),
    "on-a-window-boundary-and-one-past": (
        [WINDOW, WINDOW + 1, 2 * WINDOW, 2 * WINDOW + 1], set()),
    "one-row-full-to-the-bucket": ([BUCKET, 5, 0], set()),
}
# what a dead table entry holds: the pool's sentinel, or any index past it
DEAD_ENTRIES = {"sentinel": N_PAGES, "past-the-arena": N_PAGES + 7}


def table_for(case: str, dead: str, min_length: int = 0, seed: int = 0):
    """(table [rows, MAX_PAGES], lengths, live mask, named pages): every
    page under a row's length is a page of its own, every other entry
    `DEAD_ENTRIES[dead]`.  `min_length` lifts the live rows' lengths (a
    chunk of queries needs as many positions).  A row is live if it has a
    length and its first entry names a page."""
    lengths, unmapped = CASES[case]
    lengths = np.asarray([max(n, min_length) if n else 0 for n in lengths],
                         np.int32)
    perm = iter(np.random.RandomState(seed).permutation(N_PAGES))
    table = np.full((len(lengths), MAX_PAGES), DEAD_ENTRIES[dead], np.int32)
    for row, n in enumerate(lengths):
        if row not in unmapped:
            # a row of length 0 keeps its first page: a slot just admitted
            used = max(-(-int(n) // PT), 1)
            table[row, :used] = [next(perm) for _ in range(used)]
    live = np.asarray([n > 0 and row not in unmapped
                       for row, n in enumerate(lengths)])
    named = sorted({int(p) for row in np.flatnonzero(live)
                    for p in table[row, :-(-int(lengths[row]) // PT)]})
    return table, lengths, live, named


def poisoned(pages: np.ndarray, named, value=np.nan) -> np.ndarray:
    """`pages` [N_PAGES, ...] with `value` in every page no live entry
    names: what a walk that visits only live pages never reads."""
    out = np.array(pages)
    out[np.setdiff1d(np.arange(len(out)), named)] = value
    return out


def slot_shapes(jaxpr):
    """The shapes of the VMEM slots ([2, pages a window, heads, page_tokens,
    width] a `pages` operand) of the ONE paged kernel call in `jaxpr`."""
    (eqn,) = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    return [v.aval.shape for v in eqn.params["jaxpr"].invars
            if len(v.aval.shape) == 5]
