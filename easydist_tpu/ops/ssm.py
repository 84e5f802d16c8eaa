"""The two diagonal state-space recurrences of a state layer, each in its
two serving forms, and the short conv in front of them.

Mamba-2 (SSD): `A` is a SCALAR a head and B, C are shared by every head
(one group), so a block of positions is a masked [q, q] product:

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t      S [h, p, n]
    y_t = S_t . C_t + D * x_t

`ssd_chunk_scan` runs a window of positions from a state carried in and
gives the state carried out (chunked prefill): on a TPU ONE Pallas kernel,
a row's block of heads a grid step, whose [t, r] decay masks and products
never leave fast memory (`ssd_chunk_scan_xla` is the jnp form every other
backend runs and the tests hold the kernel to); `ssm_decode_update` is the
same recurrence for one position a sequence (decode), a Pallas kernel on a
TPU that reads and writes each state once, in place.

Mamba-1 (selective): the decay is a different number for every channel d
AND every state index n, so no matmul form exists and the window is walked
a position at a time:

    h_t[n, d] = exp(dt_t[d] * A[n, d]) * h_{t-1}[n, d] + dt_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n h_t[n, d] C_t[n] + D[d] x_t[d]              h [n, e]

`selective_chunk_scan` is a Pallas kernel on a TPU that keeps a block of
channels' [n, channels] state in fast memory across all the window's
positions and writes it once; `selective_decode_update` the one-position
kernel, in place.  The state is stored with the state index on the
sublanes and the channels on the lanes ([16, 5120]: nothing padded).

In both, a position whose `dt` is 0 leaves the state as it was, bit for
bit — exp(0) * S + 0 — which is how the callers keep padded positions and
dead rows out of it.  The state is float32 throughout.  `causal_conv_tail`
is the short conv with a carried tail that sits in front of such a
recurrence, shared with the delta rule's mixer (`ops/delta_rule.py`); the
tail is carried FLAT, [b, (taps - 1) * channels], the slots on the sublanes
and the taps side by side on the lanes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret, _largest_divisor

__all__ = ["causal_conv_tail", "ssd_chunk_scan", "ssd_chunk_scan_xla",
           "ssm_decode_update",
           "ssm_decode_update_xla", "selective_chunk_scan",
           "selective_chunk_scan_xla", "selective_decode_update",
           "selective_decode_update_xla"]


def causal_conv_tail(tail, x, w, bias, valid, activation=jax.nn.silu):
    """The short causal depthwise conv of a state layer (in front of
    Mamba-1's, Mamba-2's and the gated delta rule's recurrence; the whole
    of a gated short convolution's carry, `models/lfm2_moe.py`), over
    [carried tail | this window]: tail [b, (taps - 1) * c] (the last
    pre-activation inputs of the sequence so far, float32, FLAT: input j
    of the taps - 1 is the lanes [j * c, (j + 1) * c), oldest first), x
    [b, s, c] (float32), w [taps, c] (row j multiplies the input taps - 1 -
    j positions back), bias [c] or None, valid bool [b, s] (a PREFIX of
    each row counts) -> (activation(conv) [b, s, c], the tail after the
    positions that count: with none of them, the tail as it was, bit for
    bit).  `activation` is silu unless told otherwise; None gives the conv
    itself (a model that gates it, and applies nothing).

    Flat because that is how a v5e tiles it with nothing padded — slots on
    the sublanes, eight to a tile, and where c is whole lane tiles every
    input a slice of whole tiles: as [b, taps - 1, c] the three rows sat on
    the sublanes (3 of a tile's 4), and every shift along them cost a
    relayout of the WHOLE leaf and one back.  One position a row (a decode
    round, where the tail IS the leaf) is lane slices and a select, no
    gather; a window takes each new tail row from x or from the old tail,
    two gathers of taps - 1 rows, and never forms [tail | x] to gather
    from (the conv's slices of it fuse into the conv)."""
    b, s, c = x.shape
    taps = w.shape[0]
    w = w.astype(jnp.float32)
    if s == 1:
        full = jnp.concatenate([tail, x[:, 0]], axis=1)      # [b, taps * c]
        conv = sum(full[:, j * c:(j + 1) * c] * w[j] for j in range(taps))
        new_tail = jnp.where(valid, full[:, c:], tail)
        conv = conv[:, None]
    else:
        rows = tail.reshape(b, taps - 1, c)
        full = jnp.concatenate([rows, x], axis=1)            # [b, s+taps-1, c]
        conv = sum(full[:, j:j + s] * w[j] for j in range(taps))
        # the new tail is full[n_valid : n_valid + taps - 1]
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        at = (n_valid[:, None] + jnp.arange(taps - 1))[:, :, None]
        from_x = jnp.take_along_axis(
            x, jnp.clip(at - (taps - 1), 0, s - 1), axis=1)
        from_tail = jnp.take_along_axis(
            rows, jnp.clip(at, 0, taps - 2), axis=1)
        new_tail = jnp.where(at >= taps - 1, from_x, from_tail).reshape(
            b, (taps - 1) * c)
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    return (conv if activation is None else activation(conv)), new_tail


def _ssd_block(x, dt, a, b_mat, c_mat, state):
    """One block of q positions, all at once (the SSD form: the block's
    own positions through a masked [q, q] product, the state carried in
    through its decay), heads leading so that every product is a batched
    matmul over (b, h) with nothing re-laid out.  x [b, h, q, p], dt
    [b, h, q], a [h], b_mat / c_mat [b, q, n], state [b, h, p, n] ->
    (y [b, h, q, p], state)."""
    q = x.shape[2]
    cs = jnp.cumsum(dt * a[None, :, None], axis=2)       # [b, h, q], <= 0
    # decay from position r (exclusive) to position t (inclusive)
    seg = cs[:, :, :, None] - cs[:, :, None, :]          # [b, h, t, r]
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    scores = jnp.einsum("btn,brn->btr", c_mat, b_mat)    # C_t . B_r
    mix = scores[:, None] * decay * dt[:, :, None, :]    # [b, h, t, r]
    y = jnp.einsum("bhtr,bhrp->bhtp", mix, x)
    y += jnp.einsum("btn,bhpn->bhtp", c_mat, state) * jnp.exp(cs)[..., None]
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt            # [b, h, r]
    state = state * jnp.exp(cs[:, :, -1])[:, :, None, None] \
        + jnp.einsum("bhrp,brn->bhpn", to_end[..., None] * x, b_mat)
    return y, state


def ssd_chunk_scan_xla(x, dt, a, b_mat, c_mat, d_skip, state,
                       block: int = 256):
    """A window of `s` positions from `state`: x [b, s, h, p] (float32),
    dt [b, s, h] (after softplus; 0 at positions that do not count), a [h]
    (negative), b_mat / c_mat [b, s, n], d_skip [h], state [b, h, p, n]
    -> (y [b, s, h, p], state after the window).  `block` is how many
    positions are taken at once; any value gives the same function."""
    s = x.shape[1]
    xh, dth = x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)
    ys = []
    for lo in range(0, s, block):
        hi = min(s, lo + block)
        y, state = _ssd_block(xh[:, :, lo:hi], dth[:, :, lo:hi], a,
                              b_mat[:, lo:hi], c_mat[:, lo:hi], state)
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=2)
    return (y + d_skip[None, :, None, None] * xh).transpose(0, 2, 1, 3), \
        state


# positions a block of the scan kernel takes: the [t, r] mask of a head is
# one MXU tile, 16 vector registers
_SSD_POSITIONS = 128
# fast memory a grid step's x, y and state blocks may take, double-buffered
_SSD_VMEM = 8 * 2 ** 20


def _ssd_tiles(s: int, h: int, p: int, n: int):
    """How the scan kernel tiles a window: (g, hb, q) — `g` heads side by
    side fill whole 128-lane tiles of x and y (two heads of 64), a grid
    step holds `hb` heads (a multiple of `g`: the most whose x and y
    windows and state blocks, each double-buffered, fit `_SSD_VMEM`), and
    the window is walked `q` positions at a time.  None for a shape the
    kernel cannot tile: heads that do not fill whole lane tiles, a window
    shorter than a sublane tile, one so long that `g` heads of it are over
    the budget."""
    g = 128 // math.gcd(p, 128)
    q = min(_SSD_POSITIONS, s + -s % 8)
    per_head = 16 * p * (s + -s % q + n)
    if s < 8 or h % g or g * per_head > _SSD_VMEM:
        return None
    return g, g * _largest_divisor(
        h // g, lambda m: m * g * per_head <= _SSD_VMEM), q


def _ssd_scan_kernel(counts_ref, x_ref, dt_ref, csc_ref, csr_ref, end_ref,
                     b_ref, c_ref, d_ref, s_ref, y_ref, s_out, *, q: int,
                     g: int, p: int, operands):
    """counts int32 [b, nk] (prefetched: whether any position of the row's
    block has a `dt` that is not 0); x / y [1, sp, hb * p] (a head's p
    channels side by side on the lanes, as the model has them); dt and cs,
    the cumulative sum of dt * A INSIDE each block of q positions, heads on
    the lanes [1, 1, nk, q, hb], cs again with the positions on the lanes
    [1, 1, nk, hb, q], and a block's whole decay exp(cs[q - 1]) all along
    a head's row of lanes [1, 1, nk, hb, n] (Mosaic broadcasts a [1, 1]
    along the lanes or down the rows, not both at once); B / C [1, sp, n];
    D on x's lanes [1, hb * p]; state [1, hb * p, n].  Grid (rows, blocks
    of heads).

    `_ssd_block` a block of q positions at a time, for `g` heads at once —
    a unit, whole lane tiles of x: C . B^T once a block; a head's masked
    decay, its [t, r] mix and the mix's product with dt * x; the carried
    state's C . S^T scaled by exp(cs); and the state after the block, kept
    in the output block from one block of positions to the next.  The
    products take `operands` (bfloat16 on the chip: what the backend's
    default precision hands the MXU for the jnp form's float32 einsums) and
    accumulate in float32.  A block none of whose positions counts — the
    padding after a prompt's last chunk, a row that is no sequence — has no
    mix and moves no state (every decay is exp(0) and every dt * x is 0):
    its y is C . S^T + D * x for the whole block of heads in one product,
    the same numbers for a quarter of the work."""
    f32 = jnp.float32
    w = g * p
    n = s_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1)
    srow = jax.lax.broadcasted_iota(jnp.int32, (w, n), 0)
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)

    def by_head(at, pick, shape):
        """Head i's `pick(i)` where `at` (lane or row number) is inside
        head i's p, for the unit's g heads."""
        out = jnp.broadcast_to(pick(g - 1), shape)
        for i in range(g - 2, -1, -1):
            out = jnp.where(at < (i + 1) * p, pick(i), out)
        return out

    def dot(lhs, rhs, contract):
        return jax.lax.dot_general(
            lhs.astype(operands), rhs.astype(operands),
            ((contract, ((), ()))), preferred_element_type=f32)

    def counted(k, at):
        bk, ck = b_ref[0, at, :], c_ref[0, at, :]
        scores = dot(ck, bk, ((1,), (1,)))                       # [t, r]
        dt, csc, csr = dt_ref[0, 0, k], csc_ref[0, 0, k], csr_ref[0, 0, k]
        to_end = jnp.exp(csc[q - 1:q] - csc) * dt                # [q, hb]
        carried = jnp.exp(csc)
        whole = end_ref[0, 0, k]                                 # [hb, n]
        for u in range(x_ref.shape[2] // w):
            h0, lanes = u * g, slice(u * w, (u + 1) * w)
            x = x_ref[0, at, lanes]                              # [q, w]
            xdt = x * by_head(lane, lambda i: dt[:, h0 + i:h0 + i + 1],
                              (q, w))
            mixed = None
            for i in range(g):
                # the difference BEFORE the exponential, under the mask:
                # above the diagonal it is positive and may overflow
                seg = csc[:, h0 + i:h0 + i + 1] - csr[h0 + i:h0 + i + 1]
                mix = scores * jnp.exp(jnp.where(tri, seg, -jnp.inf))
                y_i = dot(mix, xdt, ((1,), (0,)))
                mixed = y_i if mixed is None else jnp.where(
                    lane < i * p, mixed, y_i)
            state = s_out[0, lanes, :]                           # [w, n]
            y = mixed + dot(ck, state, ((1,), (1,))) * by_head(
                lane, lambda i: carried[:, h0 + i:h0 + i + 1], (q, w))
            y_ref[0, at, lanes] = y + d_ref[:, lanes] * x
            moved = x * by_head(
                lane, lambda i: to_end[:, h0 + i:h0 + i + 1], (q, w))
            s_out[0, lanes, :] = state * by_head(
                srow, lambda i: whole[h0 + i:h0 + i + 1], (w, n)) \
                + dot(moved, bk, ((0,), (0,)))

    bi = pl.program_id(0)

    def block(k, _):
        counts = counts_ref[bi, k]
        at = pl.ds(pl.multiple_of(k * q, q), q)
        pl.when(counts != 0)(lambda: counted(k, at))

        @pl.when(counts == 0)
        def _passed():
            y_ref[0, at, :] = dot(c_ref[0, at, :], s_out[0], ((1,), (1,))) \
                + d_ref[...] * x_ref[0, at, :]

    s_out[...] = s_ref[...]
    jax.lax.fori_loop(0, x_ref.shape[1] // q, block, None)


@functools.lru_cache(maxsize=32)
def _ssd_scan_call(b: int, sp: int, h: int, p: int, n: int, g: int, hb: int,
                   q: int, operands: str, interpret: bool):
    """The scan's `pallas_call`, built ONCE a signature, as
    `_selective_scan_call` below and for its reason: a model's second
    state layer finds the first's trace and its Mosaic lowering."""
    nk, nb = sp // q, h // hb

    def lanes(bi, hi, counts):              # x, y: the block's lanes
        return (bi, 0, hi)

    def heads(bi, hi, counts):              # the state: the block's rows
        return (bi, hi, 0)

    def small(bi, hi, counts):
        return (bi, hi, 0, 0, 0)

    def shared(bi, hi, counts):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, sp, hb * p), lanes),
                  pl.BlockSpec((1, 1, nk, q, hb), small),
                  pl.BlockSpec((1, 1, nk, q, hb), small),
                  pl.BlockSpec((1, 1, nk, hb, q), small),
                  pl.BlockSpec((1, 1, nk, hb, n), small),
                  pl.BlockSpec((1, sp, n), shared),
                  pl.BlockSpec((1, sp, n), shared),
                  pl.BlockSpec((1, hb * p), lambda bi, hi, counts: (0, hi)),
                  pl.BlockSpec((1, hb * p, n), heads)],
        out_specs=[pl.BlockSpec((1, sp, hb * p), lanes),
                   pl.BlockSpec((1, hb * p, n), heads)])
    return pl.pallas_call(
        functools.partial(_ssd_scan_kernel, q=q, g=g, p=p,
                          operands=operands),
        grid_spec=grid_spec,
        # y FIRST: a reader of device traces tells the decode update from
        # the other kernels by a first result that is 4-D float32
        out_shape=[jax.ShapeDtypeStruct((b, sp, h * p), jnp.float32),
                   jax.ShapeDtypeStruct((b, h * p, n), jnp.float32)],
        # operand 9 (after the prefetched counts) is the state
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_chunk_scan",
    )


def ssd_chunk_scan(x, dt, a, b_mat, c_mat, d_skip, state, block: int = 256,
                   interpret=None, backend=None):
    """`ssd_chunk_scan_xla` as one kernel: grid (rows, blocks of heads), a
    row's block of heads a step — its x and y lane-dense slices of [b, s,
    h * p] as the model has them (nothing is transposed to heads-major and
    back), the row's B and C fetched once while the row stands, the block's
    state read once and written once to the buffer it came from
    (`input_output_aliases`).  A head's [t, r] decay mask and mix live in
    fast memory only.  The window is walked in blocks of positions of the
    kernel's choosing (`_ssd_tiles`; padded to whole blocks with `dt = 0`,
    which leave the state as it was): `block` is the jnp form's and means
    nothing here, any value gives the same function.  The Pallas kernel on
    a TPU where the shape tiles (or with `backend="pallas"`, whatever the
    shape: the interpreter takes any), the jnp form elsewhere and for
    heads that do not fill whole 128-lane tiles (`h` no multiple of 128 /
    gcd(p, 128)), a window of fewer than 8 positions, or one too long for
    fast memory."""
    b, s, h, p = x.shape
    n = state.shape[-1]
    tiles = _ssd_tiles(s, h, p, n)
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" \
            and tiles is not None else "xla"
    f32 = jnp.float32
    x, dt, a, b_mat, c_mat, d_skip, state = (
        v.astype(f32) for v in (x, dt, a, b_mat, c_mat, d_skip, state))
    if backend == "xla":
        return ssd_chunk_scan_xla(x, dt, a, b_mat, c_mat, d_skip, state,
                                  block)
    if interpret is None:
        interpret = _default_interpret()
    # every head one unit, one step: only the interpreter takes that
    g, hb, q = tiles or (h, h, min(_SSD_POSITIONS, s + -s % 8))
    pad = -s % q
    x = x.reshape(b, s, h * p)
    if pad:
        x, dt, b_mat, c_mat = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                               for v in (x, dt, b_mat, c_mat))
    nk, nb = (s + pad) // q, h // hb

    def rows(v):                         # [b, h, sp] -> [b, nb, nk, hb, q]
        return v.reshape(b, nb, hb, nk, q).transpose(0, 1, 3, 2, 4)

    # heads-major, the positions on the lanes: where XLA's cumulative sum
    # is a microsecond (with the heads on the lanes it was 239 us a layer).
    # It restarts every block: a block's decays are `_ssd_block`'s on its q
    # positions
    dth = dt.transpose(0, 2, 1)
    cs = rows(jnp.cumsum((dth * a[:, None]).reshape(b, h, nk, q), axis=-1))
    with jax.named_scope("ssd_chunk_scan"):
        y, new = _ssd_scan_call(
            b, s + pad, h, p, n, g, hb, q,
            "float32" if interpret else "bfloat16", bool(interpret))(
            (dt.reshape(b, nk, q * h) != 0).any(-1).astype(jnp.int32),
            x, rows(dth).swapaxes(3, 4), cs.swapaxes(3, 4), cs,
            jnp.broadcast_to(jnp.exp(cs[..., q - 1, None]),
                             cs.shape[:4] + (n,)),
            b_mat, c_mat, jnp.repeat(d_skip, p)[None],
            state.reshape(b, h * p, n))
    return (y[:, :s] if pad else y).reshape(b, s, h, p), \
        new.reshape(b, h, p, n)


def ssm_decode_update_xla(state, x, dt, a, b_vec, c_vec, d_skip):
    """The recurrence for one position: state [b, h, p, n], x [b, h, p],
    dt [b, h], a [h], b_vec / c_vec [b, n], d_skip [h] -> (state, y
    [b, h, p])."""
    decay = jnp.exp(dt * a)[:, :, None, None]
    state = state * decay \
        + (dt[:, :, None] * x)[..., None] * b_vec[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", state, c_vec,
                   precision=jax.lax.Precision.HIGHEST)
    return state, y + d_skip[None, :, None] * x


def _ssm_decode_kernel(row_ref, live_ref, s_ref, x_ref, dt_ref, a_ref, b_ref,
                       c_ref, d_ref, s_out, y_out):
    """state [1, hb, p, n]; x / y [1, hb, p]; dt [1, hb, 1]; A / D [hb,
    1]; B / C [1, 1, n].  Grid (head blocks, rows), rows innermost: a dead
    row's blocks are those of a live neighbour (`row_ref`), which the
    pipeline neither fetches again nor writes back while the index stands,
    so it costs no state traffic and changes nothing."""
    i = pl.program_id(1)

    @pl.when(live_ref[i] == 1)
    def _update():
        x, dt = x_ref[0], dt_ref[0]
        decay = jnp.exp(dt * a_ref[...])                       # [hb, 1]
        new = s_ref[0] * decay[:, :, None] \
            + (dt * x)[:, :, None] * b_ref[0][None]
        s_out[0] = new
        y_out[0] = jnp.sum(new * c_ref[0][None], axis=-1) + d_ref[...] * x

    @pl.when(live_ref[i] == 0)
    def _dead():
        y_out[...] = jnp.zeros_like(y_out)

        # a head block's first step: the output block holds nothing yet
        # (it may be written back before any live row's step fills it)
        @pl.when(i == 0)
        def _through():
            s_out[...] = s_ref[...]


def standing_rows(live):
    """live bool [b] -> int32 [b]: the row whose state blocks a decode
    kernel's grid step stands on — a live row its own, a dead row the
    nearest live row before it (the first live row, for those before any; 0
    where none is live): block indices never go back, so a dead row's step
    fetches nothing and writes nothing back."""
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live).astype(jnp.int32)      # 0 where none is live
    return jnp.where(before < 0, first, before)


def _heads_per_step(h: int, p: int, n: int) -> int:
    """Heads a grid step holds: the state block, read and written and each
    double-buffered, within ~4 MiB of fast memory."""
    hb = h
    while hb > 8 and 4 * hb * p * n * 4 > 4 * 2 ** 20 and hb % 2 == 0:
        hb //= 2
    return hb


def ssm_decode_update(state, x, dt, a, b_vec, c_vec, d_skip, live=None,
                      interpret=None, backend=None):
    """`ssm_decode_update_xla` as one pass over the state: each [heads,
    p, n] block is read once, updated, reduced against C and written back
    to the buffer it came from (`input_output_aliases`), so a donated
    state leaf is updated in place.  `live` (bool [b]; None = every row)
    marks the rows that are sequences: a dead row's state is neither read
    nor written (its `dt` must be 0 all the same: the jnp form relies on
    it) and its y is 0.  The Pallas kernel on a TPU (or with
    `backend="pallas"`), the jnp form elsewhere."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    b, h, p, n = state.shape
    if live is None:
        live = jnp.ones((b,), bool)
    if backend == "xla":
        new, y = ssm_decode_update_xla(state, x, dt, a, b_vec, c_vec, d_skip)
        return new, jnp.where(live[:, None, None], y, 0.0)
    if interpret is None:
        interpret = _default_interpret()
    hb = _heads_per_step(h, p, n)
    f32 = jnp.float32
    rows = standing_rows(live)

    def row(hi, bi, rows, live):
        return (rows[bi], hi, 0)

    def head(hi, bi, rows, live):
        return (hi, 0)

    def vec(hi, bi, rows, live):
        return (rows[bi], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h // hb, b),
        in_specs=[
            pl.BlockSpec((1, hb, p, n),
                         lambda hi, bi, rows, live: (rows[bi], hi, 0, 0)),
            pl.BlockSpec((1, hb, p), row),
            pl.BlockSpec((1, hb, 1), row),
            pl.BlockSpec((hb, 1), head),
            pl.BlockSpec((1, 1, n), vec),
            pl.BlockSpec((1, 1, n), vec),
            pl.BlockSpec((hb, 1), head),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p, n),
                         lambda hi, bi, rows, live: (rows[bi], hi, 0, 0)),
            pl.BlockSpec((1, hb, p), lambda hi, bi, rows, live: (bi, hi, 0))],
    )
    with jax.named_scope("ssm_decode_update"):
        new, y = pl.pallas_call(
            _ssm_decode_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                       jax.ShapeDtypeStruct((b, h, p), f32)],
            # operand 2 (after the two prefetched scalars) is the state
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="ssm_decode_update",
        )(rows, live.astype(jnp.int32), state.astype(f32), x.astype(f32),
          dt.astype(f32)[..., None], a.astype(f32)[:, None],
          b_vec.astype(f32)[:, None, :], c_vec.astype(f32)[:, None, :],
          d_skip.astype(f32)[:, None])
    return new, y


# ------------------------------------------------ the selective recurrence


def selective_chunk_scan_xla(x, dt, a, b_mat, c_mat, d_skip, state):
    """A window of `s` positions from `state`, a position at a time: x, dt
    [b, s, e] (float32; dt after softplus, 0 at positions that do not
    count), a [n, e] (negative), b_mat / c_mat [b, s, n], d_skip [e], state
    [b, n, e] -> (y [b, s, e], state after the window).  The positions are
    unrolled, not a `lax.scan`: the solver's discovery EXECUTES a `scan`
    equation once a candidate sharding, which took a tiny chunk program 340
    s on the CPU; the unrolled positions are elementwise equations it knows
    by rule.  This form serves tests and rehearsals, at windows of tens."""
    ys = []
    for t in range(x.shape[1]):
        state, y = selective_decode_update_xla(
            state, x[:, t], dt[:, t], a, b_mat[:, t], c_mat[:, t], d_skip)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


# positions a turn of the scan kernel's inner loop takes: one sublane tile
# of x, dt and y
_SCAN_POSITIONS = 8
# positions whose B and C columns a turn of its outer loop loads: one lane
# tile of the transposed [n, s] operands
_SCAN_LANES = 128


def _channel_block(e: int, most: int = 512) -> int:
    """Channels a grid step of the selective kernels holds: the largest
    multiple of 128 lanes that divides `e` and is at most `most` ([16, 512]
    float32 is 8 vector registers of state, beside as many of `A`); all of
    them where `e` is no multiple of 128."""
    if e % 128:
        return e
    return 128 * _largest_divisor(e // 128, lambda m: 128 * m <= most)


def _selective_scan_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, s_ref,
                           y_ref, s_out, *, lanes: int):
    """x / dt / y [1, s, eb]; A [n, eb]; B / C transposed [1, n, s]; D [1,
    eb]; state [1, n, eb].  Grid (rows, channel blocks).  The block's state
    is the loop's carry — vector registers, for a block of 512 channels —
    from the first position to the last, and is stored once.  An outer turn
    loads `lanes` positions' B and C columns (a whole lane tile, or the
    whole window), an inner one `_SCAN_POSITIONS` rows of x and dt, and
    stores as many rows of y."""
    s = x_ref.shape[1]
    a, d = a_ref[...], d_ref[...]

    def turn(i, h):
        t0 = pl.multiple_of(i * lanes, lanes)
        bt = bt_ref[0, :, pl.ds(t0, lanes)]
        ct = ct_ref[0, :, pl.ds(t0, lanes)]
        for j0 in range(0, lanes, _SCAN_POSITIONS):
            at = pl.ds(t0 + j0, _SCAN_POSITIONS)
            xs, dts = x_ref[0, at, :], dt_ref[0, at, :]
            ys = []
            for j in range(_SCAN_POSITIONS):
                x, dt = xs[j:j + 1], dts[j:j + 1]               # [1, eb]
                col = slice(j0 + j, j0 + j + 1)                 # [n, 1]
                h = jnp.exp(dt * a) * h + (dt * x) * bt[:, col]
                ys.append(jnp.sum(h * ct[:, col], axis=0, keepdims=True)
                          + d * x)
            y_ref[0, at, :] = jnp.concatenate(ys, axis=0)
        return h

    s_out[0] = jax.lax.fori_loop(0, s // lanes, turn, s_ref[0])


@functools.lru_cache(maxsize=32)
def _selective_scan_call(b: int, sp: int, e: int, n: int, interpret: bool):
    """The scan's `pallas_call`, built ONCE a signature (`ops/
    flash_attention.py::_paged_call`'s reason: the function it returns is a
    `jax.jit`, so a model's second layer finds the first's trace — the
    body unrolls 128 positions, and traced a layer it was 15 s of a
    26-layer chunk program's start)."""
    eb = _channel_block(e)

    def row(bi, ei):
        return (bi, 0, ei)

    def cols(bi, ei):
        return (bi, 0, 0)

    def chan(bi, ei):
        return (0, ei)

    return pl.pallas_call(
        functools.partial(_selective_scan_kernel,
                          lanes=min(sp, _SCAN_LANES)),
        grid=(b, e // eb),
        in_specs=[pl.BlockSpec((1, sp, eb), row),
                  pl.BlockSpec((1, sp, eb), row),
                  pl.BlockSpec((n, eb), chan),
                  pl.BlockSpec((1, n, sp), cols),
                  pl.BlockSpec((1, n, sp), cols),
                  pl.BlockSpec((1, eb), chan),
                  pl.BlockSpec((1, n, eb), row)],
        out_specs=[pl.BlockSpec((1, sp, eb), row),
                   pl.BlockSpec((1, n, eb), row)],
        out_shape=[jax.ShapeDtypeStruct((b, sp, e), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, e), jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="selective_chunk_scan",
    )


def selective_chunk_scan(x, dt, a, b_mat, c_mat, d_skip, state,
                         interpret=None, backend=None):
    """`selective_chunk_scan_xla` as one kernel: grid (rows, blocks of
    channels), a block's [n, channels] state held in fast memory across
    ALL the window's positions — read once, written once, to the buffer it
    came from (`input_output_aliases`) — where a `lax.scan` takes it
    through HBM at every position.  The window is padded to whole tiles of
    positions with `dt = 0`, which leave the state as it was.  The Pallas
    kernel on a TPU (or with `backend="pallas"`), the jnp form elsewhere."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    x, dt, a, b_mat, c_mat, d_skip, state = (
        v.astype(jnp.float32) for v in (x, dt, a, b_mat, c_mat, d_skip,
                                        state))
    if backend == "xla":
        return selective_chunk_scan_xla(x, dt, a, b_mat, c_mat, d_skip,
                                        state)
    if interpret is None:
        interpret = _default_interpret()
    b, s, e = x.shape
    pad = -s % (_SCAN_POSITIONS if s <= _SCAN_LANES else _SCAN_LANES)
    if pad:
        x, dt, b_mat, c_mat = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                               for v in (x, dt, b_mat, c_mat))
    with jax.named_scope("selective_chunk_scan"):
        y, new = _selective_scan_call(b, s + pad, e, a.shape[0],
                                      bool(interpret))(
            x, dt, a, b_mat.swapaxes(1, 2), c_mat.swapaxes(1, 2),
            d_skip[None], state)
    return (y[:, :s] if pad else y), new


def selective_decode_update_xla(state, x, dt, a, b_vec, c_vec, d_skip):
    """The recurrence for one position: state [b, n, e], x / dt [b, e], a
    [n, e], b_vec / c_vec [b, n], d_skip [e] -> (state, y [b, e])."""
    state = jnp.exp(dt[:, None, :] * a) * state \
        + (dt * x)[:, None, :] * b_vec[:, :, None]
    return state, jnp.sum(state * c_vec[:, :, None], axis=1) + d_skip * x


def _selective_decode_kernel(row_ref, live_ref, s_ref, x_ref, dt_ref, a_ref,
                             b_ref, c_ref, d_ref, s_out, y_out):
    """state [1, n, eb]; x / dt / y [1, 1, eb]; A [n, eb]; B / C [1, n, 1];
    D [1, eb].  Grid (channel blocks, rows), rows innermost, a dead row
    standing on a live neighbour's blocks: `_ssm_decode_kernel`'s rule."""
    i = pl.program_id(1)

    @pl.when(live_ref[i] == 1)
    def _update():
        x, dt = x_ref[0], dt_ref[0]                              # [1, eb]
        new = jnp.exp(dt * a_ref[...]) * s_ref[0] + (dt * x) * b_ref[0]
        s_out[0] = new
        y_out[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True) \
            + d_ref[...] * x

    @pl.when(live_ref[i] == 0)
    def _dead():
        y_out[...] = jnp.zeros_like(y_out)

        @pl.when(i == 0)
        def _through():
            s_out[...] = s_ref[...]


@functools.lru_cache(maxsize=32)
def _selective_decode_call(b: int, n: int, e: int, interpret: bool):
    """The decode update's `pallas_call`, built once a signature, as
    `_selective_scan_call`."""
    # a row's whole [n, e] state a step while that is under 1 MiB (Jamba's
    # [16, 5120] is 320 KiB): fewer, longer copies
    eb = _channel_block(e, most=max(128, 2 ** 20 // (4 * n)))

    def row(ei, bi, rows, live):
        return (rows[bi], 0, ei)

    def chan(ei, bi, rows, live):
        return (0, ei)

    def vec(ei, bi, rows, live):
        return (rows[bi], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e // eb, b),
        in_specs=[pl.BlockSpec((1, n, eb), row),
                  pl.BlockSpec((1, 1, eb), row),
                  pl.BlockSpec((1, 1, eb), row),
                  pl.BlockSpec((n, eb), chan),
                  pl.BlockSpec((1, n, 1), vec),
                  pl.BlockSpec((1, n, 1), vec),
                  pl.BlockSpec((1, eb), chan)],
        out_specs=[pl.BlockSpec((1, n, eb), row),
                   pl.BlockSpec((1, 1, eb),
                                lambda ei, bi, rows, live: (bi, 0, ei))],
    )
    return pl.pallas_call(
        _selective_decode_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, n, e), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, e), jnp.float32)],
        # operand 2 (after the two prefetched scalars) is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_decode_update",
    )


def selective_decode_update(state, x, dt, a, b_vec, c_vec, d_skip, live=None,
                            interpret=None, backend=None):
    """`selective_decode_update_xla` as one pass over the state: each row's
    [n, channels] block is read once, updated, reduced against C and
    written back to the buffer it came from (`input_output_aliases`), so a
    donated state leaf is updated in place.  `live` (bool [b]; None = every
    row) marks the rows that are sequences: a dead row's state is neither
    read nor written (`standing_rows`; its `dt` must be 0 all the same: the
    jnp form relies on it) and its y is 0.  The Pallas kernel on a TPU (or
    with `backend="pallas"`), the jnp form elsewhere."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    b, n, e = state.shape
    if live is None:
        live = jnp.ones((b,), bool)
    state, x, dt, a, b_vec, c_vec, d_skip = (
        v.astype(jnp.float32) for v in (state, x, dt, a, b_vec, c_vec,
                                        d_skip))
    if backend == "xla":
        new, y = selective_decode_update_xla(state, x, dt, a, b_vec, c_vec,
                                             d_skip)
        return new, jnp.where(live[:, None], y, 0.0)
    if interpret is None:
        interpret = _default_interpret()
    with jax.named_scope("selective_decode_update"):
        new, y = _selective_decode_call(b, n, e, bool(interpret))(
            standing_rows(live), live.astype(jnp.int32), state, x[:, None],
            dt[:, None], a, b_vec[..., None], c_vec[..., None], d_skip[None])
    return new, y[:, 0]
