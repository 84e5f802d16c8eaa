"""The Mamba-2 (SSD) recurrence of a state layer, in its two serving forms.

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t      S [h, p, n]
    y_t = S_t . C_t + D * x_t

`ssd_chunk_scan` runs a window of positions from a state carried in and
gives the state carried out (chunked prefill); `ssm_decode_update` is the
same recurrence for one position a sequence (decode), a Pallas kernel on a
TPU that reads and writes each state once, in place.  `A` is a scalar a
head and B, C are shared by every head (one group).  A position whose `dt`
is 0 leaves the state as it was, bit for bit — exp(0) * S + 0 — which is
how the callers keep padded positions and dead rows out of it.  The state
is float32 throughout.  `causal_conv_tail` is the short conv with a carried
tail that sits in front of such a recurrence, shared with the delta rule's
mixer (`ops/delta_rule.py`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret

__all__ = ["causal_conv_tail", "ssd_chunk_scan", "ssm_decode_update",
           "ssm_decode_update_xla"]


def causal_conv_tail(tail, x, w, bias, valid):
    """The short causal depthwise conv in front of a state layer's
    recurrence (Mamba-2's, the gated delta rule's), over [carried tail |
    this window]: tail [b, taps - 1, c] (the last pre-activation inputs of
    the sequence so far, float32), x [b, s, c] (float32), w [taps, c] (row j
    multiplies the input taps - 1 - j positions back), bias [c] or None,
    valid bool [b, s] (a PREFIX of each row counts) -> (silu(conv) [b, s,
    c], the tail after the positions that count: with none of them, the
    tail as it was, bit for bit)."""
    s, taps = x.shape[1], w.shape[0]
    full = jnp.concatenate([tail, x], axis=1)                # [b, s+taps-1, c]
    w = w.astype(jnp.float32)
    conv = sum(full[:, j:j + s] * w[j] for j in range(taps))
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    out = jax.nn.silu(conv)
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    new_tail = jnp.take_along_axis(
        full, (n_valid[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)
    return out, new_tail


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


def ssd_chunk_scan(x, dt, a, b_mat, c_mat, d_skip, state, block: int = 256):
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
