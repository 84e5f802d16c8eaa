"""The gated delta rule of a linear-attention state layer (Gated DeltaNet),
in its two serving forms.

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T     S [d_v, d_k]
    o_t = S_t q_t                    a_t = exp(g_t) in (0, 1], b_t in [0, 2]

The transition is a decay times a rank-one correction, not a diagonal:
`ops/ssm.py` cannot compute it.  `delta_chunk_scan` runs a window of
positions from a state carried in and gives the state carried out (chunked
prefill); `delta_decode_update` is the same recurrence for one position a
sequence (decode), a Pallas kernel on a TPU that reads and writes each
state once, in place.  A position with g = 0 and b = 0 leaves the state as
it was, bit for bit — 1 * S + k (0 * ...) — which is how the callers keep
padded positions and dead rows out of it.  The state is float32 throughout
and every product of the scan is taken at `highest` precision.

THE STORED STATE.  Both forms take and give the state TRANSPOSED and with
the heads PACKED: [b, heads / pack, d_k, pack * d_v], the d_v columns of
`pack` consecutive heads side by side (`state_pack`, `pack_state`).  The
TPU tiles an array's two minor dimensions (8 x 128 float32), so a [96, 192]
matrix a head would be stored, read and written a third larger than it is;
two heads' columns side by side are 384 = 3 x 128 lanes and pad nothing.
With d_k on the sublanes, k^T S and S q are sums over sublanes and the
update an outer product of a column and a row: nothing is transposed in
the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret
from .ssm import standing_rows

__all__ = ["delta_chunk_scan", "delta_decode_update",
           "delta_decode_update_xla", "state_pack", "pack_state",
           "unpack_state"]

_HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128


def state_pack(heads: int, d_v: int) -> int:
    """How many consecutive heads share a stored row: the fewest that make
    it whole 128-lane tiles and divide the heads; 1 where none does."""
    for pack in range(1, heads + 1):
        if heads % pack == 0 and (pack * d_v) % LANES == 0:
            return pack
    return 1


def pack_state(s, pack: int):
    """[b, h, d_k, d_v] -> [b, h / pack, d_k, pack * d_v]."""
    b, h, d_k, d_v = s.shape
    return s.reshape(b, h // pack, pack, d_k, d_v).transpose(
        0, 1, 3, 2, 4).reshape(b, h // pack, d_k, pack * d_v)


def unpack_state(s, pack: int):
    """[b, h / pack, d_k, pack * d_v] -> [b, h, d_k, d_v]."""
    b, hp, d_k, width = s.shape
    return s.reshape(b, hp, d_k, pack, width // pack).transpose(
        0, 1, 3, 2, 4).reshape(b, hp * pack, d_k, width // pack)


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def _unit_lower_inverse(m):
    """The inverse of unit lower triangular matrices m [..., c, c], by
    blocks that double: where B inverts the diagonal blocks of s positions
    and L holds m's blocks under every other one of them, B - B L B inverts
    the diagonal blocks of 2 s — forward substitution a block at a time, as
    stable as it, in log2(c) steps of small matmuls.  (The Neumann product
    (I - N)(I + N^2)(I + N^4)... is fewer lines and cancels catastrophically
    once keys repeat and b nears 2.)"""
    c = m.shape[-1]
    at = jnp.arange(c)
    inv = jnp.broadcast_to(jnp.eye(c, dtype=m.dtype), m.shape)
    s = 1
    while s < c:
        under = ((at[:, None] // s) % 2 == 1) \
            & (at[None, :] // s == at[:, None] // s - 1)
        low = jnp.where(under, m, 0.0)
        inv = inv - _mm("...ij,...jk->...ik",
                        _mm("...ij,...jk->...ik", inv, low), inv)
        s *= 2
    return inv


def delta_chunk_scan(q, k, v, g, beta, state, block: int = 64):
    """A window of `s` positions from `state`: q, k [b, s, h, d_k] (k of
    unit norm, q scaled), v [b, s, h, d_v], g [b, s, h] (the log of the
    decay, <= 0) and beta [b, s, h] (both 0 at positions that do not
    count), all float32, state as it is stored [b, h / pack, d_k, pack *
    d_v] -> (o [b, s, h, d_v], state after the window).  `block` is how
    many positions are taken at once (a shorter window is one block); any
    value gives the same function.

    A block of c positions from S0, with G the cumulative sum of g inside
    it and Gamma[t, r] = exp(G_t - G_r) for r <= t (the WY form of the
    chunked delta rule, arXiv:2406.06484, with the decay of 2412.06464):

        T = (I + strictLower(diag(beta) (K K^T . Gamma)))^-1
        U = T diag(beta) (V - exp(G) . K S0^T)      the corrected values
        O = exp(G) . Q S0^T + lower(Q K^T . Gamma) U
        S = exp(G_c) S0 + (exp(G_c - G) . U)^T K

    T does not depend on the state, so every block's is made at once and
    only the last three lines run block after block."""
    with jax.named_scope("delta_chunk_scan"):
        b, s, h, d_k = q.shape
        pack = h // state.shape[1]
        block = min(block, s)
        nb = -(-s // block)
        pad = nb * block - s

        def blocks(x):      # [b, s, h, ...] -> [b, h, nb, block, ...]
            x = jnp.moveaxis(x, 2, 1)
            if pad:         # g = 0, beta = 0: positions that do not count
                x = jnp.pad(x, [(0, 0), (0, 0), (0, pad)]
                            + [(0, 0)] * (x.ndim - 3))
            return x.reshape(x.shape[:2] + (nb, block) + x.shape[3:])

        q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
        cum = jnp.cumsum(g, axis=-1)                     # [b, h, nb, c], <= 0
        seg = cum[..., :, None] - cum[..., None, :]      # G_t - G_r
        tri = jnp.tril(jnp.ones((block, block), bool))
        gamma = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        kk = _mm("bhntd,bhnrd->bhntr", k, k)
        t_inv = _unit_lower_inverse(
            jnp.eye(block, dtype=jnp.float32)
            + jnp.where(jnp.tril(tri, -1), beta[..., None] * gamma * kk, 0.0))
        qk = _mm("bhntd,bhnrd->bhntr", q, k) * gamma
        into = jnp.exp(cum)[..., None]                   # decay from S0 to t
        w = _mm("bhntr,bhnrv->bhntv", t_inv, beta[..., None] * v)
        y = _mm("bhntr,bhnrd->bhntd", t_inv, beta[..., None] * into * k)
        to_end = jnp.exp(cum[..., -1:] - cum)[..., None]
        st = unpack_state(state, pack)                   # S^T [b, h, d_k, d_v]
        outs = []
        for i in range(nb):
            u = w[:, :, i] - _mm("bhtd,bhdv->bhtv", y[:, :, i], st)
            outs.append(into[:, :, i] * _mm("bhtd,bhdv->bhtv", q[:, :, i], st)
                        + _mm("bhtr,bhrv->bhtv", qk[:, :, i], u))
            st = st * jnp.exp(cum[:, :, i, -1])[:, :, None, None] \
                + _mm("bhtd,bhtv->bhdv", k[:, :, i], to_end[:, :, i] * u)
        o = outs[0] if nb == 1 else jnp.concatenate(outs, axis=2)
        return jnp.moveaxis(o[:, :, :s], 1, 2), pack_state(st, pack)


def delta_decode_update_xla(state, q, k, v, g, beta):
    """The recurrence for one position: state as it is stored [b, h / pack,
    d_k, pack * d_v], q / k [b, h, d_k], v [b, h, d_v], g / beta [b, h]
    -> (state, o [b, h, d_v])."""
    pack = q.shape[1] // state.shape[1]
    st = unpack_state(state, pack) * jnp.exp(g)[:, :, None, None]
    u = beta[:, :, None] * (v - jnp.sum(st * k[..., None], axis=2))
    st = st + k[..., None] * u[:, :, None, :]
    return pack_state(st, pack), jnp.sum(st * q[..., None], axis=2)


def _delta_decode_kernel(row_ref, live_ref, s_ref, qk_ref, vab_ref, s_out,
                         o_out, *, pack, d_v):
    """state [1, pb, d_k, pack * d_v]; q and k [1, 1, 2, pack, pb, d_k];
    v, the decay and beta [1, 1, 3, pb, pack * d_v] (the last two already
    spread over their head's lanes); o [1, 1, pb, pack * d_v].  Grid
    (blocks of packs, rows), rows innermost (`ssm.standing_rows`): a dead
    row's blocks are those of a live neighbour
    (`row_ref`), which the pipeline neither fetches again nor writes back
    while the index stands, so it costs no state traffic and changes
    nothing."""
    i = pl.program_id(1)
    pb = s_ref.shape[1]

    @pl.when(live_ref[i] == 1)
    def _update():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, pack * d_v), 1)

        def spread(which):      # a head's column [d_k, 1] over its own lanes
            cols = [qk_ref[0, 0, which, p][:, :, None] for p in range(pack)]
            out = []
            for j in range(pb):
                x = cols[0][j]
                for p in range(1, pack):
                    x = jnp.where(lane >= p * d_v, cols[p][j], x)
                out.append(x)
            return out

        q, k = spread(0), spread(1)                      # [d_k, pack * d_v]
        v, decay, beta = vab_ref[0, 0, 0], vab_ref[0, 0, 1], vab_ref[0, 0, 2]
        for j in range(pb):
            st = s_ref[0, j] * decay[j:j + 1]
            u = beta[j:j + 1] * (v[j:j + 1] - jnp.sum(st * k[j], axis=0,
                                                      keepdims=True))
            st = st + k[j] * u
            s_out[0, j] = st
            o_out[0, 0, j:j + 1, :] = jnp.sum(st * q[j], axis=0,
                                              keepdims=True)

    @pl.when(live_ref[i] == 0)
    def _dead():
        o_out[...] = jnp.zeros_like(o_out)

        # a block's first step: the output block holds nothing yet (it may
        # be written back before any live row's step fills it)
        @pl.when(i == 0)
        def _through():
            s_out[...] = s_ref[...]


def _packs_per_step(hp: int, d_k: int, width: int) -> int:
    """Packs of heads a grid step holds: the state block, read and written
    and each double-buffered, within ~4 MiB of fast memory."""
    pb = hp
    while pb > 1 and 4 * pb * d_k * width * 4 > 4 * 2 ** 20:
        pb = max(d for d in range(1, pb) if hp % d == 0)
    return pb


def delta_decode_update(state, q, k, v, g, beta, live=None, interpret=None,
                        backend=None):
    """`delta_decode_update_xla` as one pass over the state: each block of
    packed heads is read once, decayed, corrected, read out against q and
    written back to the buffer it came from (`input_output_aliases`), so a
    donated state leaf is updated in place.  `live` (bool [b]; None = every
    row) marks the rows that are sequences: a dead row's state is neither
    read nor written (its g and beta must be 0 all the same: the jnp form
    relies on it) and its o is 0.  The Pallas kernel on a TPU (or with
    `backend="pallas"`), the jnp form elsewhere."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    b, hp, d_k, width = state.shape
    h, d_v = q.shape[1], v.shape[-1]
    pack = h // hp
    if live is None:
        live = jnp.ones((b,), bool)
    if backend == "xla":
        new, o = delta_decode_update_xla(state, q, k, v, g, beta)
        return new, jnp.where(live[:, None, None], o, 0.0)
    if interpret is None:
        interpret = _default_interpret()
    pb = _packs_per_step(hp, d_k, width)
    nblk = hp // pb
    f32 = jnp.float32
    rows = standing_rows(live)

    def lanes(x):       # a number a head [b, h] over its head's d_v lanes
        return jnp.broadcast_to(x[:, :, None], v.shape)

    # v, the decay and beta a row of lanes: [b, nblk, 3, pb, pack * d_v]
    vab = jnp.stack([v, lanes(jnp.exp(g)), lanes(beta)], axis=1).astype(
        f32).reshape(b, 3, nblk, pb, width).swapaxes(1, 2)
    # q and k a head in columns: [b, nblk, 2, pack, pb, d_k]
    qk = jnp.stack([q, k], axis=1).astype(f32).reshape(
        b, 2, nblk, pb, pack, d_k).transpose(0, 2, 1, 4, 3, 5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblk, b),
        in_specs=[
            pl.BlockSpec((1, pb, d_k, width),
                         lambda hi, bi, rows, live: (rows[bi], hi, 0, 0)),
            pl.BlockSpec((1, 1, 2, pack, pb, d_k),
                         lambda hi, bi, rows, live: (rows[bi], hi, 0, 0, 0,
                                                     0)),
            pl.BlockSpec((1, 1, 3, pb, width),
                         lambda hi, bi, rows, live: (rows[bi], hi, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, pb, d_k, width),
                         lambda hi, bi, rows, live: (rows[bi], hi, 0, 0)),
            pl.BlockSpec((1, 1, pb, width),
                         lambda hi, bi, rows, live: (bi, hi, 0, 0))],
    )
    with jax.named_scope("delta_decode_update"):
        new, o = pl.pallas_call(
            functools.partial(_delta_decode_kernel, pack=pack, d_v=d_v),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                       jax.ShapeDtypeStruct((b, nblk, pb, width), f32)],
            # operand 2 (after the two prefetched scalars) is the state
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="delta_decode_update",
        )(rows, live.astype(jnp.int32), state.astype(f32), qk, vab)
    return new, o.reshape(b, h, d_v)
