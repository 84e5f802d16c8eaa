"""Flash attention as Pallas TPU kernels (forward AND fused backward).

Blockwise online-softmax attention.  A grid step holds blocks of its OWN
side — Q blocks in the forward and dQ passes, K blocks in the dK/dV pass —
and walks the other side's blocks in loops of the body's own (`_walk`):

* **resident** — where a row's whole other side (K and V; in dK/dV its Q,
  dO, lse and delta) fits in VMEM beside the step's own blocks
  (`_step_shape`: the VMEM layout's bytes, double-buffered, against
  `_TRAIN_VMEM_BUDGET`) the grid's third axis has ONE step, the other side
  is fetched once a row, a step holds up to `_OWN_BLOCKS` own blocks, and
  each walks the blocks that hold something for it: up to the diagonal
  when causal, so no step is taken for a block that is masked out.  Where
  one grid step holds the row on both sides (the train cell: 4 x 4 blocks
  of 256) the walks' bounds are static and a row's ten block steps are
  unrolled, so the compiler schedules one step's products behind the
  next one's; where the grid position enters the bounds they are loops.
* **streamed** — where it does not fit (the 8k-32k contexts, ring
  attention's long blocks) a step holds one own block and the other side
  rides the grid's third axis, as many blocks a step as the budget takes
  (at least one): the walk covers the step's blocks up to the diagonal
  (none of a step wholly above it, which is not fetched either), and VMEM
  residency per program is bounded by the budget, not by the sequence
  length.  Statistics and accumulators persist in f32 VMEM scratch across
  those grid steps (TPU grids iterate sequentially; ``@pl.when(ki == 0)``
  initialises, ``@pl.when(ki == last)`` writes out).

The same body serves both; the shapes decide the walks' lengths.  The
causal mask is built only on the blocks the diagonal crosses; the blocks
under it take no iota, compare or select.

The MXU gets its operands in the dtype they were stored in and
accumulates in f32 (`_mxu`: the contraction is named, nothing is
transposed by hand).  Scores, running max and denominator, lse, delta and
the accumulators are f32; `p` and `ds` are rounded to the operands' dtype
for the products that consume them.  With f32 operands that is the
arithmetic the kernels always had.

All three kernels keep the scores [bk, bq], keys down the sublanes and
queries along the lanes: a query's max, denominator, lse and delta are a
lane each (a [1, bq] row: 2 vregs a 256-block, where [bq, 1] took 32 and a
lane broadcast a use), the reductions over keys run down the sublanes, and
``P^T`` / ``dS^T`` come off the VPU in the layout the next product wants.
The forward accumulates o^T [d, bq] and dQ accumulates dq^T, each turned
once a Q block at the write.  lse and delta travel as [rows, t / bq, 1,
bq]: dense in HBM, and a legal block whatever bq is.

The backward is the FlashAttention-2 recipe: the forward saves the per-row
logsumexp, ``delta = rowsum(dO * O)`` is precomputed in XLA, then two
kernels walk blocks — dQ accumulates over K blocks, dK/dV accumulate over
Q blocks — recomputing ``P = exp(S - lse)`` per block.  No [T, T] residual
survives the forward.  The ring variant composes this kernel with the
ppermute loop in parallel/ring_attention.py.

Each `pallas_call` is built once a signature and kept (`_forward_call`,
`_backward_calls`), as the paged kernels' is: a model's layers share one
traced body and one Mosaic lowering.

Reference scenario: the reference relies on torch SDPA/cutlass kernels
(benchmark/torch/model/gpt.py attention); this is the TPU-native analog.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easydist_tpu.kv.arena import plain_pages

_NEG_INF = -1e30
# What the blocks, scratch and block-step temporaries of a training kernel
# may take in VMEM for a row's other side to be held whole (`_resident`):
# half of the 16 MiB a v5e kernel may scope
_TRAIN_VMEM_BUDGET = 8 * 2 ** 20
# Blocks of its OWN side a grid step holds beside a resident other side,
# at most: each is a copy of the block step's code in the body
_OWN_BLOCKS = 4
# A walk whose bounds are static is unrolled up to this many trips
_UNROLL_TRIPS = 4


def _default_interpret() -> bool:
    """The Pallas interpreter everywhere but on a TPU, where the kernels
    compile natively (and a kernel Mosaic refuses fails the call)."""
    return jax.default_backend() != "tpu"


def _pick_block(block: int, t: int) -> int:
    b = min(block, t)
    while t % b:
        b //= 2
    return max(b, 1)


def _mxu(a, b, contract):
    """The product of 2-D `a` and `b` over axis `contract[0]` of a and
    `contract[1]` of b, f32: the operands go to the MXU in the dtype they
    share (bf16 products are exact in the f32 accumulator), in f32 where
    they differ, and the contraction is named, so neither is transposed by
    hand."""
    if a.dtype != b.dtype:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)),
                                      ((), ())),
                               preferred_element_type=jnp.float32)


_dot = functools.partial(_mxu, contract=(1, 0))     # a . b:   [m,n] x [n,d]
_dot_nt = functools.partial(_mxu, contract=(1, 1))  # a . b^T: [m,d] x [n,d]
_dot_tn = functools.partial(_mxu, contract=(0, 0))  # a^T . b: [n,d] x [n,m]


def _prescale(x, scale: float):
    """(x for the scores' product, what multiplies the f32 scores).  The
    operand takes the scale itself where that is exact — f32, as the
    scores would, or a power of two (a head of 64: 1/8), which moves no
    mantissa bit of a narrower dtype — once a block; else it goes to the
    MXU as stored and the scale multiplies the f32 scores."""
    if x.dtype == jnp.float32 or math.frexp(scale)[0] == 0.5:
        return x * scale, None
    return x, scale


def _scaled(s, s_scale):
    return s if s_scale is None else s * s_scale


def _causal_mask(s, q0, k0):
    """Scores [keys k0.., queries q0..] with the keys after each query at
    -inf."""
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos - q_pos <= q0 - k0, s, _NEG_INF)


def _clip(x, lo: int, hi: int):
    """x held to [lo, hi]; a Python int stays one (a static loop bound)."""
    return max(lo, min(hi, x)) if isinstance(x, int) else jnp.clip(x, lo, hi)


def _k_walk(causal: bool, qi, first, sub: int, block_q: int, block_k: int):
    """The K blocks Q block `qi` walks of the `sub` a grid step holds from
    block `first` on, as `_walk` takes them: those wholly under the
    diagonal unmasked, then those it crosses masked; none above it."""
    if not causal:
        return ((0, sub, False),)
    under = _clip((qi * block_q + 1) // block_k - first, 0, sub)
    live = _clip(((qi + 1) * block_q + block_k - 1) // block_k - first, 0,
                 sub)
    return ((0, under, False), (under, live, True))


def _q_walk(causal: bool, ki, first, sub: int, block_q: int, block_k: int):
    """The Q blocks K block `ki` walks of the `sub` a grid step holds from
    block `first` on: those the diagonal crosses masked, then those
    wholly under it unmasked; none above it."""
    if not causal:
        return ((0, sub, False),)
    live = _clip((ki * block_k) // block_q - first, 0, sub)
    under = _clip(((ki + 1) * block_k - 1 + block_q - 1) // block_q - first,
                  0, sub)
    return ((live, under, True), (under, sub, False))


def _walk(step, spans):
    """`step(j, masked)` for every j of each (lo, hi, masked) span in
    turn.  Bounds the grid position enters make a loop of the body's own;
    static ones (a row whose blocks one grid step holds on both sides)
    are unrolled up to `_UNROLL_TRIPS`, so that the compiler schedules
    one block step's products behind the next one's."""
    for lo, hi, masked in spans:
        if isinstance(lo, int) and isinstance(hi, int) \
                and hi - lo <= _UNROLL_TRIPS:
            for j in range(lo, hi):
                step(j, masked)
        else:
            jax.lax.fori_loop(
                lo, hi, lambda j, c, masked=masked: step(j, masked) or c, 0)


def _rows_of(j, block: int):
    if isinstance(j, int):
        return pl.ds(j * block, block)
    return pl.ds(pl.multiple_of(j * block, block), block)


def _first_block(axis: int, n_steps: int, blocks: int):
    """The first block of the `blocks` that the step at this position of
    grid `axis` holds: 0, statically, where the axis has one step."""
    return 0 if n_steps == 1 else pl.program_id(axis) * blocks


def _other_side_map(causal: bool, resident: bool, first_live):
    """Index map of the side a grid step walks, for grid (row, own step,
    other side's step).  Resident: the row's one block, whatever the
    position.  Streamed and causal: steps that hold nothing clamp to the
    nearest block that does (`first_live(own, other)`) — Pallas skips the
    DMA when the mapped index repeats, so blocks that are masked out cost
    no HBM traffic (and the body's loop takes no trip for them)."""
    if resident:
        return lambda bh, own, other: (bh, 0, 0)
    if not causal:
        return lambda bh, own, other: (bh, other, 0)
    return lambda bh, own, other: (bh, first_live(own, other), 0)


def _flash_cost(arrays, n_q, n_k, bq, bk, causal, matmuls):
    """`pl.CostEstimate` of one training call over `arrays` (its operands
    and results, `[b*h, t, d]` first): what the kernel executes, advisory
    to XLA and read by the auto-solver's cost model (jaxfront/bridge.py).
    Every (Q block, K block) pair the walks visit — causal: those not
    strictly above the diagonal — does `matmuls` products of 2 * bq * bk *
    d FLOPs and one exp per score; every array crosses HBM once."""
    bh, _, d = arrays[0].shape
    pairs = sum(min(n_k, ((qi + 1) * bq - 1) // bk + 1) for qi in range(n_q)) \
        if causal else n_q * n_k
    scores = bh * pairs * bq * bk
    return pl.CostEstimate(
        flops=2 * matmuls * scores * d, transcendentals=scores,
        bytes_accessed=sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
                           for a in arrays))


def _train_vmem_bytes(kernel: str, bq: int, bk: int, held: int, d: int,
                      dtype, own: int = 1) -> int:
    """VMEM a grid step of training kernel `kernel` takes with `own`
    blocks of its own side and `held` positions of the side it walks (one
    block of it streamed, the row's whole extent resident): its blocks in
    their VMEM layout, double-buffered as Pallas keeps them, the f32
    scratch, and the f32 [block, block] temporaries of one block step."""
    f32 = jnp.float32

    def blk(rows, cols=d, dt=dtype):
        return _vmem_block_bytes((rows, cols), dt)

    if kernel == "flash_fwd":       # q, o, lse | k, v | acc, m, l | s, p
        mine = 2 * blk(bq) + blk(1, bq, f32)
        blocks = 2 * blk(held)
        scratch = blk(d, bq, f32) + 2 * blk(1, bq, f32)
        work = 2 * blk(bk, bq, f32)
    elif kernel == "flash_bwd_dq":  # q, do, dq, lse, delta | k, v | acc
        mine = 3 * blk(bq) + 2 * blk(1, bq, f32)
        blocks = 2 * blk(held)
        scratch = blk(d, bq, f32)
        work = 4 * blk(bk, bq, f32)
    else:                           # k, v, dk, dv | q, do, lse, delta | 2 acc
        mine = 4 * blk(bk)
        blocks = 2 * blk(held) + 2 * (held // bq) * blk(1, bq, f32)
        scratch = 2 * blk(bk, d, f32)
        work = 4 * blk(bk, bq, f32)
    return 2 * (own * mine + blocks) + own * scratch + work


def _step_shape(kernel: str, bq: int, bk: int, t_q: int, t_k: int, d: int,
                dtype):
    """(blocks of its own side, positions of the side it walks) that a
    grid step of `kernel` holds, by what reckons under
    `_TRAIN_VMEM_BUDGET`.  Resident: the walked side's whole extent beside
    the largest divisor of the own side's blocks up to `_OWN_BLOCKS`.
    Else streamed: one own block, and as many blocks of the walked side as
    fit (a divisor of its blocks, at least one) — a long row takes fewer,
    longer grid steps, and fewer of them above the diagonal."""
    (t, block), n_own = ((t_q, bq), t_k // bk) \
        if kernel == "flash_bwd_dkv" else ((t_k, bk), t_q // bq)

    def fits(own, blocks):
        return _train_vmem_bytes(kernel, bq, bk, blocks * block, d, dtype,
                                 own) <= _TRAIN_VMEM_BUDGET

    own = _largest_divisor(n_own, lambda m: m <= _OWN_BLOCKS)
    if fits(own, t // block):
        return own, t
    return 1, block * _largest_divisor(t // block, lambda m: fits(1, m))


def _count_call(kernel: str, resident: bool, dtype) -> None:
    """`flash_train_calls{kernel, kv, operands}`, once per traced call."""
    from easydist_tpu.runtime import spans

    spans.count("flash_train_calls", kernel=kernel,
                kv="resident" if resident else "streamed",
                operands=jnp.dtype(dtype).name)


# ---------------------------------------------------------------- forward


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_scr, m_scr, l_scr,
                  *, causal: bool, scale: float, block_q: int, block_k: int,
                  n_qs: int, n_kv: int):
    """The scores lie [bk, bq], keys down the sublanes: a query's running
    max and denominator are a lane each ([1, bq]: 2 vregs a 256-block
    where [bq, 1] takes 32), the reductions over keys run down the
    sublanes, and the accumulator is o^T [d, bq], turned once a Q block."""
    ki = pl.program_id(2)
    own = q_ref.shape[1] // block_q         # Q blocks this step holds
    sub = k_ref.shape[1] // block_k         # K blocks this step holds
    first_q = _first_block(1, n_qs, own)
    first_k = _first_block(2, n_kv, sub)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        o_scr[...] = jnp.zeros_like(o_scr)

    for i in range(own):
        q, s_scale = _prescale(q_ref[0, pl.ds(i * block_q, block_q), :],
                               scale)                   # [bq, d]

        def step(j, masked, i=i, q=q, s_scale=s_scale):
            rows = _rows_of(j, block_k)
            k_blk = k_ref[0, rows, :]                   # [bk, d]
            v_blk = v_ref[0, rows, :]
            s = _scaled(_dot_nt(k_blk, q), s_scale)     # [bk, bq]
            if masked:
                s = _causal_mask(s, (first_q + i) * block_q,
                                 (first_k + j) * block_k)
            m_prev = m_scr[i]                           # [1, bq]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)             # [1, bq]
            m_scr[i] = m_new
            l_scr[i] = l_scr[i] * alpha + jnp.sum(p, axis=0, keepdims=True)
            o_scr[i] = o_scr[i] * alpha + _dot_tn(v_blk,
                                                  p.astype(v_blk.dtype))

        _walk(step, _k_walk(causal, first_q + i, first_k, sub, block_q,
                            block_k))

    @pl.when(ki == n_kv - 1)
    def _write():
        for i in range(own):
            l_safe = jnp.maximum(l_scr[i], 1e-30)       # [1, bq]
            o_ref[0, pl.ds(i * block_q, block_q), :] = \
                (o_scr[i] / l_safe).T.astype(o_ref.dtype)
            lse_ref[0, i] = m_scr[i] + jnp.log(l_safe)


def _kv_map(causal: bool, held: int, t_k: int, bq: int, bk: int):
    """K/V steps of `held` positions for grid (bh, qi, ks): streamed and
    causal, steps above the diagonal clamp to the step the diagonal is
    in."""
    return _other_side_map(
        causal, held == t_k,
        lambda qi, ks: jnp.minimum(ks, ((qi + 1) * bq - 1) // held))


def _own_map(bh, own, other):
    return (bh, own, 0)


def _own_stat_map(bh, own, other):
    return (bh, own, 0, 0)


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


@functools.lru_cache(maxsize=64)
def _forward_call(rows: int, t_q: int, t_k: int, d: int, dtypes,
                  causal: bool, scale: float, bq: int, bk: int,
                  interpret: bool):
    """(the forward's `pallas_call` over q, k, v `[rows, t, d]` of
    `dtypes`, whether K/V are resident), built ONCE a signature
    (`_paged_call`'s reason: what it returns is a `jax.jit`, so a model's
    second layer finds the first's trace, and a program's equations carry
    ONE kernel jaxpr, lowered to a Mosaic module once a lowering — a body
    that unrolls a row's block steps is dear to trace a layer)."""
    n_q, n_k = t_q // bq, t_k // bk
    own, held = _step_shape("flash_fwd", bq, bk, t_q, t_k, d, dtypes[1])
    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale,
                               block_q=bq, block_k=bk, n_qs=n_q // own,
                               n_kv=t_k // held)
    kv_map = _kv_map(causal, held, t_k, bq, bk)
    operands = [_aval((rows, t_q, d), dtypes[0]),
                _aval((rows, t_k, d), dtypes[1]),
                _aval((rows, t_k, d), dtypes[2])]
    out_shape = [
        _aval((rows, t_q, d), dtypes[0]),
        # a Q block's lse a row of lanes: the layout the kernels keep it in
        _aval((rows, n_q, 1, bq), jnp.float32),
    ]
    call = pl.pallas_call(
        kernel,
        grid=(rows, n_q // own, t_k // held),
        in_specs=[
            pl.BlockSpec((1, own * bq, d), _own_map),
            pl.BlockSpec((1, held, d), kv_map),
            pl.BlockSpec((1, held, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, own * bq, d), _own_map),
            pl.BlockSpec((1, own, 1, bq), _own_stat_map),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((own, d, bq), jnp.float32),
            pltpu.VMEM((own, 1, bq), jnp.float32),
            pltpu.VMEM((own, 1, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=_flash_cost(operands + out_shape, n_q, n_k, bq, bk,
                                  causal, matmuls=2),
        interpret=interpret,
        name="flash_fwd",
    )
    return jax.jit(call), held == t_k


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool):
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    call, resident = _forward_call(
        b * h, t_q, t_k, d, (q.dtype.name, k.dtype.name, v.dtype.name),
        bool(causal), float(scale), _pick_block(block_q, t_q),
        _pick_block(block_k, t_k), bool(interpret))
    _count_call("flash_fwd", resident, k.dtype)
    with jax.named_scope("flash_fwd"):
        out, lse = call(q.reshape(b * h, t_q, d), k.reshape(b * h, t_k, d),
                        v.reshape(b * h, t_k, d))
    return out.reshape(b, h, t_q, d), lse.reshape(b * h, t_q)


# --------------------------------------------------------------- backward


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal: bool, scale: float,
                         block_q: int, block_k: int, n_qs: int, n_kv: int):
    ki = pl.program_id(2)
    own = q_ref.shape[1] // block_q
    sub = k_ref.shape[1] // block_k
    first_q = _first_block(1, n_qs, own)
    first_k = _first_block(2, n_kv, sub)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    for i in range(own):
        mine = pl.ds(i * block_q, block_q)
        q, s_scale = _prescale(q_ref[0, mine, :], scale)
        do = do_ref[0, mine, :]
        lse = lse_ref[0, i]                             # [1, bq]
        delta = delta_ref[0, i]

        def step(j, masked, i=i, q=q, s_scale=s_scale, do=do, lse=lse,
                 delta=delta):
            rows = _rows_of(j, block_k)
            k_blk = k_ref[0, rows, :]
            v_blk = v_ref[0, rows, :]
            s = _scaled(_dot_nt(k_blk, q), s_scale)     # [bk, bq]
            if masked:
                s = _causal_mask(s, (first_q + i) * block_q,
                                 (first_k + j) * block_k)
            p = jnp.exp(s - lse)  # masked entries: exp(-inf) = 0
            ds = p * (_dot_nt(v_blk, do) - delta)
            dq_scr[i] = dq_scr[i] + _dot_tn(k_blk, ds.astype(k_blk.dtype))

        _walk(step, _k_walk(causal, first_q + i, first_k, sub, block_q,
                            block_k))

    @pl.when(ki == n_kv - 1)
    def _write():
        for i in range(own):
            dq_ref[0, pl.ds(i * block_q, block_q), :] = \
                (dq_scr[i] * scale).T.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                          scale: float, block_q: int, block_k: int,
                          n_ks: int, n_qv: int):
    qs = pl.program_id(2)
    own = k_ref.shape[1] // block_k         # K blocks this step holds
    sub = q_ref.shape[1] // block_q         # Q blocks this step holds
    first_k = _first_block(1, n_ks, own)
    first_q = _first_block(2, n_qv, sub)

    @pl.when(qs == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    for i in range(own):
        mine = pl.ds(i * block_k, block_k)
        k, s_scale = _prescale(k_ref[0, mine, :], scale)    # [bk, d]
        v = v_ref[0, mine, :]

        def step(j, masked, i=i, k=k, s_scale=s_scale, v=v):
            rows = _rows_of(j, block_q)
            q_blk = q_ref[0, rows, :]                   # [bq, d]
            do_blk = do_ref[0, rows, :]
            s = _scaled(_dot_nt(k, q_blk), s_scale)     # [bk, bq]
            if masked:
                s = _causal_mask(s, (first_q + j) * block_q,
                                 (first_k + i) * block_k)
            p = jnp.exp(s - lse_ref[0, j])              # lse: [1, bq]
            dv_scr[i] = dv_scr[i] + _dot(p.astype(do_blk.dtype), do_blk)
            ds = p * (_dot_nt(v, do_blk) - delta_ref[0, j])
            dk_scr[i] = dk_scr[i] + _dot(ds.astype(q_blk.dtype), q_blk)

        _walk(step, _q_walk(causal, first_k + i, first_q, sub, block_q,
                            block_k))

    @pl.when(qs == n_qv - 1)
    def _write():
        for i in range(own):
            mine = pl.ds(i * block_k, block_k)
            dk_ref[0, mine, :] = (dk_scr[i] * scale).astype(dk_ref.dtype)
            dv_ref[0, mine, :] = dv_scr[i].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=64)
def _backward_calls(rows: int, t_q: int, t_k: int, d: int, dtypes,
                    causal: bool, scale: float, bq: int, bk: int,
                    interpret: bool):
    """(dQ's `pallas_call`, dK/dV's, whether each holds the side it walks
    whole) over q, k, v, dO `[rows, t, d]` of `dtypes` and lse, delta
    `[rows, t_q / bq, 1, bq]`, built once a signature as `_forward_call`."""
    n_q, n_k = t_q // bq, t_k // bk
    operands = [_aval((rows, t_q, d), dtypes[0]),
                _aval((rows, t_k, d), dtypes[1]),
                _aval((rows, t_k, d), dtypes[2]),
                _aval((rows, t_q, d), dtypes[3]),
                _aval((rows, n_q, 1, bq), jnp.float32),
                _aval((rows, n_q, 1, bq), jnp.float32)]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    own, held = _step_shape("flash_bwd_dq", bq, bk, t_q, t_k, d, dtypes[1])
    kv_map = _kv_map(causal, held, t_k, bq, bk)
    dq_call = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, n_qs=n_q // own,
                          n_kv=t_k // held),
        grid=(rows, n_q // own, t_k // held),
        in_specs=[
            pl.BlockSpec((1, own * bq, d), _own_map),
            pl.BlockSpec((1, held, d), kv_map),
            pl.BlockSpec((1, held, d), kv_map),
            pl.BlockSpec((1, own * bq, d), _own_map),
            pl.BlockSpec((1, own, 1, bq), _own_stat_map),
            pl.BlockSpec((1, own, 1, bq), _own_stat_map),
        ],
        out_specs=pl.BlockSpec((1, own * bq, d), _own_map),
        out_shape=operands[0],
        scratch_shapes=[pltpu.VMEM((own, d, bq), jnp.float32)],
        compiler_params=params,
        cost_estimate=_flash_cost(operands + operands[:1], n_q, n_k, bq, bk,
                                  causal, matmuls=3),
        interpret=interpret,
        name="flash_bwd_dq",
    )
    kv_resident = held == t_k

    own, held = _step_shape("flash_bwd_dkv", bq, bk, t_q, t_k, d, dtypes[0])
    # streamed and causal: Q steps above the K block's first row clamp to
    # the first that contributes
    q_map = _other_side_map(
        causal, held == t_q,
        lambda ki, qs: jnp.maximum(qs, (ki * bk) // held))

    def stat_map(bh, ki, qb):
        return q_map(bh, ki, qb) + (0,)

    dkv_call = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, n_ks=n_k // own,
                          n_qv=t_q // held),
        grid=(rows, n_k // own, t_q // held),
        in_specs=[
            pl.BlockSpec((1, held, d), q_map),
            pl.BlockSpec((1, own * bk, d), _own_map),
            pl.BlockSpec((1, own * bk, d), _own_map),
            pl.BlockSpec((1, held, d), q_map),
            pl.BlockSpec((1, held // bq, 1, bq), stat_map),
            pl.BlockSpec((1, held // bq, 1, bq), stat_map),
        ],
        out_specs=[
            pl.BlockSpec((1, own * bk, d), _own_map),
            pl.BlockSpec((1, own * bk, d), _own_map),
        ],
        out_shape=operands[1:3],
        scratch_shapes=[
            pltpu.VMEM((own, bk, d), jnp.float32),
            pltpu.VMEM((own, bk, d), jnp.float32),
        ],
        compiler_params=params,
        cost_estimate=_flash_cost(operands + operands[1:3], n_q, n_k, bq, bk,
                                  causal, matmuls=4),
        interpret=interpret,
        name="flash_bwd_dkv",
    )
    return jax.jit(dq_call), jax.jit(dkv_call), kv_resident, held == t_q


def _flash_backward(q, k, v, o, lse, g, causal: bool, scale: float,
                    block_q: int, block_k: int, interpret: bool,
                    g_lse=None):
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    bq = _pick_block(block_q, t_q)
    dq_call, dkv_call, kv_resident, q_resident = _backward_calls(
        b * h, t_q, t_k, d,
        (q.dtype.name, k.dtype.name, v.dtype.name, g.dtype.name),
        bool(causal), float(scale), bq, _pick_block(block_k, t_k),
        bool(interpret))
    _count_call("flash_bwd_dq", kv_resident, k.dtype)
    _count_call("flash_bwd_dkv", q_resident, q.dtype)

    dof = g.reshape(b * h, t_q, d)
    of = o.reshape(b * h, t_q, d)
    # delta_i = sum_d dO_i O_i — O(T) rowwise, plain XLA; an lse cotangent
    # enters with opposite sign (dL/ds += g_lse * p)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.reshape(b * h, t_q).astype(jnp.float32)
    # positions on the lanes, a Q block a row of the second axis: what
    # broadcasts down the [bk, bq] scores (and a block whose last two dims
    # are the array's is legal whatever bq is)
    operands = [q.reshape(b * h, t_q, d), k.reshape(b * h, t_k, d),
                v.reshape(b * h, t_k, d), dof,
                lse.reshape(b * h, t_q // bq, 1, bq),
                delta.reshape(b * h, t_q // bq, 1, bq)]
    with jax.named_scope("flash_bwd_dq"):
        dq = dq_call(*operands)
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv_call(*operands)
    return (dq.reshape(b, h, t_q, d), dk.reshape(b, h, t_k, d),
            dv.reshape(b, h, t_k, d))


def estimate_vmem_bytes(t_q: int, t_k: int, d: int, block_q: int = 256,
                        block_k: int = 256, dtype=jnp.float32) -> int:
    """Worst-case per-program VMEM residency across the three kernels as
    they are built for these shapes and this operand dtype (blocks in
    their VMEM layout, double-buffered; f32 scratch; a block step's f32
    temporaries): a row's other side whole where that stays under
    `_TRAIN_VMEM_BUDGET`, else one block of it — independent of the
    sequence length from there on, the long-context guarantee."""
    bq = _pick_block(block_q, t_q)
    bk = _pick_block(block_k, t_k)

    def reckon(kernel):
        own, held = _step_shape(kernel, bq, bk, t_q, t_k, d, dtype)
        return _train_vmem_bytes(kernel, bq, bk, held, d, dtype, own)

    return max(map(reckon, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")))


def _reference_attention(q, k, v, causal: bool, scale: float):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = q.shape[2], k.shape[2]
        qi = jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 1)
        s = jnp.where(ki <= qi, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_lse(q, k, v, causal: bool = True,
                        scale: Optional[float] = None, block_q: int = 256,
                        block_k: int = 256,
                        interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    [batch*heads, seq] (f32) — differentiable in BOTH outputs, which ring
    attention needs (the online merge weights blocks by their lse)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret)


def _fwd_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = flash_attention_lse(q, k, v, causal, scale, block_q, block_k,
                                   interpret)
    return (out, lse), (q, k, v, out, lse)


def _bwd_lse(causal, scale, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse = res
    g, g_lse = cts
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    # dL/ds_ij = p_ij * (dp_ij - delta_i) + g_lse_i * p_ij: the lse
    # cotangent folds into delta (delta' = delta - g_lse), so the same
    # kernels serve both outputs
    return _flash_backward(q, k, v, o, lse, g, causal, scale, block_q,
                           block_k, interpret,
                           g_lse=None if g_lse is None else g_lse)


flash_attention_lse.defvjp(_fwd_lse, _bwd_lse)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None, block_q: int = 256,
                    block_k: int = 256, interpret: Optional[bool] = None):
    """q, k, v: [batch, heads, seq, head_dim].  Returns same shape.

    `interpret=None` auto-selects the Pallas interpreter off-TPU so tests
    run on CPU; on TPU the kernels compile natively.
    """
    out, _ = flash_attention_lse(q, k, v, causal, scale, block_q, block_k,
                                 interpret)
    return out


# ------------------------------------------------- single-query decode


def _decode_block_update(q, k_blk, v_blk, k0, length, scale, o_scr, m_scr,
                         l_scr):
    """Online-softmax update of `r` query rows per KV head against one K/V
    block, shared by the decode kernels and the paged chunk kernel: q
    [g, r, d], k_blk/v_blk [g, t, d] (g KV heads, r = the GQA group; 1, 1
    for the contiguous kernel; group x chunk for a chunk of queries), each
    still in the dtype it was stored in.  `k0` is the block's first cache
    position and positions >= `length` are masked: a scalar for one query a
    row (unwritten slots, not future tokens), int32 [1, r, 1] where every
    query row has its own (the chunk kernel: position + 1, which is the
    causal mask too).  q . K^T goes to the MXU in the operands' own
    dtype when they share one (bf16 products are exact in the f32
    accumulator) and in f32 otherwise; scores, statistics, p and the
    accumulator are f32, and p . V runs in f32."""
    if q.dtype != k_blk.dtype:
        q, k_blk = q.astype(jnp.float32), k_blk.astype(jnp.float32)
    s = jnp.einsum("grd,gtd->grt", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(k_pos < length, s, _NEG_INF)
    m_prev = m_scr[...]                                 # [g, r, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_scr[...] = o_scr[...] * alpha + jnp.einsum(
        "grt,gtd->grd", p, v_blk.astype(jnp.float32),
        preferred_element_type=jnp.float32)


def _decode_init(o_scr, m_scr, l_scr):
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    o_scr[...] = jnp.zeros_like(o_scr)


def _decode_result(o_scr, l_scr):
    return o_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def _decode_scratch(g: int, r: int, d: int):
    return [pltpu.VMEM((g, r, d), jnp.float32),
            pltpu.VMEM((g, r, 1), jnp.float32),
            pltpu.VMEM((g, r, 1), jnp.float32)]


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, o_scr, m_scr,
                         l_scr, *, scale: float, block_k: int, n_k: int,
                         heads: int):
    """One query row against a streamed K/V cache: the forward kernel with
    bq=1 and the causal mask replaced by a per-row length mask."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _decode_init(o_scr, m_scr, l_scr)

    length = len_ref[jax.lax.div(pl.program_id(0), heads)]

    @pl.when(ki * block_k < length)
    def _compute():
        _decode_block_update(q_ref[...], k_ref[...], v_ref[...],
                             ki * block_k, length, scale, o_scr, m_scr, l_scr)

    @pl.when(ki == n_k - 1)
    def _write():
        o_ref[...] = _decode_result(o_scr, l_scr).astype(o_ref.dtype)


def flash_decode_attention(q, k, v, lengths, scale: Optional[float] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Single-query flash attention against a KV cache (the decode step).

    q: [batch, heads, head_dim] — ONE query per sequence; k, v: [batch,
    heads, max_len, head_dim] cache buffers; lengths: int32 [batch] valid
    prefix length per row (positions >= length are masked).  Returns
    [batch, heads, head_dim].  VMEM residency is O(block_k), independent
    of the cache length.  The lengths ride scalar prefetch (SMEM-resident
    before the grid runs — a blocked SMEM operand is not a legal TPU
    block).
    """
    from easydist_tpu import config as edconfig

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if block_k is None:
        block_k = edconfig.decode_block_k
    if interpret is None:
        interpret = _default_interpret()
    b, h, d = q.shape
    t_k = k.shape[2]
    bk = _pick_block(block_k, t_k)
    n_k = t_k // bk

    qf = q.reshape(b * h, 1, d)
    kf = k.reshape(b * h, t_k, d)
    vf = v.reshape(b * h, t_k, d)

    kernel = functools.partial(_flash_decode_kernel, scale=scale,
                               block_k=bk, n_k=n_k, heads=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bh, ki, len_ref: (bh, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, len_ref: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, len_ref: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d),
                               lambda bh, ki, len_ref: (bh, 0, 0)),
        scratch_shapes=_decode_scratch(1, 1, d),
    )
    with jax.named_scope("flash_decode"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="flash_decode",
        )(jnp.asarray(lengths, jnp.int32), qf, kf, vf)
    return out.reshape(b, h, d)


def _decode_attention_xla(q, k, v, lengths, scale: float):
    """Masked dot_general decode path — the off-TPU fallback, and the
    numerical reference the kernel is tested against.  Masking matches the
    models' einsum path (-1e30 fill, softmax over the full cache length)
    so cached and uncached greedy decode agree argmax-exactly."""
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(k_pos < lengths.astype(jnp.int32)[:, None, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_attention(q, k, v, lengths, scale: Optional[float] = None,
                     backend: Optional[str] = None):
    """Backend-dispatching decode attention (the models' decode steps call
    this): the Pallas single-query kernel on TPU, the masked dot_general
    path elsewhere.  `EASYDIST_DECODE_ATTENTION` forces either
    ("flash"/"xla"); the choice is part of the strategy-cache salt."""
    from easydist_tpu import config as edconfig

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (q.shape[0],))
    if backend is None:
        backend = edconfig.decode_attention_backend
    if backend == "paged":
        # "paged" selects the page-gathering kernel in paged_decode_attention;
        # contiguous callers degrade to auto (there is no table to chase)
        backend = "auto"
    if backend == "auto":
        backend = "flash" if jax.default_backend() == "tpu" else "xla"
    if backend == "flash":
        return flash_decode_attention(q, k, v, lengths, scale=scale)
    if backend == "xla":
        return _decode_attention_xla(q, k, v, lengths, scale)
    raise ValueError(f"unknown decode attention backend {backend!r}; "
                     f"expected auto|flash|xla|paged")


# ------------------------------------------------- paged decode


def gather_pages(pages, table, n_heads: Optional[int] = None):
    """Materialize the contiguous "virtual cache" a page table describes.

    pages: [n_pages, kv_heads, page_tokens, d] (one layer of the arena);
    table: int32 [batch, max_pages] arena page per window (sentinel
    `n_pages` for unmapped).  Returns [batch, heads, max_pages *
    page_tokens, d]: sentinel entries clip to the last real page, whose
    rows sit at masked positions (>= the row's length) so their softmax
    weight is exactly zero — garbage values are unobservable as long as
    they are finite, which arena zeros/stale KV always are.  `n_heads`
    repeats kv_heads GQA-style AFTER the gather, matching the contiguous
    llama path's repeat-then-attend order bitwise."""
    n_pages, kvh, pt, d = pages.shape
    b, mp = table.shape
    idx = jnp.clip(table.astype(jnp.int32), 0, n_pages - 1)
    v = jnp.take(pages, idx, axis=0)                 # [b, mp, kvh, pt, d]
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, kvh, mp * pt, d)
    if n_heads is not None and n_heads != kvh:
        v = jnp.repeat(v, n_heads // kvh, axis=1)
    return v


def _paged_decode_attention_xla(q, k_pages, v_pages, table, lengths,
                                scale: float):
    """Gather-then-mask fallback: reconstruct the virtual contiguous cache
    through the page table, then run the exact `_decode_attention_xla`
    einsum.  When max_pages * page_tokens equals a contiguous cache's
    length, every downstream shape (and therefore the lowered reduction
    order) matches that cache's path — the bitwise-parity spine of the
    paged kernel tests."""
    h = q.shape[1]
    kf = gather_pages(k_pages, table, n_heads=h)
    vf = gather_pages(v_pages, table, n_heads=h)
    return _decode_attention_xla(q, kf, vf, lengths, scale)


# ------------------------------------------- block-scaled int8 KV pages
#
# The EQuARX idiom (comm/quant.py) applied to KV pages: each K/V row is
# split into `n_blocks` equal head-dim blocks, every block carries one f32
# scale (amax / 127), and the payload is stored int8.  `jnp.rint` is
# round-half-to-even — deterministic, so re-prefilling the same token
# prefix reproduces quantized pages BITWISE (the crash-resume parity the
# int8 fleet-chaos wave gates).  Scales live in a parallel scale arena
# ({"k_scale", "v_scale"}: [..., page_tokens, n_blocks] f32) that rides
# the same page table indices as the payload.

_KV_QMAX = 127.0


def kv_quantize(x, n_blocks: int):
    """Block-scaled int8 over the LAST dim of `x` [..., d] with d split
    into `n_blocks` equal blocks.  Returns (q int8 [..., d],
    scales f32 [..., n_blocks]); all-zero blocks get scale 1.0 so
    dequantization is exact for them."""
    d = x.shape[-1]
    if d % n_blocks:
        raise ValueError(f"head_dim {d} not a multiple of n_blocks "
                         f"{n_blocks}")
    block = d // n_blocks
    xb = x.astype(jnp.float32).reshape(*x.shape[:-1], n_blocks, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / _KV_QMAX, 1.0)
    q = jnp.clip(jnp.rint(xb / scale), -_KV_QMAX, _KV_QMAX).astype(jnp.int8)
    return q.reshape(x.shape), scale[..., 0]


def kv_dequantize(q, scales, dtype=jnp.float32):
    """Inverse of `kv_quantize`: q int8 [..., d], scales f32
    [..., n_blocks] -> [..., d] in `dtype`."""
    d = q.shape[-1]
    nb = scales.shape[-1]
    block = d // nb
    xb = q.astype(jnp.float32).reshape(*q.shape[:-1], nb, block)
    return (xb * scales[..., None]).reshape(q.shape).astype(dtype)


def _paged_decode_attention_quant_xla(q, k_pages, v_pages, k_scale,
                                      v_scale, table, lengths,
                                      scale: float):
    """Quantized gather-then-mask fallback: gather int8 payload AND scale
    pages through the same table, dequantize to f32, then run the exact
    `_decode_attention_xla` einsum — the numerical reference the quant
    kernel is tested against."""
    h = q.shape[1]
    kf = kv_dequantize(gather_pages(k_pages, table, n_heads=h),
                       gather_pages(k_scale, table, n_heads=h))
    vf = kv_dequantize(gather_pages(v_pages, table, n_heads=h),
                       gather_pages(v_scale, table, n_heads=h))
    return _decode_attention_xla(q, kf, vf, lengths, scale)


# One window of a paged kernel's walk — one turn of its loop: the pages it
# copies together, and behind which it starts the next window's — covers up
# to this many tokens of a row (swept on a v5e at 32 slots, 8 KV heads of
# 128, 64-token pages: a call alone is within 4 us of its best from 2 to 16
# pages a window, at 5 live rows and at 32, and 5-30 % slower at ONE, whose
# copies nothing hides; PERF.md section 6, PR 42) ...
_PAGED_STEP_TOKENS = 256
# ... as long as what the window holds in VMEM stays under this: half of
# what a v5e kernel may scope.  A step's shape is CHOSEN under it; a step
# that has no smaller shape to take (one KV head, one page) may reckon over
# it and still run (K-EXAONE's chunk kernel: 2,048 query rows a KV head,
# 9.75 MiB) ...
_PAGED_VMEM_BUDGET = 8 * 2 ** 20
# ... up to the 16 MiB a v5e kernel may scope, over which Mosaic refuses it
_PAGED_VMEM_LIMIT = 16 * 2 ** 20


def _vmem_block_bytes(shape, dtype) -> int:
    """Bytes a block takes in VMEM: the minor dim padded to 128 lanes, the
    one before it to the dtype's sublane tile (8 rows of 32 bits)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sublanes = 8 * max(4 // itemsize, 1)
    return (math.prod(lead) * -(-rows // sublanes) * sublanes
            * -(-lanes // 128) * 128 * itemsize)


def _largest_divisor(n: int, fits) -> int:
    return next((m for m in range(n, 1, -1) if n % m == 0 and fits(m)), 1)


def _paged_step_bytes(pages, g: int, n: int, rows: int = 0) -> int:
    """VMEM one grid step of the paged kernels takes with g KV heads and
    windows of n pages: every `pages` operand's window in its two slots,
    and the f32 working copies of one page's K and V.  `rows` query rows a KV head
    count where they are many (a chunk of queries; a decode round's GQA
    group is left out): q and the output double-buffered, the accumulator
    and the two statistics, and a page's f32 scores and probabilities."""
    _, _, pt, d = pages[0].shape
    step = (2 * n * sum(_vmem_block_bytes((g, pt, a.shape[-1]), a.dtype)
                        for a in pages)
            + 4 * _vmem_block_bytes((g, pt, d), jnp.float32))
    if rows:
        step += (4 * _vmem_block_bytes((g, rows, d), pages[0].dtype)
                 + _vmem_block_bytes((g, rows, d), jnp.float32)
                 + 2 * _vmem_block_bytes((g, rows, 1), jnp.float32)
                 + 2 * _vmem_block_bytes((g, rows, pt), jnp.float32))
    return step


def _paged_step_shape(max_pages: int, pages, want: Optional[int] = None,
                      rows: int = 0):
    """(KV heads a grid step of the paged kernels holds, pages a window of
    its walk), from the shapes and dtypes of the `pages` operands ([n_pages,
    kv_heads, page_tokens, *] each; K first) and the query `rows` a KV head
    (`_paged_step_bytes`) alone.  Heads: all of them — a page is then one
    contiguous block — unless one such page is over `_PAGED_VMEM_BUDGET`
    (then the largest divisor of kv_heads that fits).  Pages: the largest
    divisor of `max_pages` that is at most `want` (default:
    `_PAGED_STEP_TOKENS` worth) and fits the budget.  (1, 1) is what is
    left where nothing fits the budget; it is refused where it reckons
    over `_PAGED_VMEM_LIMIT` — the chunk kernel then takes blocks of the
    group's query heads first (`_query_head_block`)."""
    _, kvh, pt, _ = pages[0].shape
    g = _largest_divisor(
        kvh, lambda g: _paged_step_bytes(pages, g, 1, rows)
        <= _PAGED_VMEM_BUDGET)
    least = _paged_step_bytes(pages, g, 1, rows)
    if least > _PAGED_VMEM_LIMIT:
        raise ValueError(
            f"one KV head's step of the paged kernels ({rows} query rows, "
            f"pages of {pt}) reckons to {least} bytes of VMEM, over the "
            f"{_PAGED_VMEM_LIMIT} a kernel may scope")
    if want is None:
        want = max(_PAGED_STEP_TOKENS // pt, 1)
    n = _largest_divisor(
        max_pages, lambda n: n <= want
        and _paged_step_bytes(pages, g, n, rows) <= _PAGED_VMEM_BUDGET)
    return g, n


def _query_head_block(pages, group: int, rows: int) -> int:
    """The query heads of a KV head's GQA group that one grid step of the
    chunk kernel holds, `rows` query rows each: the whole group wherever
    one KV head's step can run with it (`_PAGED_VMEM_LIMIT`: every shape
    served before a group of 20 came) — a row's pages then leave HBM once a
    KV head — else the largest divisor of `group` whose step fits
    `_PAGED_VMEM_BUDGET`, every block walking the row's pages again
    (`_latent_head_block`'s rule, at the K/V kernel's own bytes: 20 query
    heads of 256 rows on ONE KV head reckon to 23.25 MiB whole, 6.4 MiB in
    blocks of 5)."""
    def step(hb):
        return _paged_step_bytes(pages, 1, 1, hb * rows)

    if step(group) <= _PAGED_VMEM_LIMIT:
        return group
    return _largest_divisor(group, lambda hb: step(hb) <= _PAGED_VMEM_BUDGET)


def _row_parts(pages) -> int:
    """The positions of a page that share a 128-lane row in the form a paged
    kernel takes its `pages` operands (`_whole_lanes`): 128 / head_dim where
    every operand has that one narrow minor dim and a page's positions
    divide so (heads of 64: 2), else 1."""
    _, _, pt, w = pages[0].shape
    parts = 128 // w if w < 128 and 128 % w == 0 else 1
    same = all(a.shape[-1] == w for a in pages)
    return parts if same and pt % parts == 0 else 1


def _page_views(pages, d: int):
    """Each `pages` operand as [n_pages, heads, page_tokens, w] whichever
    way it is STORED (shape and dtype alone): a lane-dense leaf
    (`kv/arena.py`: [.., page_tokens / parts, parts * d], wider than the
    query's head d) as the plain leaf it holds, any other as it is.  Every
    shape rule of the paged kernels reckons from these, so a lane-dense
    leaf is stepped through exactly as the plain one it replaces."""
    return [jax.eval_shape(lambda a: plain_pages(a, d), a) for a in pages]


def _whole_lanes(a, parts: int):
    """`a` [n_pages, heads, page_tokens, w] as a paged kernel takes it, in
    whole 128-lane rows: as it is where w is whole tiles (heads of 128 or
    256, a 640-wide latent row); `parts` consecutive positions to a row
    where they fill one (`_row_parts`; [.., page_tokens / parts, 128], row
    r the positions r * parts + i); else the minor dim zero-padded (a
    width that divides no 128, as 96).  A leaf the arena STORES lane-dense
    already lies so and never comes here (`_paged_attend`).  The kernels
    copy pages by hand, and Mosaic slices an HBM ref along whole tiles only ("Slice shape
    along dimension 3 must be aligned to tiling (128)", v5e, libtpu
    0.0.34).  A narrow operand costs a copy of the leaf a call, as it did
    the BlockSpec form before the walk was the kernel's own: a v5e keeps
    such a leaf with its PAGES on the lanes ({0,3,2,1}) and a Mosaic call
    takes it row-major, which XLA made of it with every row padded to 128
    lanes (PERF.md section 6, PR 42)."""
    *lead, pt, w = a.shape
    if parts > 1:
        return a.reshape(*lead, pt // parts, parts * w)
    pad = -w % 128
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),)) if pad else a


def _use_kernel(backend: str, knob: str) -> bool:
    """The paged dispatchers' one rule: "paged" / "flash" pick the kernel,
    "xla" the gather path, "auto" the kernel on a TPU and the gather path
    elsewhere."""
    if backend == "auto":
        return jax.default_backend() == "tpu"
    if backend not in ("paged", "flash", "xla"):
        raise ValueError(f"unknown {knob} attention backend {backend!r}; "
                         f"expected auto|paged|flash|xla")
    return backend != "xla"


def _q_map(bi, gi, tbl_ref, len_ref):
    return (bi, gi, 0, 0)


@functools.lru_cache(maxsize=128)
def _paged_call(body, name: str, scale: float, q_view, pages, max_pages: int,
                pages_per_step: Optional[int], interpret: bool,
                chunk: int = 0, out_dim: Optional[int] = None,
                q_blocks: int = 1):
    """The pallas_call the paged kernels share (`name`: `paged_decode`,
    `paged_decode_int8`, `paged_chunk`, `latent_decode` or `latent_chunk`,
    as the trace shows it), built
    ONCE a signature: `q_view` and each of `pages` are (shape, dtype name).
    A model's layers call a kernel at one signature, and the function this
    returns is a `jax.jit`, so the second layer's call finds the first's
    trace: a program's equations then carry ONE kernel jaxpr and ONE grid
    mapping, the body is traced once a process and lowered to a Mosaic
    module once a lowering (jax keys an equation's lowering by its
    parameters) — not once a layer, which on a 16-layer model was seconds
    of every start (PERF.md section 6, PR 35).

    Grid (batch, kv_heads / g) with g from `_paged_step_shape`: one step
    serves EVERY query row of g KV heads (in a decode round as a rule all
    heads of the row) over the row's whole extent, so a live K/V row
    leaves HBM once, whatever the GQA group.  The grid has no page axis:
    every `pages` operand ([n_pages, kv_heads, page_tokens, *]) is passed
    once (in whole lanes: `_whole_lanes`) and stays where it is (`pl.ANY`),
    and the body (`_paged_decode_steps`) walks the row's LIVE windows of n
    pages (n from `_paged_step_shape` too) in a loop of its own, copying
    each page into one of two VMEM slots of [n, g, page_tokens, *] an
    operand — the scratch after the accumulator and the statistics, with
    a DMA semaphore a slot's page and the one SMEM word that hands the slot
    of a step's first window to it.  Both axes are `arbitrary`: the slots and that
    word outlive a grid step.  Table and lengths are scalar-prefetched.  q
    and the output ride a [batch, kv_heads, rows, head_dim] view (rows: the
    GQA group, times `chunk` queries for the chunk kernel), whose blocks'
    trailing dims equal the array's.  The latent kernels differ in two
    things: the pages have one head, which every block of the q view's
    second axis (blocks of query heads, there) attends, and the result is
    `out_dim` wide, the page's leading columns being its values.  With
    `q_blocks` > 1 (the chunk kernel, where a group's query rows do not
    fit a step: `_query_head_block`) the q view's second axis is kv_heads x
    `q_blocks` blocks of a group's query heads, a step holds ONE KV head,
    and entry i of that axis attends KV head i // q_blocks."""
    (b, kvh, rows, d), q_dtype = q_view
    avals = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in pages]
    _, page_heads, pt, _ = avals[0].shape
    g, n_step = _paged_step_shape(
        max_pages, avals if q_blocks == 1 else [
            a.update(shape=(a.shape[0], 1) + a.shape[2:]) for a in avals],
        pages_per_step, rows if chunk else 0)
    parts = _row_parts(avals)
    kw = {} if out_dim is None else {"out_dim": out_dim}
    out_dim = out_dim or d
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh // g),
        in_specs=[pl.BlockSpec((1, g, rows, d), _q_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(avals),
        out_specs=pl.BlockSpec((1, g, rows, out_dim), _q_map),
        scratch_shapes=_decode_scratch(g, rows, out_dim) + [
            pltpu.VMEM((2, n_step, min(g, page_heads), pt // parts,
                        -(-parts * a.shape[-1] // 128) * 128), a.dtype)
            for a in avals]
        + [pltpu.SemaphoreType.DMA((2, n_step)),
           pltpu.SMEM((1,), jnp.int32)],
    )
    if q_blocks > 1:
        kw["q_blocks"] = q_blocks
    return pl.pallas_call(
        functools.partial(body, scale=scale, chunk=chunk,
                          widths=tuple(a.shape[-1] for a in avals),
                          parts=parts, **kw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, out_dim), q_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )


def _count_paged_call(name: str, head_dim: int, parts: int,
                      dense: bool) -> None:
    """`paged_attn_calls{kernel, head_dim, row_parts, leaf}`, once per
    traced call: which paged kernel, at what head, how many positions to a
    128-lane row, and whether the leaf came stored so (`lane_dense`) or was
    handed over as it is (`as_is`: heads of 128 and wider, or a narrow leaf
    that `_whole_lanes` reshapes or pads — a copy of the leaf a call)."""
    from easydist_tpu.runtime import spans

    spans.count("paged_attn_calls", kernel=name.replace("paged_", ""),
                head_dim=head_dim, row_parts=parts,
                leaf="lane_dense" if dense else "as_is")


def _paged_attend(body, name: str, scale: float, q_view, pages, table,
                  lengths, pages_per_step: Optional[int], interpret: bool,
                  chunk: int = 0, out_dim: Optional[int] = None,
                  q_blocks: int = 1):
    """`_paged_call` at the operands' signature, applied: `q_view` is q as
    [batch, kv_heads (x `q_blocks`), rows, head_dim], and so is the result
    (`out_dim` wide, where that is given)."""
    views = _page_views(pages, q_view.shape[-1])
    call = _paged_call(
        body, name, float(scale), (q_view.shape, q_view.dtype.name),
        tuple((v.shape, v.dtype.name) for v in views), table.shape[1],
        pages_per_step, bool(interpret), chunk, out_dim, q_blocks)
    parts = _row_parts(views)
    dense = any(a.shape != v.shape for a, v in zip(pages, views))
    _count_paged_call(name, q_view.shape[-1], parts, dense)
    with jax.named_scope(name):
        return call(jnp.asarray(table, jnp.int32),
                    jnp.asarray(lengths, jnp.int32), q_view,
                    *(a if a.shape != v.shape else _whole_lanes(a, parts)
                      for a, v in zip(pages, views)))


def _paged_decode_call(body, name: str, scale: float, q, pages, table,
                       lengths, pages_per_step: Optional[int],
                       interpret: bool):
    """One query a row: q [batch, heads, head_dim] rides the view as
    [batch, kv_heads, group, head_dim]."""
    b, h, d = q.shape
    kvh = pages[0].shape[1]
    if h % kvh:
        raise ValueError(f"heads {h} not a multiple of kv_heads {kvh}")
    out = _paged_attend(body, name, scale, q.reshape(b, kvh, h // kvh, d),
                        pages, table, lengths, pages_per_step, interpret)
    return out.reshape(b, h, d)


def _paged_decode_steps(tbl_ref, len_ref, q_ref, pages, o_ref, o_scr, m_scr,
                        l_scr, slots, sem, first_ref, load_page, *,
                        scale: float, widths, parts: int = 1,
                        chunk: int = 0, q_blocks: int = 1):
    """The body the paged kernels share.  Grid step (bi, gi) is row bi and
    its gi-th group of g KV heads (with `q_blocks` > 1: block gi %
    q_blocks of the query heads of KV head gi // q_blocks); `pages` are
    the arena operands where
    they lie (HBM, in whole lanes: `_whole_lanes`), `slots` their VMEM
    scratch [2, n_step, g, page_tokens / parts, *], `sem` a DMA semaphore a
    page of a slot (its operands share it), `widths` each operand's own
    minor dim — what is read of a padded slot's lanes — and
    `load_page(*blocks)` makes the K and V [g, page_tokens, d] of a page
    from its operands' blocks.  With `parts` positions to a row, a page is
    attended in `parts` turns: turn i the lanes of the positions i, i +
    parts, ... of the page.

    A row walks ceil(length / (n_step * page_tokens)) windows of its page
    table — none if its first entry names no page of the arena (a slot
    that holds no sequence) — in a `fori_loop`: window w + 1's pages are
    asked for (one copy a page an operand, from `pages.at[table[bi, p],
    heads]`, the entry clipped as `gather_pages` clips it) before window w
    is waited for and computed, into the other slot.  A page past the
    row's length, inside its last window, is neither copied nor computed.
    The LAST window of a step asks for the first window of the next step
    that walks any (the row's next group of heads, or the first of the
    next row that has windows: `live_row`), so that no step opens on a
    copy nobody started; step (0, 0) asks for the first of all.
    `first_ref[0]` is the slot that window lies in.  (A step that starts
    its own first window waits ~1.0-1.3 us for it: a decode call alone
    was 12 % slower at Olmo's shapes, 16 % at K-EXAONE's and 20 % at
    Granite's — PERF.md section 6, PR 42.)  Whatever is started is waited
    for by the step it was started for, so nothing is in flight when the
    call ends.  With
    `chunk`, the block's rows are the GQA group x a chunk of queries (the
    chunk padded to a whole tile) at the row's LAST `chunk` positions, and
    each sees the keys up to its own."""
    bi, gi = pl.program_id(0), pl.program_id(1)
    n_rows, n_groups = pl.num_programs(0), pl.num_programs(1)
    n_pages, page_heads = pages[0].shape[:2]
    _, n_step, g, page_rows, _ = slots[0].shape
    page_tokens = page_rows * parts
    max_windows = tbl_ref.shape[1] // n_step

    def windows(row):
        first = tbl_ref[row, 0]
        return jnp.where(
            (first >= 0) & (first < n_pages),
            jnp.minimum(pl.cdiv(len_ref[row], n_step * page_tokens),
                        max_windows), 0)

    def live_row(row):
        """The first row from `row` on that walks any window, or n_rows."""
        return jax.lax.while_loop(
            lambda r: (r < n_rows)
            & (windows(jnp.minimum(r, n_rows - 1)) == 0),
            lambda r: r + 1, row)

    def start(row, group, w, slot):
        """Ask for the pages of window w of (row, group), into `slot`;
        row n_rows: there is none to ask for."""
        length = jnp.where(row < n_rows,
                           len_ref[jnp.minimum(row, n_rows - 1)], 0)
        for j in range(n_step):
            p = w * n_step + j

            @pl.when(p * page_tokens < length)
            def _copy():
                page = jnp.clip(tbl_ref[row, p], 0, n_pages - 1)
                first_head = group * g if q_blocks == 1 \
                    else group // q_blocks
                heads = pl.ds(first_head if page_heads > g else 0, g)
                for src, dst in zip(pages, slots):
                    pltpu.make_async_copy(src.at[page, heads],
                                          dst.at[slot, j],
                                          sem.at[slot, j]).start()

    @pl.when((bi == 0) & (gi == 0))
    def _open():
        first_ref[0] = 0
        start(live_row(0), 0, 0, 0)

    _decode_init(o_scr, m_scr, l_scr)
    n_windows = windows(bi)
    first = first_ref[0]
    length = limit = len_ref[bi]
    if chunk:
        rows = q_ref.shape[2]
        limit = length - chunk + 1 + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1),
            _chunk_rows(chunk))

    def window(w, carry):
        slot = jax.lax.rem(first + w, 2)

        # the window after this one: the row's next, or the first of the
        # next step that walks any (`live_row(bi)` is bi: it is walking)
        last = w + 1 == n_windows
        same_row = gi + 1 < n_groups
        start(live_row(jnp.where(last & ~same_row, bi + 1, bi)),
              jnp.where(last, jnp.where(same_row, gi + 1, 0), gi),
              jnp.where(last, 0, w + 1), 1 - slot)
        q = q_ref[0]
        for j in range(n_step):
            k0 = (w * n_step + j) * page_tokens

            @pl.when(k0 < length)
            def _page():
                for src, dst in zip(pages, slots):
                    pltpu.make_async_copy(src.at[0, pl.ds(0, g)],
                                          dst.at[slot, j],
                                          sem.at[slot, j]).wait()
                for i in range(parts):
                    k_blk, v_blk = load_page(*(
                        dst[slot, j, :, :, i * w:(i + 1) * w]
                        for dst, w in zip(slots, widths)))
                    # turn i of a row of `parts` positions: lane block i of
                    # row r is position k0 + r * parts + i, so the rows
                    # under the limit are ceil((limit - k0 - i) / parts)
                    first, under = (k0, limit) if parts == 1 else (
                        0, -((k0 + i - limit) // parts))
                    _decode_block_update(q, k_blk, v_blk, first, under,
                                         scale, o_scr, m_scr, l_scr)
        return carry

    jax.lax.fori_loop(0, n_windows, window, 0)
    first_ref[0] = jax.lax.rem(first + n_windows, 2)
    o_ref[0] = _decode_result(o_scr, l_scr).astype(o_ref.dtype)


def _flash_paged_decode_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                               o_scr, m_scr, l_scr, k_slots, v_slots, sem,
                               first_ref, **kw):
    """The single-query decode kernel with the K/V stream indirected
    through the page table.  With `chunk` in `kw` it is the chunk kernel
    (`flash_paged_chunk_attention`)."""
    _paged_decode_steps(
        tbl_ref, len_ref, q_ref, (k_hbm, v_hbm), o_ref, o_scr, m_scr, l_scr,
        (k_slots, v_slots), sem, first_ref, lambda k, v: (k, v), **kw)


def flash_paged_decode_attention(q, k_pages, v_pages, table, lengths,
                                 scale: Optional[float] = None,
                                 pages_per_step: Optional[int] = None,
                                 interpret: Optional[bool] = None):
    """Single-query flash attention through a page table (paged decode).

    q: [batch, heads, head_dim]; k_pages/v_pages: [n_pages, kv_heads,
    page_tokens, head_dim] arena layers; table: int32 [batch, max_pages];
    lengths: int32 [batch].  The table and lengths ride
    `PrefetchScalarGridSpec` scalar prefetch: they land in SMEM before the
    grid runs, and the kernel's own loop (`_paged_decode_steps`) reads them
    to copy the pages under a row's length — and no other: a window past
    it costs nothing, a row whose table names no page (a slot that holds
    no sequence) walks nothing and gives zeros.  One grid step is a row
    (all its KV heads, as a rule: a page is then one contiguous block of
    the arena); a window of its walk holds several consecutive pages, and
    every query head of a GQA group attends the page of its KV head while
    it is in VMEM.  `pages_per_step` (a window's pages) is for tests and
    sweeps; left None, `_paged_step_shape` derives it from the shapes.
    Returns [batch, heads, head_dim]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    return _paged_decode_call(_flash_paged_decode_kernel, "paged_decode",
                              scale, q, (k_pages, v_pages), table, lengths,
                              pages_per_step, interpret)


def _chunk_rows(chunk: int) -> int:
    """The rows a chunk of queries takes in the kernel's block: the chunk
    padded to a whole bf16 tile (16 rows), so that a verify step's few
    queries are a block Mosaic lays out as any other."""
    return -(-chunk // 16) * 16


def flash_paged_chunk_attention(q, k_pages, v_pages, table, extents,
                                scale: Optional[float] = None,
                                pages_per_step: Optional[int] = None,
                                interpret: Optional[bool] = None):
    """A chunk of queries a row through a page table (chunked prefill, and
    the speculation verify step): the paged decode kernel with group x
    chunk query rows a KV head and each query's own limit.

    q: [rows, heads, chunk, head_dim], row r's queries at the LAST `chunk`
    positions of its extent, `extents[r] - chunk + [0..chunk)` (int32
    [rows]; 0 marks a row that holds no sequence, which reads nothing and
    gives zeros); k_pages/v_pages: [n_pages, kv_heads, page_tokens,
    head_dim] arena layers AFTER the chunk's own write; table: int32
    [rows, max_pages].  A key at position kp is visible to a query at qp
    iff kp <= qp — `_chunk_attention_xla`'s one rule: the causal mask
    inside the chunk and the validity mask over what an earlier tenant
    left in a page.  Only the pages under a row's extent leave HBM, each
    once a GQA group: the group is folded into the rows of the matmuls
    ([group x chunk, head_dim] x [head_dim, page_tokens]), nothing is
    gathered, repeated or copied to float32 outside VMEM.  Grid, walk and
    online softmax are the decode kernel's (`_paged_call`), the KV heads a
    step holds bounded by what the query rows take in VMEM; where ONE KV
    head's group x chunk rows are over what a kernel may scope (20 query
    heads on one KV head), a step takes a block of the group's query heads
    (`_query_head_block`) and each block walks the row's pages.
    Returns [rows, heads, chunk, head_dim]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    b, h, c, d = q.shape
    kvh = k_pages.shape[1]
    if h % kvh:
        raise ValueError(f"heads {h} not a multiple of kv_heads {kvh}")
    pad = _chunk_rows(c) - c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    head_block = _query_head_block(_page_views((k_pages, v_pages), d),
                                   h // kvh, c + pad)
    out = _paged_attend(
        _flash_paged_decode_kernel, "paged_chunk", scale,
        q.reshape(b, h // head_block, head_block * (c + pad), d),
        (k_pages, v_pages), table, extents, pages_per_step, interpret,
        chunk=c, q_blocks=h // kvh // head_block)
    return out.reshape(b, h, c + pad, d)[:, :, :c]


def _scale_columns(scales, d: int):
    """Block scales [..., n_blocks] a COLUMN, [..., d]: each block's over
    its d // n_blocks columns.  Written as selects over broadcasts of one
    block's scales and not as a `jnp.repeat`: that a v5e made in the
    leaf's own pages-minor layout and then copied whole, two passes over
    the d-wide form where this is one (PERF.md section 6, PR 42)."""
    nb = scales.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, scales.shape[:-1] + (d,),
                                   scales.ndim - 1)
    out = jnp.broadcast_to(scales[..., :1], col.shape)
    for j in range(1, nb):
        out = jnp.where(col >= j * (d // nb), scales[..., j:j + 1], out)
    return out


def _flash_paged_decode_quant_kernel(tbl_ref, len_ref, q_ref, *refs, **kw):
    """`_flash_paged_decode_kernel` over block-scaled int8 pages: the K/V
    pages arrive int8 with their f32 scale pages, a scale a column, copied
    from the SAME table entries (`refs`: K, V, K scales and V scales in
    HBM, the output and the accumulator's scratch, then the four operands'
    slots, the semaphores and the slot word), and dequantization happens in
    VMEM inside the online-softmax loop: the payload stays int8 all the
    way from HBM."""
    _paged_decode_steps(
        tbl_ref, len_ref, q_ref, refs[:4], *refs[4:8], refs[8:12],
        *refs[12:],
        lambda k, v, ks, vs: (k.astype(jnp.float32) * ks,
                              v.astype(jnp.float32) * vs), **kw)


def flash_paged_decode_quant_attention(q, k_pages, v_pages, k_scale,
                                       v_scale, table, lengths,
                                       scale: Optional[float] = None,
                                       pages_per_step: Optional[int] = None,
                                       interpret: Optional[bool] = None):
    """`flash_paged_decode_attention` over a block-scaled int8 arena.

    k_pages/v_pages: int8 [n_pages, kv_heads, page_tokens, head_dim];
    k_scale/v_scale: f32 [n_pages, kv_heads, page_tokens, n_blocks].  The
    scale pages are copied from the same table entries as the payload (one
    indirection, four streams), and the kernel dequantizes on-chip inside
    the online-softmax loop.  It takes the scales a COLUMN, each block's
    repeated over its head_dim / n_blocks columns: a page of them is then
    whole lanes, as the hand copy needs (`_whole_lanes`), and dequantizing
    is one product.  That is a pass over the scale leaves a call, and a
    scale page four times its payload page on the way to VMEM — what a
    v5e made of the [.., n_blocks] leaf too, whose tile is 128 lanes
    whatever it holds (PERF.md section 6, PR 42).  Returns [batch, heads,
    head_dim] in q.dtype."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    return _paged_decode_call(
        _flash_paged_decode_quant_kernel, "paged_decode_int8", scale, q,
        (k_pages, v_pages, _scale_columns(k_scale, q.shape[-1]),
         _scale_columns(v_scale, q.shape[-1])), table, lengths,
        pages_per_step, interpret)


def paged_decode_attention(q, k_pages, v_pages, table, lengths,
                           scale: Optional[float] = None,
                           backend: Optional[str] = None,
                           k_scale=None, v_scale=None):
    """Backend-dispatching paged decode attention (the models' paged
    decode steps call this): the Pallas page-walking kernel on a TPU, the
    gather + masked dot_general path elsewhere (`_use_kernel`).
    `EASYDIST_DECODE_ATTENTION` forces it — "paged"/"flash" pick the
    kernel, "xla" the gather fallback — and the value rides the same
    strategy-cache salt entry as the contiguous knob.  When
    `k_scale`/`v_scale` are given the pages are block-scaled int8 and
    both backends dequantize before the softmax (in-VMEM for the kernel,
    post-gather for the fallback)."""
    from easydist_tpu import config as edconfig

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (q.shape[0],))
    if backend is None:
        backend = edconfig.decode_attention_backend
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if k_scale is not None:
        if _use_kernel(backend, "paged decode"):
            return flash_paged_decode_quant_attention(
                q, k_pages, v_pages, k_scale, v_scale, table, lengths,
                scale=scale)
        return _paged_decode_attention_quant_xla(
            q, k_pages, v_pages, k_scale, v_scale, table, lengths, scale)
    if _use_kernel(backend, "paged decode"):
        return flash_paged_decode_attention(q, k_pages, v_pages, table,
                                            lengths, scale=scale)
    d = q.shape[-1]
    return _paged_decode_attention_xla(q, plain_pages(k_pages, d),
                                       plain_pages(v_pages, d), table,
                                       lengths, scale)


def _chunk_attention_xla(q, k, v, q_pos, scale: float):
    """Masked dot_general chunked-prefill path: q [b, h, c, hd] at absolute
    positions `q_pos` (int32 [b, c]) attends the full cache window k/v
    [b, h, T, hd].  A key at position kp is visible iff kp <= q_pos, which
    is simultaneously the causal mask *within* the chunk and the validity
    mask over the cache tail (stale rows beyond the row's live length sit
    at positions > q_pos, so their softmax weight underflows to exact 0 —
    the no-stale-leakage property SERVE002 audits statically)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    s = jnp.where(k_pos <= q_pos.astype(jnp.int32)[:, None, :, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def chunk_attention(q, k, v, q_pos, scale: Optional[float] = None,
                    backend: Optional[str] = None):
    """Chunked-prefill attention over a CONTIGUOUS cache (the
    one-sequence `*_prefill_chunk` and verify steps call this): q is a
    fixed-size token chunk at absolute positions `q_pos`, k/v are the full
    bucket-length cache.  Always the masked dot_general path, whatever
    `EASYDIST_PREFILL_ATTENTION` says short of an unknown value: the
    kernel chases a page table (`paged_chunk_attention`), and a contiguous
    cache has none."""
    from easydist_tpu import config as edconfig

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if backend is None:
        backend = edconfig.prefill_attention_backend
    if backend in ("auto", "paged", "flash", "xla"):
        return _chunk_attention_xla(q, k, v, q_pos, scale)
    raise ValueError(f"unknown prefill attention backend {backend!r}; "
                     f"expected auto|paged|flash|xla")


def paged_chunk_attention(q, k_pages, v_pages, table, q_pos,
                          scale: Optional[float] = None,
                          backend: Optional[str] = None):
    """Backend-dispatching chunk attention through a page table (the paged
    layout's chunk-prefill and verify steps call this, for an arena
    without scale leaves): q [rows, heads, chunk, head_dim] at CONSECUTIVE
    absolute positions `q_pos` (int32 [rows, chunk]), the arena layer as
    it is after the chunk's own write.  `EASYDIST_PREFILL_ATTENTION`
    forces the backend as `EASYDIST_DECODE_ATTENTION` does the decode
    round's: "paged"/"flash" pick `flash_paged_chunk_attention`, which
    reads only the pages under each row's extent; "xla" gathers the whole
    virtual cache, repeats it to the query heads and runs
    `_chunk_attention_xla`; "auto" is the kernel on a TPU and "xla"
    elsewhere.  The value is part of the strategy-cache salt.  A row whose
    first window is unmapped holds no sequence: the kernel gives it zeros,
    the gather path whatever the clipped page holds — nobody reads
    either."""
    from easydist_tpu import config as edconfig

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if backend is None:
        backend = edconfig.prefill_attention_backend
    if _use_kernel(backend, "prefill"):
        live = table[:, 0].astype(jnp.int32) < k_pages.shape[0]
        extents = jnp.where(live, q_pos[:, -1].astype(jnp.int32) + 1, 0)
        return flash_paged_chunk_attention(q, k_pages, v_pages, table,
                                           extents, scale=scale)
    h, d = q.shape[1], q.shape[-1]
    return _chunk_attention_xla(
        q, gather_pages(plain_pages(k_pages, d), table, n_heads=h),
        gather_pages(plain_pages(v_pages, d), table, n_heads=h), q_pos,
        scale)


# ------------------------------------------------- latent attention
#
# Multi-head latent attention caches ONE row a position for all the heads:
# [c | k_r], the normed low-rank latent and the shared rotary key.  With the
# keys' and values' up-projections absorbed into the query and the output
# (models/axk1.py), a head's key IS that row and its value the row's leading
# `values` columns — so one page serves every head as K and as V at once,
# and is read once for all of them.  Queries come already scaled.

# one window of the latent decode kernel's walk covers up to this many tokens
# of a row: a page's row is a quarter of a GQA page's bytes (no heads, no
# V), so a window holds four times `_PAGED_STEP_TOKENS` for the same copy
# (a call alone within 3 % from 512 to 4,096 tokens, 13 % slower at 256:
# PERF.md section 6, PR 42)
_LATENT_STEP_TOKENS = 1024


def _latent_pages(table, pages):
    """[rows, max_pages * page_tokens, width]: the rows a table describes,
    gathered (the fallbacks; sentinels clip, as `gather_pages`)."""
    n_pages, pt, w = pages.shape
    idx = jnp.clip(table.astype(jnp.int32), 0, n_pages - 1)
    return jnp.take(pages, idx, axis=0).reshape(table.shape[0], -1, w)


def _latent_attention_xla(q, pages, table, q_pos, values: int):
    """Gather-then-mask fallback of both latent kernels, and the reference
    they are tested against: q [rows, heads, chunk, width] at `q_pos`
    (int32 [rows, chunk]) against the gathered rows, key kp visible iff
    kp <= q_pos (`_chunk_attention_xla`'s rule; a decode round is a chunk
    of one query at `length - 1`), float32 throughout."""
    kv = _latent_pages(table, pages).astype(jnp.float32)
    s = jnp.einsum("bhqw,bkw->bhqk", q.astype(jnp.float32), kv)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    s = jnp.where(k_pos <= q_pos.astype(jnp.int32)[:, None, :, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkv->bhqv", p, kv[..., :values]).astype(q.dtype)


def _flash_latent_kernel(tbl_ref, len_ref, q_ref, hbm, o_ref, o_scr, m_scr,
                         l_scr, slots, sem, first_ref, *, out_dim: int, **kw):
    """The paged kernels' body over latent pages, each both K and, in its
    leading `out_dim` columns, V.  The block's rows are query heads (a
    decode round: all of them) or a block of heads x a chunk of queries."""
    _paged_decode_steps(tbl_ref, len_ref, q_ref, (hbm,), o_ref, o_scr, m_scr,
                        l_scr, (slots,), sem, first_ref,
                        lambda blk: (blk, blk[:, :, :out_dim]), **kw)


def flash_latent_decode_attention(q, pages, table, lengths, values: int,
                                  pages_per_step: Optional[int] = None,
                                  interpret: Optional[bool] = None):
    """One query a row against latent pages through a page table: q
    [batch, heads, width] (absorbed and scaled), pages [n_pages,
    page_tokens, width] (one layer of a latent arena), table int32 [batch,
    max_pages], lengths int32 [batch].  The paged decode kernel with ONE
    KV head whose group is every query head, so a live row leaves HBM once
    a round; the result is [batch, heads, values], the softmax-weighted sum
    of the rows' leading `values` columns."""
    if interpret is None:
        interpret = _default_interpret()
    b, h, w = q.shape
    if pages_per_step is None:
        pages_per_step = max(_LATENT_STEP_TOKENS // pages.shape[1], 1)
    out = _paged_attend(_flash_latent_kernel, "latent_decode", 1.0,
                        q.reshape(b, 1, h, w), (pages[:, None],), table,
                        lengths, pages_per_step, interpret, out_dim=values)
    return out.reshape(b, h, values)


def _latent_head_block(heads: int, rows: int, width: int, values: int,
                       page_tokens: int, dtype) -> int:
    """The query heads one grid step of the latent chunk kernel holds: the
    largest divisor of `heads` whose q and output blocks (double-buffered),
    float32 accumulator, a page's scores and probabilities and its values
    in float32 stay under `_PAGED_VMEM_BUDGET`.  Every block of heads reads the row's pages
    again, so fewer, larger blocks read less."""
    def step_bytes(hb):
        r = hb * rows
        return (2 * _vmem_block_bytes((r, width), dtype)
                + 2 * _vmem_block_bytes((r, values), dtype)
                + _vmem_block_bytes((r, values), jnp.float32)
                + 2 * _vmem_block_bytes((r, page_tokens), jnp.float32)
                + _vmem_block_bytes((page_tokens, values), jnp.float32))

    return _largest_divisor(
        heads, lambda hb: step_bytes(hb) <= _PAGED_VMEM_BUDGET)


def flash_latent_chunk_attention(q, pages, table, extents, values: int,
                                 pages_per_step: Optional[int] = None,
                                 interpret: Optional[bool] = None):
    """A chunk of queries a row against latent pages (chunked prefill): q
    [rows, heads, chunk, width] (absorbed and scaled), row r's queries at
    the LAST `chunk` positions of its extent (`extents[r]`, int32 [rows]; 0
    = no sequence: reads nothing, gives zeros), pages AFTER the chunk's own
    write.  `flash_paged_chunk_attention`'s rule (key kp visible to query
    qp iff kp <= qp), grid and walk, with a block of query HEADS where that
    has a KV head: a block's rows are heads x chunk queries, every block reads
    the row's pages — one head, shared — as far as its extent, nothing is
    expanded to keys or values a head.  Returns [rows, heads, chunk,
    values]."""
    if interpret is None:
        interpret = _default_interpret()
    b, h, c, w = q.shape
    pad = _chunk_rows(c) - c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    hb = _latent_head_block(h, c + pad, w, values, pages.shape[1], q.dtype)
    out = _paged_attend(
        _flash_latent_kernel, "latent_chunk", 1.0,
        q.reshape(b, h // hb, hb * (c + pad), w), (pages[:, None],), table,
        extents, pages_per_step, interpret, chunk=c, out_dim=values)
    return out.reshape(b, h, c + pad, values)[:, :, :c]


def latent_decode_attention(q, pages, table, lengths, values: int,
                            backend: Optional[str] = None):
    """Backend-dispatching latent decode attention (`models/decoder.py::
    Latent`): the kernel on a TPU, gather + masked einsum elsewhere;
    `EASYDIST_DECODE_ATTENTION` forces it as it does the paged kernel's."""
    from easydist_tpu import config as edconfig

    lengths = jnp.asarray(lengths, jnp.int32)
    if _use_kernel(backend or edconfig.decode_attention_backend, "decode"):
        return flash_latent_decode_attention(q, pages, table, lengths, values)
    return _latent_attention_xla(q[:, :, None], pages, table,
                                 lengths[:, None] - 1, values)[:, :, 0]


def latent_chunk_attention(q, pages, table, q_pos, values: int,
                           backend: Optional[str] = None):
    """Backend-dispatching latent chunk attention: q [rows, heads, chunk,
    width] at CONSECUTIVE positions `q_pos` (int32 [rows, chunk]);
    `EASYDIST_PREFILL_ATTENTION` forces the backend, as for
    `paged_chunk_attention`.  A row whose first window is unmapped holds no
    sequence: the kernel gives it zeros, the gather path whatever the
    clipped page holds — nobody reads either."""
    from easydist_tpu import config as edconfig

    if _use_kernel(backend or edconfig.prefill_attention_backend,
                   "prefill"):
        live = table[:, 0].astype(jnp.int32) < pages.shape[0]
        extents = jnp.where(live, q_pos[:, -1].astype(jnp.int32) + 1, 0)
        return flash_latent_chunk_attention(q, pages, table, extents, values)
    return _latent_attention_xla(q, pages, table, q_pos, values)


def window_attention(q, k, v, q_pos, k_pos, window: int,
                     scale: Optional[float] = None):
    """Attention of a layer that sees its last `window` positions, over keys
    that come with their absolute positions: q [b, h, c, hd] at `q_pos`
    (int32 [b, c]), k / v [b, kv_heads, n, hd] at `k_pos` (int32 [b, n]; a
    negative one marks a row that holds nothing).  Key j is visible to
    query i iff q_pos[i] - window < k_pos[j] <= q_pos[i] — by position, not
    by place, so the keys may be a ring in any rotation, with a chunk's own
    keys after it (`models/decoder.py::Ring`).  Every query head of a GQA
    group attends its KV head as it is: nothing is repeated.  A masked
    dot_general, as `_chunk_attention_xla`: n is a window and a chunk, not
    a bucket."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, c, d = q.shape
    kvh = k.shape[1]
    s = jnp.einsum("bgrqd,bgkd->bgrqk",
                   q.reshape(b, kvh, h // kvh, c, d).astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qp = q_pos.astype(jnp.int32)[:, None, None, :, None]
    kp = k_pos.astype(jnp.int32)[:, None, None, None, :]
    s = jnp.where((kp >= 0) & (kp <= qp) & (kp > qp - window), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", p, v.astype(jnp.float32))
    return out.reshape(b, h, c, d).astype(q.dtype)
