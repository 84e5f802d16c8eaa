"""Preset (analytic) SPMD rules for common jax primitives.

Execution-based ShardCombine is the general mechanism, but the hot primitives
of any transformer/convnet have well-known sharding rules — computing them
analytically makes compile time independent of tensor sizes.  This is the
TPU analog of the reference's discovery-bypass rule bank
(easydist/torch/preset_propagation.py:32-378 and the preset short-circuit in
sharding_interpreter.py:336-338).  Anything not covered here falls back to
execution discovery, and tests cross-check these rules against discovery.

A rule receives the eqn and returns {"space": ShardSpace, "recombines":
{group: partial}} with rows covering the eqn's tensor (non-Literal-scalar)
inputs in order, or None to decline.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional

from jax.extend import core as jex_core

from easydist_tpu.metashard.annotation import DimSharding, ShardSpace
from easydist_tpu.metashard.combination import Recombine, Reduction
from easydist_tpu.metashard.view_propagation import view_rule

_RULES: Dict[str, Callable] = {}


def register_preset(*prim_names):
    def deco(fn):
        for name in prim_names:
            _RULES[name] = fn
        return fn

    return deco


def preset_rule(eqn, world_size: int) -> Optional[dict]:
    fn = _RULES.get(eqn.primitive.name)
    if fn is None:
        return None
    try:
        return fn(eqn, world_size)
    except Exception:
        return None


def _tensor_avals(eqn) -> List:
    """Avals of the inputs that occupy discovery rows: every non-Literal var
    plus array-valued literals (scalar literals take no row, matching
    MetaOp's jax.Array check)."""
    avals = []
    for v in eqn.invars:
        if isinstance(v, jex_core.Literal):
            if getattr(v.val, "ndim", None) is not None and v.val.ndim > 0:
                avals.append(v.aval)
        else:
            avals.append(v.aval)
    return avals


def _concat(dim):
    return functools.partial(Recombine.concat, dim=dim)


def _reduce(op=Reduction.SUM):
    return functools.partial(Recombine.reduce, op=op)


# ------------------------------------------------------------- elementwise

_ELEMENTWISE = [
    "add", "sub", "mul", "div", "pow", "max", "min", "rem", "atan2",
    "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "nextafter",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp", "add_any",
    "exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan", "asin",
    "acos", "atan", "sinh", "cosh", "asinh", "acosh", "atanh", "logistic",
    "sqrt", "rsqrt", "cbrt", "neg", "sign", "abs", "floor", "ceil", "round",
    "is_finite", "not", "erf", "erfc", "erf_inv", "integer_pow", "square",
    "convert_element_type", "stop_gradient", "copy", "real", "imag",
    "exp2", "logb", "population_count", "clz",
]


@register_preset(*_ELEMENTWISE)
def _elementwise_rule(eqn, world_size):
    avals = _tensor_avals(eqn)
    if not avals:
        # all-literal-scalar op: nothing to shard, nothing to execute either
        return {"space": ShardSpace([]), "recombines": {}}
    out_aval = eqn.outvars[0].aval
    rank = out_aval.ndim
    # inputs are same-rank (possibly with broadcasting size-1 dims) or scalar
    for a in avals:
        if a.ndim not in (0, rank):
            return None
        if a.ndim == rank:
            for d in range(rank):
                if a.shape[d] not in (1, out_aval.shape[d]):
                    return None

    table, recombines = [], {}
    dim_groups = {}
    group = 1
    for d in range(rank):
        dim_groups[d] = group
        recombines[group] = _concat(d)
        group += 1
    for a in avals:
        if a.ndim == 0:
            table.append([])
        else:
            # size-1 (broadcast) dims ride along replicated in that group
            table.append([DimSharding(group=dim_groups[d])
                          if a.shape[d] == out_aval.shape[d] != 1
                          else DimSharding()
                          for d in range(rank)])
    # drop groups where no input actually shards (out dim size 1)
    live = {d.group for row in table for d in row if d.group > 0}
    recombines = {g: fn for g, fn in recombines.items() if g in live}
    return {"space": ShardSpace(table), "recombines": recombines}


# -------------------------------------------------------------- dot_general

@register_preset("dot_general")
def _dot_general_rule(eqn, world_size):
    avals = _tensor_avals(eqn)
    if len(avals) != 2:
        return None
    lhs, rhs = avals
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs_row = [DimSharding() for _ in range(lhs.ndim)]
    rhs_row = [DimSharding() for _ in range(rhs.ndim)]
    recombines = {}
    group = 1

    # output layout: batch dims, then lhs free dims, then rhs free dims
    lhs_free = [d for d in range(lhs.ndim) if d not in lc and d not in lb]
    rhs_free = [d for d in range(rhs.ndim) if d not in rc and d not in rb]

    for i, (ld, rd) in enumerate(zip(lb, rb)):
        lhs_row[ld] = DimSharding(group=group)
        rhs_row[rd] = DimSharding(group=group)
        recombines[group] = _concat(i)
        group += 1
    for ld, rd in zip(lc, rc):
        lhs_row[ld] = DimSharding(group=group)
        rhs_row[rd] = DimSharding(group=group)
        recombines[group] = _reduce()
        group += 1
    for i, ld in enumerate(lhs_free):
        lhs_row[ld] = DimSharding(group=group)
        recombines[group] = _concat(len(lb) + i)
        group += 1
    for i, rd in enumerate(rhs_free):
        rhs_row[rd] = DimSharding(group=group)
        recombines[group] = _concat(len(lb) + len(lhs_free) + i)
        group += 1
    return {"space": ShardSpace([lhs_row, rhs_row]), "recombines": recombines}


# ---------------------------------------------------------------- reshape &c

@register_preset("transpose")
def _transpose_rule(eqn, world_size):
    (aval,) = _tensor_avals(eqn)
    perm = eqn.params["permutation"]
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    for out_dim, in_dim in enumerate(perm):
        row[in_dim] = DimSharding(group=group)
        recombines[group] = _concat(out_dim)
        group += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


@register_preset("broadcast_in_dim")
def _broadcast_rule(eqn, world_size):
    avals = _tensor_avals(eqn)
    if not avals:
        # scalar broadcast: a create-op with no shardable inputs; returning
        # the empty rule (replicate) avoids materializing the (possibly
        # huge) output in eager discovery
        return {"space": ShardSpace([]), "recombines": {}}
    (aval,) = avals
    bcast_dims = eqn.params["broadcast_dimensions"]
    out_shape = eqn.params["shape"]
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    for in_dim, out_dim in enumerate(bcast_dims):
        # size-1 input dims are stretched, not sharded
        if aval.shape[in_dim] == out_shape[out_dim]:
            row[in_dim] = DimSharding(group=group)
            recombines[group] = _concat(out_dim)
            group += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


@register_preset("squeeze")
def _squeeze_rule(eqn, world_size):
    (aval,) = _tensor_avals(eqn)
    squeezed = set(eqn.params["dimensions"])
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    out_dim = 0
    for d in range(aval.ndim):
        if d in squeezed:
            continue
        row[d] = DimSharding(group=group)
        recombines[group] = _concat(out_dim)
        group += 1
        out_dim += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


@register_preset("reshape")
def _reshape_rule(eqn, world_size):
    (aval,) = _tensor_avals(eqn)
    if eqn.params.get("dimensions") is not None:
        return None
    rule = view_rule(list(aval.shape), list(eqn.params["new_sizes"]),
                     world_size=world_size)
    return {"space": rule["space"], "recombines": rule["recombines"]}


# ---------------------------------------------------------------- reductions

_REDUCE_OPS = {
    "reduce_sum": Reduction.SUM,
    "reduce_max": Reduction.MAX,
    "reduce_min": Reduction.MIN,
}


@register_preset("reduce_sum", "reduce_max", "reduce_min")
def _reduce_rule(eqn, world_size):
    (aval,) = _tensor_avals(eqn)
    axes = set(eqn.params["axes"])
    red = _REDUCE_OPS[eqn.primitive.name]
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    out_dim = 0
    for d in range(aval.ndim):
        row[d] = DimSharding(group=group)
        if d in axes:
            recombines[group] = _reduce(red)
        else:
            recombines[group] = _concat(out_dim)
            out_dim += 1
        group += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


@register_preset("argmax", "argmin", "reduce_and", "reduce_or",
                 "cumsum", "cumlogsumexp", "cumprod", "cummax", "cummin")
def _scan_reduce_rule(eqn, world_size):
    """Only non-reduced/non-scanned dims are shardable."""
    avals = _tensor_avals(eqn)
    if len(avals) != 1:
        return None
    (aval,) = avals
    if "axes" in eqn.params:
        special = set(eqn.params["axes"])
        collapses = True
    else:
        special = {eqn.params["axis"]}
        collapses = False
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    out_dim = 0
    for d in range(aval.ndim):
        if d in special:
            if not collapses:
                out_dim += 1
            continue
        row[d] = DimSharding(group=group)
        recombines[group] = _concat(out_dim)
        group += 1
        out_dim += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


# ------------------------------------------------------------------ slicing

@register_preset("slice")
def _slice_rule(eqn, world_size):
    (aval,) = _tensor_avals(eqn)
    starts = eqn.params["start_indices"]
    limits = eqn.params["limit_indices"]
    strides = eqn.params["strides"] or [1] * aval.ndim
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    for d in range(aval.ndim):
        # only dims taken whole can shard
        if starts[d] == 0 and limits[d] == aval.shape[d] and strides[d] == 1:
            row[d] = DimSharding(group=group)
            recombines[group] = _concat(d)
            group += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


@register_preset("pad")
def _pad_rule(eqn, world_size):
    avals = _tensor_avals(eqn)
    aval = avals[0]
    config = eqn.params["padding_config"]
    row = [DimSharding() for _ in range(aval.ndim)]
    table = [row] + [[] for _ in avals[1:]]  # padding value is scalar
    recombines = {}
    group = 1
    for d, (lo, hi, interior) in enumerate(config):
        if lo == 0 and hi == 0 and interior == 0:
            row[d] = DimSharding(group=group)
            recombines[group] = _concat(d)
            group += 1
    return {"space": ShardSpace(table), "recombines": recombines}


@register_preset("concatenate")
def _concatenate_rule(eqn, world_size):
    avals = _tensor_avals(eqn)
    cat_dim = eqn.params["dimension"]
    rank = avals[0].ndim
    table = [[DimSharding() for _ in range(rank)] for _ in avals]
    recombines = {}
    group = 1
    for d in range(rank):
        if d == cat_dim:
            continue
        for row in table:
            row[d] = DimSharding(group=group)
        recombines[group] = _concat(d)
        group += 1
    return {"space": ShardSpace(table), "recombines": recombines}


@register_preset("rev")
def _rev_rule(eqn, world_size):
    (aval,) = _tensor_avals(eqn)
    flipped = set(eqn.params["dimensions"])
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    for d in range(aval.ndim):
        if d not in flipped:
            row[d] = DimSharding(group=group)
            recombines[group] = _concat(d)
            group += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


# -------------------------------------------------------------- convolution

@register_preset("conv_general_dilated")
def _conv_rule(eqn, world_size):
    """Batch and feature-dim rules only; spatial sharding (halo exchange) is
    left to execution discovery or the solver never picks it.  Layouts read
    from dimension_numbers; grouped conv limited to feature_group_count=1
    for the channel rules."""
    avals = _tensor_avals(eqn)
    if len(avals) != 2:
        return None
    lhs, rhs = avals
    dn = eqn.params["dimension_numbers"]
    lhs_spec, rhs_spec, out_spec = dn
    groups_feat = eqn.params.get("feature_group_count", 1)
    batch_count = eqn.params.get("batch_group_count", 1)
    if batch_count != 1:
        return None

    lhs_row = [DimSharding() for _ in range(lhs.ndim)]
    rhs_row = [DimSharding() for _ in range(rhs.ndim)]
    recombines = {}
    group = 1
    # batch: lhs batch dim -> out batch dim
    lhs_row[lhs_spec[0]] = DimSharding(group=group)
    recombines[group] = _concat(out_spec[0])
    group += 1
    if groups_feat == 1:
        # output channels: rhs out-feature dim -> out feature dim
        rhs_row[rhs_spec[0]] = DimSharding(group=group)
        recombines[group] = _concat(out_spec[1])
        group += 1
        # input channels: contraction -> partial
        lhs_row[lhs_spec[1]] = DimSharding(group=group)
        rhs_row[rhs_spec[1]] = DimSharding(group=group)
        recombines[group] = _reduce()
        group += 1
    return {"space": ShardSpace([lhs_row, rhs_row]), "recombines": recombines}


# ------------------------------------------------------- gather / scatter

def _trailing_offset_dims(offset_dims, out_rank):
    return tuple(offset_dims) == tuple(range(out_rank - len(offset_dims),
                                             out_rank))


@register_preset("gather")
def _gather_rule(eqn, world_size):
    """General gather rule (embedding lookup, take_along_axis, batched
    gathers).  GSPMD handles the static slice_sizes under sharding — the
    eager discovery harness cannot, which is why this rule is analytic-only.

    Shardable:
      - indices dims (except the trailing index-vector dim): concat at the
        matching output dim; batching dims also shard the paired operand dim
      - operand slice dims taken WHOLE (slice_sizes[j] == shape[j]): concat
        at the matching offset output dim
    The gathered (start_index_map / collapsed) operand dims never shard."""
    avals = _tensor_avals(eqn)
    if len(avals) != 2:
        return None
    operand, indices = avals
    dn = eqn.params["dimension_numbers"]
    slice_sizes = eqn.params["slice_sizes"]
    out_rank = eqn.outvars[0].aval.ndim

    offset_dims = tuple(dn.offset_dims)
    # output dims not in offset_dims correspond, in order, to indices dims
    # 0..n-2 (the last indices dim is the index vector)
    batch_out_dims = [d for d in range(out_rank) if d not in offset_dims]
    n_idx_batch = indices.ndim - 1
    if len(batch_out_dims) != n_idx_batch:
        return None
    # operand slice dims (not collapsed, not batching) map in order to
    # offset_dims
    slice_dims = [j for j in range(operand.ndim)
                  if j not in dn.collapsed_slice_dims
                  and j not in dn.operand_batching_dims]
    if len(slice_dims) != len(offset_dims):
        return None
    idx_batching = list(dn.start_indices_batching_dims)
    op_batching = list(dn.operand_batching_dims)

    op_row = [DimSharding() for _ in range(operand.ndim)]
    idx_row = [DimSharding() for _ in range(indices.ndim)]
    recombines = {}
    group = 1
    for i in range(n_idx_batch):
        idx_row[i] = DimSharding(group=group)
        if i in idx_batching:
            op_row[op_batching[idx_batching.index(i)]] = DimSharding(group=group)
        recombines[group] = _concat(batch_out_dims[i])
        group += 1
    for k, j in enumerate(slice_dims):
        if slice_sizes[j] == operand.shape[j]:
            op_row[j] = DimSharding(group=group)
            recombines[group] = _concat(offset_dims[k])
            group += 1
    return {"space": ShardSpace([op_row, idx_row]), "recombines": recombines}


@register_preset("scatter-add")
def _scatter_add_rule(eqn, world_size):
    """General scatter-add rule (embedding gradients, take_along_axis
    gradients, batched scatters).

    Shardable:
      - operand window dims (taken whole): shard operand + the matching
        updates window dim, concat at that output dim
      - indices dims: batching dims shard indices+updates+operand together
        (concat); non-batching index dims shard indices+updates and make the
        output PARTIAL(SUM) — scatter-add over index subsets sums exactly."""
    avals = _tensor_avals(eqn)
    if len(avals) != 3:
        return None
    operand, indices, updates = avals
    dn = eqn.params["dimension_numbers"]
    window_dims = tuple(dn.update_window_dims)
    # updates dims not in update_window_dims correspond to indices dims 0..n-2
    upd_batch_dims = [d for d in range(updates.ndim) if d not in window_dims]
    n_idx_batch = indices.ndim - 1
    if len(upd_batch_dims) != n_idx_batch:
        return None
    # operand window dims (not inserted, not batching) map in order to
    # update_window_dims
    op_window = [j for j in range(operand.ndim)
                 if j not in dn.inserted_window_dims
                 and j not in dn.operand_batching_dims]
    if len(op_window) != len(window_dims):
        return None
    idx_batching = list(dn.scatter_indices_batching_dims)
    op_batching = list(dn.operand_batching_dims)

    op_row = [DimSharding() for _ in range(operand.ndim)]
    idx_row = [DimSharding() for _ in range(indices.ndim)]
    upd_row = [DimSharding() for _ in range(updates.ndim)]
    recombines = {}
    group = 1
    for i in range(n_idx_batch):
        idx_row[i] = DimSharding(group=group)
        upd_row[upd_batch_dims[i]] = DimSharding(group=group)
        if i in idx_batching:
            j = op_batching[idx_batching.index(i)]
            op_row[j] = DimSharding(group=group)
            recombines[group] = _concat(j)
        else:
            recombines[group] = _reduce()
        group += 1
    for k, j in enumerate(op_window):
        if updates.shape[window_dims[k]] == operand.shape[j]:
            op_row[j] = DimSharding(group=group)
            upd_row[window_dims[k]] = DimSharding(group=group)
            recombines[group] = _concat(j)
            group += 1
    return {"space": ShardSpace([op_row, idx_row, upd_row]),
            "recombines": recombines}


@register_preset("split")
def _split_rule(eqn, world_size):
    (aval,) = _tensor_avals(eqn)
    axis = eqn.params["axis"]
    n_out = len(eqn.outvars)
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    for d in range(aval.ndim):
        if d == axis:
            continue
        row[d] = DimSharding(group=group)
        recombines[group] = [_concat(d)] * n_out
        group += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


# ------------------------------------------------------------ sort / top_k

@register_preset("sort")
def _sort_rule(eqn, world_size):
    """Variadic lax.sort: all operands share one shape; any dim except the
    sort dimension shards freely (the comparator only looks along
    `dimension`), every output concats at the same dim."""
    avals = _tensor_avals(eqn)
    if not avals:
        return None
    shape = avals[0].shape
    if any(a.shape != shape for a in avals):
        return None
    dim = eqn.params["dimension"]
    n_out = len(eqn.outvars)
    rows = [[DimSharding() for _ in shape] for _ in avals]
    recombines = {}
    group = 1
    for d in range(len(shape)):
        if d == dim:
            continue
        for row in rows:
            row[d] = DimSharding(group=group)
        recombines[group] = [_concat(d)] * n_out
        group += 1
    return {"space": ShardSpace(rows), "recombines": recombines}


@register_preset("top_k")
def _top_k_rule(eqn, world_size):
    """lax.top_k selects along the last dim; batch dims shard freely and
    both outputs (values, indices) concat there."""
    (aval,) = _tensor_avals(eqn)
    if aval.ndim == 0:
        return None
    row = [DimSharding() for _ in range(aval.ndim)]
    recombines = {}
    group = 1
    for d in range(aval.ndim - 1):
        row[d] = DimSharding(group=group)
        recombines[group] = [_concat(d)] * len(eqn.outvars)
        group += 1
    return {"space": ShardSpace([row]), "recombines": recombines}


# ------------------------------------------- dynamic slice / dynamic update

@register_preset("dynamic_slice")
def _dynamic_slice_rule(eqn, world_size):
    """Dims taken WHOLE (slice_sizes[d] == shape[d]) shard freely: the
    start index clamps to 0 there, so per-shard slices concat to the
    global slice.  GSPMD handles the baked slice_sizes under sharding —
    the eager harness cannot (full-size param vs shard-size operand),
    which keeps this rule analytic-only (see _CROSSCHECK_SKIP).  Scalar
    start-index operands ride along replicated (empty rows)."""
    avals = _tensor_avals(eqn)
    if not avals or avals[0].ndim == 0:
        return None
    operand, index_avals = avals[0], avals[1:]
    if any(a.ndim != 0 for a in index_avals):
        return None
    slice_sizes = eqn.params["slice_sizes"]
    op_row = [DimSharding() for _ in range(operand.ndim)]
    recombines = {}
    group = 1
    for d in range(operand.ndim):
        if slice_sizes[d] == operand.shape[d]:
            op_row[d] = DimSharding(group=group)
            recombines[group] = _concat(d)
            group += 1
    return {"space": ShardSpace([op_row] + [[] for _ in index_avals]),
            "recombines": recombines}


@register_preset("dynamic_update_slice")
def _dynamic_update_slice_rule(eqn, world_size):
    """Dims where the update covers the WHOLE operand dim shard freely
    (start clamps to 0; operand and update shard together, output concats).
    Analytic-only for the same reason as dynamic_slice."""
    avals = _tensor_avals(eqn)
    if len(avals) < 2 or avals[0].ndim == 0:
        return None
    operand, update, index_avals = avals[0], avals[1], avals[2:]
    if update.ndim != operand.ndim or any(a.ndim != 0 for a in index_avals):
        return None
    op_row = [DimSharding() for _ in range(operand.ndim)]
    upd_row = [DimSharding() for _ in range(update.ndim)]
    recombines = {}
    group = 1
    for d in range(operand.ndim):
        if update.shape[d] == operand.shape[d]:
            op_row[d] = DimSharding(group=group)
            upd_row[d] = DimSharding(group=group)
            recombines[group] = _concat(d)
            group += 1
    return {"space": ShardSpace([op_row, upd_row] +
                                [[] for _ in index_avals]),
            "recombines": recombines}


# --------------------------------------------------------------------- rng

@register_preset("threefry2x32")
def _threefry_rule(eqn, world_size):
    """The threefry2x32 counter hash is elementwise over its broadcast
    (k1, k2, x1, x2) operands: each output element depends only on the
    matching key/counter elements, so counter dims shard freely and both
    output words concat there.  Keys are usually scalar and ride along
    replicated."""
    avals = _tensor_avals(eqn)
    out_aval = eqn.outvars[0].aval
    rank = out_aval.ndim
    if rank == 0:
        return None
    for a in avals:
        if a.ndim not in (0, rank):
            return None
        if a.ndim == rank and any(s not in (1, out_aval.shape[d])
                                  for d, s in enumerate(a.shape)):
            return None
    n_out = len(eqn.outvars)
    table, recombines = [], {}
    group = 1
    dim_groups = {}
    for d in range(rank):
        dim_groups[d] = group
        recombines[group] = [_concat(d)] * n_out
        group += 1
    for a in avals:
        if a.ndim == 0:
            table.append([])
        else:
            table.append([DimSharding(group=dim_groups[d])
                          if a.shape[d] == out_aval.shape[d] != 1
                          else DimSharding()
                          for d in range(rank)])
    live = {d.group for row in table for d in row if d.group > 0}
    recombines = {g: fn for g, fn in recombines.items() if g in live}
    return {"space": ShardSpace(table), "recombines": recombines}


@register_preset("random_bits", "random_wrap", "random_unwrap",
                 "random_seed", "random_fold_in", "random_split")
def _random_rule(eqn, world_size):
    """Typed-key RNG primitives stay replicated: the counter stream is a
    function of flat element position, so a per-shard rebind would
    regenerate the full stream, not a slice of it.  An analytic replicate
    rule skips nshards x candidates of doomed probe executions (and the
    key<fry> avals the eager harness cannot materialize anyway)."""
    avals = _tensor_avals(eqn)
    return {"space": ShardSpace([[DimSharding() for _ in a.shape]
                                 for a in avals]),
            "recombines": {}}


# ------------------------------------------------------------- create ops

@register_preset("iota")
def _create_rule(eqn, world_size):
    """No tensor inputs to shard; output stays replicated (consumers slice
    for free under GSPMD)."""
    return {"space": ShardSpace([]), "recombines": {}}


def _binds_program_id(jaxpr, axis: int) -> bool:
    """Whether `jaxpr`, or any jaxpr nested in its equations' params (the
    `pl.when` branches, loops), reads the grid position or extent of
    `axis`."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("program_id", "num_programs") \
                and eqn.params.get("axis") == axis:
            return True
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns") and _binds_program_id(sub, axis):
                    return True
    return False


def pallas_row_extent(eqn) -> Optional[int]:
    """`n` when the `pallas_call` equation is ROW-PARALLEL over a leading
    extent of `n`, else None.  Read from the equation alone, by the rule
    below and by emission (api.py::_bind_pallas_rows) alike.

    Row-parallel: the grid's leading axis has the static extent `n`; every
    operand and every result has leading extent `n`, block size 1 along it,
    and an index map whose first output IS its first input (grid position
    `i` touches row `i` of everything and no other row); no scalar-prefetch
    operands (their contents may name rows); and the kernel body never
    reads the position or extent of grid axis 0.  Rows `[a, b)` of the
    results are then the same call on rows `[a, b)` of the operands.  The
    three flash training kernels qualify; the decode kernels carry
    `lengths` / the page table as prefetched scalars and do not."""
    from jax.experimental import pallas as pl

    gm = eqn.params.get("grid_mapping")
    if gm is None or gm.num_index_operands or not gm.grid \
            or getattr(gm, "num_dynamic_grid_bounds", 0):
        return None
    n = gm.grid[0]
    if not isinstance(n, int) or n < 1:
        return None
    avals = [v.aval for v in list(eqn.invars) + list(eqn.outvars)]
    if len(avals) != len(gm.block_mappings):
        return None
    for aval, bm in zip(avals, gm.block_mappings):
        shape = getattr(aval, "shape", ())
        index_map = bm.index_map_jaxpr.jaxpr
        if not shape or shape[0] != n or bm.array_aval.shape[0] != n \
                or bm.block_shape[0] not in (pl.Blocked(1), pl.Squeezed()) \
                or not index_map.invars \
                or index_map.outvars[0] is not index_map.invars[0]:
            return None
    if _binds_program_id(eqn.params["jaxpr"], 0):
        return None
    return n


@register_preset("pallas_call")
def _pallas_call_rule(eqn, world_size):
    """A row-parallel Pallas kernel (`pallas_row_extent`) is a shardable
    op with ONE shard group: dimension 0 of every operand and every result
    together, recombined by concatenation along 0.  Emission honours the
    placement by re-binding the kernel at the shard's row count under a
    `shard_map` (api.py::_bind_pallas_rows); an axis that does not divide
    the rows drops out of the pool like any indivisible dim and the call
    stays whole along it.  Nothing is padded.

    Any other `pallas_call` is replicated: its grid mapping bakes shapes
    this rule cannot re-derive and GSPMD cannot partition a Mosaic custom
    call, so emission runs it whole on every device.  Stating that
    analytically also spares nshards x candidates eager executions that
    could only fail.  Ring attention and the pipeline programs compose
    their kernels per shard inside their own `shard_map`."""
    avals = _tensor_avals(eqn)
    rows = [[DimSharding() for _ in a.shape] for a in avals]
    if pallas_row_extent(eqn) is None:
        return {"space": ShardSpace(rows), "recombines": {}}
    for row in rows:
        row[0] = DimSharding(group=1)
    return {"space": ShardSpace(rows),
            "recombines": {1: [_concat(0)] * len(eqn.outvars)},
            "shard_where_valid": True}


@register_preset("sharding_constraint")
def _sharding_constraint_rule(eqn, world_size):
    """User with_sharding_constraint markers pass through the solver as
    freely shardable identity ops; XLA enforces the user's constraint at
    emission (the scope_auto analog — reference easydist/scope_auto)."""
    (aval,) = _tensor_avals(eqn)
    row = [DimSharding(group=d + 1) for d in range(aval.ndim)]
    recombines = {d + 1: _concat(d) for d in range(aval.ndim)}
    return {"space": ShardSpace([row]), "recombines": recombines}


# ---------------------------------------------------- attention composite

def _attention_strategies(eqn, world_size, backward):
    """Explicit strategy pool for the ed_attention_{fwd,bwd} primitives
    (SURVEY §7 step 7: ring/Ulysses as solver-visible strategies).

    Rows: fwd (q, k, v) / bwd (q, k, v, dout), all [b, h, t, d].
    batch and head sharding are comm-free; seq sharding prices the cheaper
    of ring (ppermute) and Ulysses (all_to_all) as intrinsic cost, with the
    winning variant recorded in strategy meta for emission."""
    import numpy as np

    from easydist_tpu import config as edconfig
    from easydist_tpu.metashard.metair import Placement
    from easydist_tpu.ops.attention_prim import seq_strategy_costs

    q_aval = eqn.invars[0].aval
    b, h, t, d = q_aval.shape
    n_in = 4 if backward else 3
    n_out = 3 if backward else 1
    dtype_bytes = np.dtype(q_aval.dtype).itemsize

    def strat(dim):
        return ([Placement.shard(dim)] * n_in,
                [Placement.shard(dim)] * n_out)

    # MXU-bound compute proxy: 2 matmuls of 2*b*h*t^2*d flops each (the
    # backward does ~2.5x); bytes/hbm under-prices attention by the t/d
    # ratio at long sequence
    flops = 4.0 * b * h * float(t) * t * d * (2.5 if backward else 1.0)
    full_compute = flops / edconfig.peak_flops
    shard_compute = full_compute / world_size

    strategies = []
    if b % world_size == 0:
        ins, outs = strat(0)
        strategies.append((ins, outs, 0.0, shard_compute, None))
    if h % world_size == 0:
        ins, outs = strat(1)
        strategies.append((ins, outs, 0.0, shard_compute, None))
    if t % world_size == 0 and world_size > 1:
        ring, ulysses = seq_strategy_costs((b, h, t, d), dtype_bytes,
                                           world_size, backward)
        # Ulysses needs head divisibility for its head-shard inner compute
        if h % world_size == 0 and ulysses < ring:
            cost, variant = ulysses, "ulysses"
        else:
            cost, variant = ring, "ring"
        ins, outs = strat(2)
        strategies.append((ins, outs, cost, shard_compute,
                           {"variant": variant}))
    if not strategies:
        return None
    return {"space": None, "recombines": {}, "strategies": strategies,
            "compute": full_compute}


@register_preset("ed_attention_fwd")
def _attention_fwd_rule(eqn, world_size):
    return _attention_strategies(eqn, world_size, backward=False)


@register_preset("ed_attention_bwd")
def _attention_bwd_rule(eqn, world_size):
    return _attention_strategies(eqn, world_size, backward=True)
