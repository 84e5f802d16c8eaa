"""Manual data-parallel and ZeRO modes (reference: easydist/torch/compile_dp.py).

The auto solver discovers DP on its own; these wrappers are the explicit
`parallel_mode="ddp"/"zero2"/"zero3"` equivalents (compile_dp.py:55-198),
expressed as sharding annotations + shard_map collectives instead of graph
surgery over NCCL ops:

  ddp    — batch sharded, params replicated, grads pmean'd
  zero2  — + optimizer state sharded over dp: reduce_scatter grads, update
           the local shard, all_gather updated params
  zero3  — fully sharded params AND moments: per-step all_gather of
           params for fw/bw, reduce_scatter grads, shard-local Adam

All gradient reductions route through `easydist_tpu.comm`: with the
default config the wrappers emit the exact historical collectives
(bitwise-identical programs); with `comm_quant_dtype`/`comm_bucket_bytes`
set, gradients travel block-quantized and/or fused into fixed-size
buckets (docs/COMM.md), with sensitive leaves (`comm_quant_skip`) kept at
full precision.

Two opt-in latency-hiding knobs ride on top (docs/COMM.md "Overlapped
flush"):

  * ``edconfig.comm_overlap`` — gradients are flushed in backward
    EMISSION order as a barrier-pinned chain (`comm.overlap`), letting
    XLA slide each collective under the remaining backward compute.
    Values are bitwise-identical to the sequential flush with
    quantization off.
  * ``grad_accum_microbatches=K`` (kwarg or the config default) — the
    batch is split into K microbatches accumulated in a scan; with
    overlap on, microbatch k's backward hides the reduction of
    microbatch k-1's gradients (double buffering).

With both knobs at their defaults the emitted programs are unchanged.

A third opt-in, ``step_guard`` (kwarg, default from
``EASYDIST_STEP_GUARD``), folds the NaN/Inf skip-and-hold guard
(resilience/guard.py) into the jitted step: the carry becomes
``(state, guard_state)`` (seed the second element with
``resilience.init_guard_state()``) and a non-finite step holds the
previous state instead of committing garbage.  Guard OFF takes the
historical code path untouched — the emitted program is bitwise-identical
(tested by jaxpr identity in tests/test_resilience/test_guard.py).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from easydist_tpu import comm
from easydist_tpu import config as edconfig
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def _accum_k(grad_accum_microbatches: Optional[int]) -> int:
    """Effective microbatch count: the kwarg wins, else the config knob;
    0/1 both mean no accumulation."""
    k = (edconfig.grad_accum_microbatches if grad_accum_microbatches is None
         else grad_accum_microbatches)
    return int(k) if k else 0


def _maybe_guard(step: Callable, step_guard: Optional[bool]) -> Callable:
    """Fold the NaN/Inf skip-and-hold guard into the (unjitted) step when
    requested; OFF returns `step` itself, so the guard-off trace cannot
    differ from pre-guard builds by construction."""
    on = (edconfig.resilience_step_guard if step_guard is None
          else bool(step_guard))
    if not on:
        return step
    from easydist_tpu.resilience.guard import guard_train_step

    return guard_train_step(step)


def _grad_paths(grads):
    """keystr paths of the grad tree's leaves, flat order (the
    comm_quant_skip opt-out matches against these)."""
    return [jax.tree_util.keystr(kp)
            for kp, _ in jax.tree_util.tree_flatten_with_path(grads)[0]]


def ddp_step(loss_fn: Callable, mesh, axis: str = "dp", lr: float = 1e-2,
             grad_accum_microbatches: Optional[int] = None,
             step_guard: Optional[bool] = None):
    """SGD DDP step: batch sharded over `axis`, grads averaged with psum.
    Returns step(params, batch...) -> (new_params, loss); with the guard
    on, step((params, guard_state), batch...) -> ((..., ...), loss)."""
    n = mesh.shape[axis]

    def local_step(params, *batch):
        k = _accum_k(grad_accum_microbatches)
        if k > 1:
            grads, loss = comm.accumulate_gradients(
                loss_fn, params, batch, axis_name=axis, axis_size=n,
                n_micro=k)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            order = (comm.grad_emission_order(loss_fn, params, *batch)
                     if edconfig.comm_overlap else None)
            grads = comm.reduce_gradients(grads, axis, n, op="pmean",
                                          emission_order=order)
        loss = jax.lax.pmean(loss, axis)
        new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                            params, grads)
        return new_params, loss

    def step(params, *batch):
        p_spec = jax.tree_util.tree_map(lambda _: P(), params)
        b_spec = tuple(P(axis) for _ in batch)
        fn = shard_map(local_step, mesh=mesh,
                       in_specs=(p_spec,) + b_spec,
                       out_specs=(p_spec, P()),
                       check_vma=False)
        return fn(params, *batch)

    return jax.jit(_maybe_guard(step, step_guard))


def zero_shard_params(params, mesh, axis: str = "dp"):
    """Shard every param leaf's dim 0 over `axis` when divisible (ZeRO-3
    placement); indivisible leaves stay replicated."""
    n = mesh.shape[axis]

    def place(p):
        if p.ndim > 0 and p.shape[0] % n == 0:
            return jax.device_put(p, NamedSharding(mesh, P(axis)))
        return jax.device_put(p, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(place, params)


def zero3_step(loss_fn: Callable, mesh, axis: str = "dp", lr: float = 1e-2,
               b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
               grad_accum_microbatches: Optional[int] = None,
               step_guard: Optional[bool] = None):
    """Adam ZeRO-3: parameters AND optimizer moments sharded over dp.

    Params live sharded on dim 0; each step all_gathers them for the
    forward/backward (XLA schedules gather/free per layer), reduce_scatters
    grads, and updates only the local shard (reference transform_fsdp
    shard_param=True, compile_dp.py:93-123).  Leaves that do not divide the
    axis stay replicated with pmean'd grads.

    Returns (step, init_state): state = (sharded_params, opt, count);
    step(state, *batch) -> (state, loss).
    """
    n = mesh.shape[axis]

    def shardable(p):
        return p.ndim > 0 and p.shape[0] % n == 0

    def init_state(params):
        def shard(p):
            if shardable(p):
                return jax.device_put(p, NamedSharding(mesh, P(axis)))
            return jax.device_put(p, NamedSharding(mesh, P()))

        sharded = jax.tree_util.tree_map(shard, params)
        def moment(p):
            return jnp.zeros_like(p)

        opt = {"mu": jax.tree_util.tree_map(moment, sharded),
               "nu": jax.tree_util.tree_map(moment, sharded)}
        return (sharded, opt, jnp.zeros((), jnp.int32))

    # local_step needs static knowledge of which leaves are sharded; build
    # it per params structure via a factory
    def make_step(shard_flags, tdef, grad_accum_microbatches=None):
        def local_step(flat_ps, flat_mu, flat_nu, count, *batch):
            full = [jax.lax.all_gather(p, axis, axis=0, tiled=True)
                    if flag else p
                    for p, flag in zip(flat_ps, shard_flags)]
            params = jax.tree_util.tree_unflatten(tdef, full)
            k = _accum_k(grad_accum_microbatches)
            overlap = bool(edconfig.comm_overlap)
            g_paths = _grad_paths(params)

            def reduce_leaf(i, g):
                if shard_flags[i]:
                    return comm.reduce_scatter_grad(g, axis, n,
                                                    path=g_paths[i])
                return comm.all_reduce_grad(g, axis, n, path=g_paths[i])

            if k > 1:
                # the reducer output is shard-shaped for flagged leaves —
                # exactly the local param shards' shapes
                order = (comm.grad_emission_order(loss_fn, params, *batch)
                         if overlap else None)

                def reduce_tree(gtree):
                    fg = jax.tree_util.tree_flatten(gtree)[0]
                    if overlap:
                        fg = comm.chain_leaf_reduces(fg, order, reduce_leaf)
                    else:
                        fg = [reduce_leaf(i, g) for i, g in enumerate(fg)]
                    return jax.tree_util.tree_unflatten(tdef, fg)

                acc_shapes = jax.tree_util.tree_unflatten(tdef, [
                    jax.ShapeDtypeStruct(jnp.shape(p), jnp.result_type(p))
                    for p in flat_ps])
                grads, loss = comm.accumulate_gradients(
                    loss_fn, params, batch, axis_name=axis, axis_size=n,
                    n_micro=k, reduce_tree=reduce_tree,
                    acc_shapes=acc_shapes, overlapped=overlap)
                flat_g = jax.tree_util.tree_flatten(grads)[0]
                pre_reduced = True
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
                flat_g = jax.tree_util.tree_flatten(grads)[0]
                if overlap:
                    # pre-reduce as a backward-ordered pinned chain; the
                    # Adam update below then consumes reduced shards
                    order = comm.grad_emission_order(loss_fn, params,
                                                     *batch)
                    flat_g = comm.chain_leaf_reduces(flat_g, order,
                                                     reduce_leaf)
                    pre_reduced = True
                else:
                    pre_reduced = False
            loss = jax.lax.pmean(loss, axis)
            count = count + 1
            c1 = 1 - b1 ** count.astype(jnp.float32)
            c2 = 1 - b2 ** count.astype(jnp.float32)
            new_p, new_m, new_v = [], [], []
            for i, (p_shard, g, m, v, flag) in enumerate(
                    zip(flat_ps, flat_g, flat_mu, flat_nu, shard_flags)):
                if not pre_reduced:
                    g = reduce_leaf(i, g)
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                new_p.append(p_shard - lr * (m / c1) / (jnp.sqrt(v / c2) + eps))
                new_m.append(m)
                new_v.append(v)
            return tuple(new_p), tuple(new_m), tuple(new_v), count, loss

        return local_step

    def step(state, *batch):
        params_shards, opt, count = state
        flat_p, tdef = jax.tree_util.tree_flatten(params_shards)
        # a leaf is sharded iff its global dim0 divides the axis; after
        # init_state the leaf still has GLOBAL shape (sharded array), so
        # shardable() applies directly
        shard_flags = tuple(shardable(p) for p in flat_p)
        local = make_step(shard_flags, tdef, grad_accum_microbatches)

        def spec_for(p, flag):
            return P(axis) if flag else P()

        p_specs = [spec_for(p, f) for p, f in zip(flat_p, shard_flags)]
        b_spec = tuple(P(axis) for _ in batch)
        fn = shard_map(
            local, mesh=mesh,
            in_specs=(tuple(p_specs), tuple(p_specs), tuple(p_specs), P())
            + b_spec,
            out_specs=(tuple(p_specs), tuple(p_specs), tuple(p_specs), P(),
                       P()),
            check_vma=False)
        flat_mu = jax.tree_util.tree_flatten(opt["mu"])[0]
        flat_nu = jax.tree_util.tree_flatten(opt["nu"])[0]
        new_p, new_m, new_v, count, loss = fn(tuple(flat_p), tuple(flat_mu),
                                              tuple(flat_nu), count, *batch)
        params = jax.tree_util.tree_unflatten(tdef, list(new_p))
        opt = {"mu": jax.tree_util.tree_unflatten(tdef, list(new_m)),
               "nu": jax.tree_util.tree_unflatten(tdef, list(new_v))}
        return (params, opt, count), loss

    return jax.jit(_maybe_guard(step, step_guard)), init_state


def zero2_step(loss_fn: Callable, mesh, axis: str = "dp", lr: float = 1e-2,
               b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
               grad_accum_microbatches: Optional[int] = None,
               step_guard: Optional[bool] = None):
    """Adam ZeRO-2: params replicated, optimizer moments sharded over dp.

    reduce_scatter(grads) -> local Adam shard update -> all_gather(params)
    (reference transform_fsdp shard_param=False, compile_dp.py:125-183).
    Leaves whose dim 0 does not divide the axis fall back to replicated
    moments with pmean'd grads.
    Returns (step, init_opt): step((params, opt, count), batch...) ->
    ((new_params, new_opt, count), loss).
    """
    n = mesh.shape[axis]

    def shardable(p):
        return p.ndim > 0 and p.shape[0] % n == 0

    def init_opt(params):
        def moment(p):
            if shardable(p):
                shard_shape = (p.shape[0] // n,) + p.shape[1:]
                z = jnp.zeros((n,) + shard_shape, p.dtype)
                return jax.device_put(z, NamedSharding(mesh, P(axis)))
            return jnp.zeros_like(p)

        return {"mu": jax.tree_util.tree_map(moment, params),
                "nu": jax.tree_util.tree_map(moment, params)}

    def local_step(params, mu, nu, count, *batch):
        flat_p, tdef = jax.tree_util.tree_flatten(params)
        g_paths = _grad_paths(params)
        k = _accum_k(grad_accum_microbatches)
        overlap = bool(edconfig.comm_overlap)

        def reduce_leaf(i, g):
            if shardable(flat_p[i]):
                # grads: [d0, ...] -> reduce_scatter -> [d0/n, ...]
                return comm.reduce_scatter_grad(g, axis, n, path=g_paths[i])
            return comm.all_reduce_grad(g, axis, n, path=g_paths[i])

        if k > 1:
            order = (comm.grad_emission_order(loss_fn, params, *batch)
                     if overlap else None)

            def reduce_tree(gtree):
                fg = jax.tree_util.tree_flatten(gtree)[0]
                if overlap:
                    fg = comm.chain_leaf_reduces(fg, order, reduce_leaf)
                else:
                    fg = [reduce_leaf(i, g) for i, g in enumerate(fg)]
                return jax.tree_util.tree_unflatten(tdef, fg)

            acc_shapes = jax.tree_util.tree_unflatten(tdef, [
                jax.ShapeDtypeStruct(
                    (p.shape[0] // n,) + p.shape[1:] if shardable(p)
                    else p.shape, jnp.result_type(p))
                for p in flat_p])
            grads, loss = comm.accumulate_gradients(
                loss_fn, params, batch, axis_name=axis, axis_size=n,
                n_micro=k, reduce_tree=reduce_tree, acc_shapes=acc_shapes,
                overlapped=overlap)
            flat_g = jax.tree_util.tree_flatten(grads)[0]
            pre_reduced = True
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            flat_g = jax.tree_util.tree_flatten(grads)[0]
            if overlap:
                order = comm.grad_emission_order(loss_fn, params, *batch)
                flat_g = comm.chain_leaf_reduces(flat_g, order, reduce_leaf)
                pre_reduced = True
            else:
                pre_reduced = False
        loss = jax.lax.pmean(loss, axis)
        count = count + 1
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def update(i, p, g, m, v):
            if shardable(p):
                g_shard = g if pre_reduced else reduce_leaf(i, g)
                m, v = m[0], v[0]
                p_shard = jax.lax.dynamic_slice_in_dim(
                    p, jax.lax.axis_index(axis) * g_shard.shape[0],
                    g_shard.shape[0], axis=0)
                m = b1 * m + (1 - b1) * g_shard
                v = b2 * v + (1 - b2) * g_shard * g_shard
                p_new = p_shard - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
                p_full = jax.lax.all_gather(p_new, axis, axis=0, tiled=True)
                return p_full, m[None], v[None]
            g = g if pre_reduced else reduce_leaf(i, g)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v

        flat_m = jax.tree_util.tree_flatten(mu)[0]
        flat_v = jax.tree_util.tree_flatten(nu)[0]
        new = [update(i, p, g, m, v) for i, (p, g, m, v) in
               enumerate(zip(flat_p, flat_g, flat_m, flat_v))]
        new_params = jax.tree_util.tree_unflatten(tdef, [t[0] for t in new])
        new_mu = jax.tree_util.tree_unflatten(tdef, [t[1] for t in new])
        new_nu = jax.tree_util.tree_unflatten(tdef, [t[2] for t in new])
        return new_params, new_mu, new_nu, count, loss

    def step(state, *batch):
        params, opt, count = state
        p_spec = jax.tree_util.tree_map(lambda _: P(), params)
        m_spec = jax.tree_util.tree_map(
            lambda p: P(axis) if shardable(p) else P(), params)
        b_spec = tuple(P(axis) for _ in batch)
        fn = shard_map(local_step, mesh=mesh,
                       in_specs=(p_spec, m_spec, m_spec, P()) + b_spec,
                       out_specs=(p_spec, m_spec, m_spec, P(), P()),
                       check_vma=False)
        new_params, mu, nu, count, loss = fn(params, opt["mu"], opt["nu"],
                                             count, *batch)
        return (new_params, {"mu": mu, "nu": nu}, count), loss

    return jax.jit(_maybe_guard(step, step_guard)), init_opt
