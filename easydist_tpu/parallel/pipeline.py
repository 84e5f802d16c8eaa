"""Pipeline parallelism as a single compiled SPMD program.

The reference implements PP with per-stage processes, eager NCCL P2P sends,
and Python schedule loops (easydist/torch/experimental/pp/runtime.py:113-700,
ScheduleGPipe :630, ScheduleDAPPLE :658).  On TPU the idiomatic design is a
single XLA program: every device runs the same `stage_fn` on its own stage's
weights (stacked on a leading stage axis sharded over the `pp` mesh axis),
activations rotate between neighbours with `lax.ppermute` inside a
`lax.scan` over pipeline ticks.  Autodiff through the scan yields the
backward pipeline automatically (ppermute transposes to the reverse
rotation), giving a GPipe-equivalent schedule; memory is controlled with
`jax.checkpoint` on the stage body (the XLA-era answer to 1F1B's
activation-memory motivation).

Requires homogeneous stages (transformer blocks) — heterogeneous first/last
layers (embedding, head) run outside the pipelined middle, which is how GPT
class models decompose naturally.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


@dataclass
class PipelineConfig:
    n_stages: int
    n_microbatches: int
    axis_name: str = "pp"
    # "gpipe" keeps all microbatch activations (scan); "remat" wraps the
    # stage body in jax.checkpoint to trade recompute for memory; "1f1b"
    # (spmd_pipeline_grad only) interleaves forward and backward ticks so at
    # most O(n_stages) microbatch residuals are live per device — the
    # DAPPLE/1F1B working-set profile (reference runtime.py:658-700)
    schedule: str = "gpipe"
    # hybrid PPxSPMD (reference compile_auto.py:683-715 mesh
    # ['pp','spmd0','spmd1']): shard the microbatch dim over a data axis
    # and/or stage params over a tensor axis, all inside the same program
    data_axis: Optional[str] = None  # shards microbatches' batch dim
    param_spec: Optional[object] = None  # extra PartitionSpec tail for params
    # virtual stages per device (interleaved, Megatron-style): the model
    # is split into n_virtual * n_stages chunks; chunk j runs on device
    # j % n_stages and stage_params carry a LEADING DIM of
    # n_virtual * n_stages.  Shrinks the pipeline bubble ~1/n_virtual.
    # Used by spmd_pipeline (forward) and spmd_pipeline_grad ("1f1b").
    n_virtual: int = 1


def _stage_param_specs(stage_params, config: PipelineConfig, axis: str,
                       replicate_stage: bool = False):
    """PartitionSpecs for stage-stacked params: leading dim over `pp`,
    optionally a tensor-parallel tail spec (per-leaf or uniform).

    replicate_stage=True leaves the leading (stage) dim unsharded — used
    on hybrid pp x data meshes where resharding an inside-jit-produced
    stage stack into a pp-sharded shard_map input is miscompiled (see
    the data_axis note in spmd_pipeline); the pipeline bodies then slice
    their stage by `axis_index` instead of receiving a pre-sliced shard.
    The tensor-parallel tail specs are preserved either way."""
    lead = None if replicate_stage else axis
    if config.param_spec is None:
        return jax.tree_util.tree_map(lambda _: P(lead), stage_params)
    is_spec = lambda x: isinstance(x, (tuple, P))  # noqa: E731
    p_leaves, p_td = jax.tree_util.tree_flatten(stage_params)
    s_leaves, s_td = jax.tree_util.tree_flatten(config.param_spec,
                                                is_leaf=is_spec)
    if s_td == p_td:
        # per-leaf spec tails (pytree matching stage_params)
        specs = [P(lead, *tuple(t)) for t in s_leaves]
        return jax.tree_util.tree_unflatten(p_td, specs)
    tail = tuple(config.param_spec)
    return jax.tree_util.tree_map(lambda _: P(lead, *tail), stage_params)


def spmd_pipeline(stage_fn: Callable, mesh, config: PipelineConfig):
    """Build fn(stage_params, microbatches) -> outputs.

    stage_params: pytree with leading dim n_stages (or
    n_virtual * n_stages when interleaving; sharded over `pp`).
    microbatches: [n_microbatches, microbatch..., features] (replicated).
    Returns outputs of the last stage, same leading microbatch layout,
    replicated across the pp axis.
    """
    S = config.n_stages
    M = config.n_microbatches
    axis = config.axis_name
    if mesh.shape[axis] != S:
        raise ValueError(f"mesh axis {axis!r} has size {mesh.shape[axis]}, "
                         f"expected n_stages={S}")

    body = stage_fn
    if config.schedule == "remat":
        body = jax.checkpoint(stage_fn)
    if config.n_virtual > 1:
        return _interleaved_forward(body, mesh, config)

    from easydist_tpu import config as edconfig

    if edconfig.enable_analyze:
        from easydist_tpu.analyze import (check_schedule_tables,
                                          gpipe_schedule_tables)

        check_schedule_tables(gpipe_schedule_tables(S, M), S, 1, M,
                              fwd_only=True, node="pipeline/gpipe")

    def pipelined(stage_params, microbatches):
        # stage-stacked params shard their leading dim over pp (optionally
        # with a tensor-parallel tail spec); microbatches shard their batch
        # dim over the data axis when configured.
        #
        # data_axis caveat: on a multi-axis (pp x data) mesh, feeding a
        # stage stack PRODUCED INSIDE the surrounding jit into a
        # pp-sharded in_spec is miscompiled by GSPMD — the reshard into
        # the manual region inserts a spurious all-reduce over the data
        # axis, scaling every stage's params by the data-axis size
        # (repro: jit(lambda ps, x: pipe(jnp.stack(ps), x)) on a (4, 2)
        # mesh applies each stage bias twice; pre-stacked args are
        # unaffected).  Work around it by passing the stage dim
        # REPLICATED and slicing each device's stage by axis_index
        # inside the manual region — an all-gather resolves that
        # resharding correctly.
        rep_stage = config.data_axis is not None
        param_specs = _stage_param_specs(stage_params, config, axis,
                                         replicate_stage=rep_stage)
        data_spec = P(None, config.data_axis) if config.data_axis else P()

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(param_specs, data_spec),
                           out_specs=data_spec,
                           check_vma=False)
        def run(params, x_mb):
            stage_id = jax.lax.axis_index(axis)
            if rep_stage:
                local = jax.tree_util.tree_map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, stage_id, 0, keepdims=False), params)
            else:
                local = jax.tree_util.tree_map(lambda p: p[0], params)
            T = M + S - 1
            mb_shape = x_mb.shape[1:]
            state0 = jnp.zeros(mb_shape, x_mb.dtype)
            out0 = jnp.zeros_like(x_mb)

            def tick(carry, t):
                state_in, outputs = carry
                # stage 0 ingests microbatch t while t < M
                mb_idx = jnp.clip(t, 0, M - 1)
                fresh = x_mb[mb_idx]
                inp = jnp.where(stage_id == 0, fresh, state_in)
                out = body(local, inp)
                # last stage emits microbatch t-(S-1) once the fill ends
                out_idx = jnp.clip(t - (S - 1), 0, M - 1)
                emit = jnp.logical_and(stage_id == S - 1, t >= S - 1)
                outputs = outputs.at[out_idx].set(
                    jnp.where(emit, out, outputs[out_idx]))
                nxt = jax.lax.ppermute(
                    out, axis, [(i, (i + 1) % S) for i in range(S)])
                return (nxt, outputs), None

            (_, outputs), _ = jax.lax.scan(tick, (state0, out0),
                                           jnp.arange(T))
            # outputs live on the last stage only; replicate over pp
            outputs = jax.lax.psum(
                jnp.where(stage_id == S - 1, outputs, jnp.zeros_like(outputs)),
                axis)
            return outputs

        return run(stage_params, microbatches)

    return pipelined


def stack_stage_params(per_stage_params):
    """[pytree per stage] -> single pytree with leading stage dim."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def _virtual_params_and_specs(stage_params, config, axis, V, S,
                              replicate_stage: bool = False):
    """[V*S, ...] stage params regrouped to [V, S, ...] with specs sharding
    the S dim over pp (shared by the interleaved forward and 1F1B paths).
    replicate_stage leaves the S dim unsharded (the data_axis reshard
    workaround — see spmd_pipeline)."""
    vparams = jax.tree_util.tree_map(
        lambda p: p.reshape((V, S) + p.shape[1:]), stage_params)
    base_specs = _stage_param_specs(stage_params, config, axis,
                                    replicate_stage=replicate_stage)
    vspecs = jax.tree_util.tree_map(
        lambda sp: P(None, *tuple(sp)), base_specs,
        is_leaf=lambda x: isinstance(x, P))
    data_spec = P(None, config.data_axis) if config.data_axis else P()
    return vparams, vspecs, data_spec


def _interleaved_forward(body, mesh, config: PipelineConfig):
    """Forward pipeline with V interleaved virtual chunks per device
    (chunk j on device j % S): the fwd half of the 1F1B supertick tables,
    shrinking the fill bubble ~1/V for inference pipelines."""
    S, M, V = config.n_stages, config.n_microbatches, config.n_virtual
    axis = config.axis_name
    tables = _1f1b_schedule_tables(S, V, M, fwd_only=True)
    U = tables["n_superticks"]

    def pipelined(stage_params, microbatches):
        # rep_stage: the data_axis reshard workaround (see spmd_pipeline)
        rep_stage = config.data_axis is not None
        vparams, vspecs, data_spec = _virtual_params_and_specs(
            stage_params, config, axis, V, S, replicate_stage=rep_stage)

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(vspecs, data_spec),
                           out_specs=data_spec, check_vma=False)
        def run(params, x_mb):
            tree = jax.tree_util
            s = jax.lax.axis_index(axis)
            if rep_stage:
                local = tree.tree_map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, s, 1, keepdims=False), params)  # [V, ...]
            else:
                local = tree.tree_map(lambda p: p[:, 0], params)  # [V, ...]
            MF, KF, FOK = (jnp.asarray(tables[k]) for k in
                           ("m_f", "k_f", "f_ok"))
            out0 = jnp.zeros_like(x_mb)
            zero_mb = jnp.zeros(x_mb.shape[1:], x_mb.dtype)

            def tick(carry, u):
                act_in, outputs = carry
                m_f, k_f, f_ok = MF[u, s], KF[u, s], FOK[u, s]
                local_f = tree.tree_map(lambda p: p[k_f], local)
                inp = jnp.where((s == 0) & (k_f == 0), x_mb[m_f], act_in)
                y = body(local_f, inp)
                emit = (s == S - 1) & (k_f == V - 1) & f_ok
                outputs = outputs.at[m_f].set(
                    jnp.where(emit, y, outputs[m_f]))
                act_out = jax.lax.ppermute(
                    y, axis, [(i, (i + 1) % S) for i in range(S)])
                return (act_out, outputs), None

            (_, outputs), _ = jax.lax.scan(tick, (zero_mb, out0),
                                           jnp.arange(U))
            return jax.lax.psum(
                jnp.where(s == S - 1, outputs, jnp.zeros_like(outputs)),
                axis)

        return run(vparams, microbatches)

    return pipelined


def spmd_pipeline_grad(stage_fn: Callable, loss_fn: Callable, mesh,
                       config: PipelineConfig, aux: bool = False):
    """Build fn(stage_params, microbatches, targets) -> (loss, grads).

    loss = mean over microbatches of ``loss_fn(last_stage_out_mb, target_mb)``;
    grads match ``jax.grad`` of the equivalent non-pipelined step exactly.

    With ``aux=True`` the loss takes trailing parameters (a model head) and
    the pipeline also backpropagates to its inputs:
    ``loss_fn(out_mb, target_mb, loss_params)``; the built function becomes
    ``fn(stage_params, microbatches, targets, loss_params) ->
    (loss, stage_grads, dmicrobatches, dloss_params)`` — everything needed
    to embed the pipelined middle inside a larger model (embedding in front,
    head behind), reference compile_pipeline.py's full-model stage split.

    schedule="gpipe"/"remat": differentiate through the forward pipeline
    scan — all M microbatch residuals stay live through the backward sweep.

    schedule="1f1b": DAPPLE-class one-forward-one-backward (reference
    ScheduleDAPPLE, pp/runtime.py:658-700) re-designed as a single lockstep
    SPMD scan, the TPU-idiomatic form: every "supertick" each device runs
    one (masked) forward AND one (masked) backward, activations ppermute up
    the ring while gradients ppermute down, and XLA overlaps both transfers
    with compute.  Supertick clock: fwd(s, m) at u = s + m, bwd(s, m) at
    u = 2S - 2 - s + m, total U = M + 2S - 2 superticks.  Each stage keeps
    at most min(2S-1, M) microbatches of vjp residuals in a ring buffer —
    the 1F1B O(n_stages) working set — versus gpipe's O(M).  Residual
    leaves that are just the (tick-invariant) stage params are detected by
    tracer identity and NOT stored per-slot.  In steady state every device
    does one full fwd + bwd of useful work per supertick, so the bubble is
    2(2S-2) supertick-halves against gpipe's 2(S-1) — the classic 1F1B
    trade: slightly wider bubble bound, O(S) instead of O(M) memory, no
    recompute (unlike schedule="remat").
    """
    S = config.n_stages
    M = config.n_microbatches
    axis = config.axis_name
    if mesh.shape[axis] != S:
        raise ValueError(f"mesh axis {axis!r} has size {mesh.shape[axis]}, "
                         f"expected n_stages={S}")

    if config.schedule in ("gpipe", "remat"):
        fwd_pipe = spmd_pipeline(stage_fn, mesh, config)

        if aux:
            def pipelined(stage_params, microbatches, targets, loss_params):
                def total_loss(sp, mbs, lp):
                    outs = fwd_pipe(sp, mbs)
                    return jnp.mean(jax.vmap(
                        lambda o, t: loss_fn(o, t, lp))(outs, targets))

                loss, (dsp, dmb, dlp) = jax.value_and_grad(
                    total_loss, argnums=(0, 1, 2))(
                        stage_params, microbatches, loss_params)
                return loss, dsp, dmb, dlp
        else:
            def pipelined(stage_params, microbatches, targets):
                def total_loss(sp):
                    outs = fwd_pipe(sp, microbatches)
                    return jnp.mean(jax.vmap(loss_fn)(outs, targets))

                return jax.value_and_grad(total_loss)(stage_params)

        return pipelined

    if config.schedule != "1f1b":
        raise ValueError(f"unknown schedule {config.schedule!r}")

    body = stage_fn
    V = max(1, config.n_virtual)
    tables = _1f1b_schedule_tables(S, V, M)
    R = tables["ring"]
    U = tables["n_superticks"]
    loss3 = loss_fn if aux else (lambda o, t, lp: loss_fn(o, t))

    def pipelined(stage_params, microbatches, targets, loss_params=None):
        lp_in = loss_params if aux else ()
        # stage-stacked params [V*S, ...] regrouped to [V, S, ...]: chunk k
        # of device s is global stage k*S + s.  With a data axis the
        # params enter REPLICATED over the stage dim and each device
        # slices its stage by axis_index (the data_axis reshard
        # workaround — see spmd_pipeline); the grads still leave
        # stage-SHARDED, so the output spec keeps the pp-sharded form.
        rep_stage = config.data_axis is not None
        vparams, vspecs, data_spec = _virtual_params_and_specs(
            stage_params, config, axis, V, S)
        vspecs_in = vspecs
        if rep_stage:
            _, vspecs_in, _ = _virtual_params_and_specs(
                stage_params, config, axis, V, S, replicate_stage=True)

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(vspecs_in, data_spec, data_spec, P()),
                           out_specs=(P(), vspecs, data_spec, P()),
                           check_vma=False)
        def run(params, x_mb, tgt_mb, lp):
            tree = jax.tree_util
            s = jax.lax.axis_index(axis)
            if rep_stage:
                local = tree.tree_map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, s, 1, keepdims=False), params)  # [V, ...]
            else:
                local = tree.tree_map(lambda p: p[:, 0], params)  # [V, ...]
            mb_shape = x_mb.shape[1:]

            MF, KF, FOK = (jnp.asarray(tables[k]) for k in
                           ("m_f", "k_f", "f_ok"))
            MB, KB, BOK = (jnp.asarray(tables[k]) for k in
                           ("m_b", "k_b", "b_ok"))

            # Probe the vjp residual structure once (dead code after trace:
            # only the treedef and which-leaves-are-params survive).  Leaves
            # that ARE a chunk-param leaf (tracer identity) are rebuilt from
            # `local` at backward time instead of being stored per ring slot.
            local0 = tree.tree_map(lambda p: p[0], local)
            probe_leaves = tree.tree_leaves(local0)
            _, vjp0 = jax.vjp(body, local0, jnp.zeros(mb_shape, x_mb.dtype))
            leaves0, res_tree = tree.tree_flatten(vjp0)
            shared_idx = [
                next((j for j, q in enumerate(probe_leaves) if l is q), -1)
                for l in leaves0]
            store_idx = [i for i, si in enumerate(shared_idx) if si < 0]
            rings0 = [jnp.zeros((V, R) + tuple(leaves0[i].shape),
                                leaves0[i].dtype) for i in store_idx]

            zero_mb = jnp.zeros(mb_shape, x_mb.dtype)
            dacc0 = tree.tree_map(jnp.zeros_like, local)
            dxs0 = jnp.zeros_like(x_mb)
            dlp0 = tree.tree_map(jnp.zeros_like, lp)

            def tick(carry, u):
                act_in, g_in, rings, dacc, lacc, dxs, dlp_acc = carry

                # ---- forward half
                m_f, k_f, f_ok = MF[u, s], KF[u, s], FOK[u, s]
                local_f = tree.tree_map(lambda p: p[k_f], local)
                inp = jnp.where((s == 0) & (k_f == 0), x_mb[m_f], act_in)
                y, vjp = jax.vjp(body, local_f, inp)
                leaves = tree.tree_flatten(vjp)[0]
                slot_f = m_f % R
                rings = [
                    r.at[k_f, slot_f].set(
                        jnp.where(f_ok, leaves[i], r[k_f, slot_f]))
                    for r, i in zip(rings, store_idx)]

                # the final chunk's stage turns around in the same
                # supertick: loss grad of THIS microbatch feeds its
                # backward.  The head loss (+vjp) can be as heavy as a
                # stage (GPT logits at vocab scale), so gate it behind a
                # per-device conditional — only the last stage's turnaround
                # ticks pay it.  (loss_fn must not contain collectives.)
                m_b, k_b, b_ok = MB[u, s], KB[u, s], BOK[u, s]
                # stage S-1 chunk V-1 has fwd and bwd of one microbatch in
                # the same supertick, so one predicate covers lval, g, dlp
                pred = (s == S - 1) & (k_f == V - 1) & f_ok

                def loss_branch(args):
                    y_, t_, lp_ = args
                    lval, loss_vjp = jax.vjp(loss3, y_, t_, lp_)
                    g_, _, dlp_ = loss_vjp(jnp.ones_like(lval) / M)
                    return jnp.float32(lval), g_, dlp_

                def zero_branch(args):
                    y_, _, lp_ = args
                    return (jnp.float32(0.0), jnp.zeros_like(y_),
                            tree.tree_map(jnp.zeros_like, lp_))

                lval, g_last, dlp_t = jax.lax.cond(
                    pred, loss_branch, zero_branch, (y, tgt_mb[m_b], lp))
                g = jnp.where(pred, g_last, g_in)
                lacc = lacc + lval
                dlp_acc = tree.tree_map(lambda a, d: a + d, dlp_acc, dlp_t)

                # ---- backward half: rebuild the saved vjp and apply it
                local_b = tree.tree_map(lambda p: p[k_b], local)
                pl_b = tree.tree_leaves(local_b)
                slot_b = m_b % R
                stored = iter(range(len(store_idx)))
                rebuilt = [
                    pl_b[shared_idx[i]] if shared_idx[i] >= 0
                    else rings[next(stored)][k_b, slot_b]
                    for i in range(len(leaves))]
                dp, dx = tree.tree_unflatten(res_tree, rebuilt)(g)
                dacc = tree.tree_map(
                    lambda a, d: a.at[k_b].add(jnp.where(b_ok, d, 0)),
                    dacc, dp)
                # pipeline-input grads surface at stage 0's chunk-0 backward
                dxs = dxs.at[m_b].set(jnp.where(
                    (s == 0) & (k_b == 0) & b_ok, dx, dxs[m_b]))

                # activations ride up the ring, gradients ride down
                act_out = jax.lax.ppermute(
                    y, axis, [(i, (i + 1) % S) for i in range(S)])
                g_out = jax.lax.ppermute(
                    dx, axis, [(i, (i - 1) % S) for i in range(S)])
                return (act_out, g_out, rings, dacc, lacc, dxs, dlp_acc), None

            carry0 = (zero_mb, zero_mb, rings0, dacc0, jnp.float32(0.0),
                      dxs0, dlp0)
            (_, _, _, dacc, lacc, dxs, dlp_acc), _ = jax.lax.scan(
                tick, carry0, jnp.arange(U))

            loss = jax.lax.psum(
                jnp.where(s == S - 1, lacc, 0.0), axis) / M
            # input grads live on stage 0, head grads on the last stage;
            # replicate both across pp
            dxs = jax.lax.psum(dxs, axis)
            dlp_acc = tree.tree_map(lambda d: jax.lax.psum(d, axis), dlp_acc)
            if config.data_axis:
                loss = jax.lax.pmean(loss, config.data_axis)
                dacc = tree.tree_map(
                    lambda d: jax.lax.pmean(d, config.data_axis), dacc)
                dlp_acc = tree.tree_map(
                    lambda d: jax.lax.pmean(d, config.data_axis), dlp_acc)
                # input grads stay per-shard but must reflect the GLOBAL
                # mean loss: d(mean of shard means)/dx = (1/dp) d(local)/dx
                dxs = dxs / mesh.shape[config.data_axis]
            grads = tree.tree_map(lambda d: d[:, None], dacc)
            return loss, grads, dxs, dlp_acc

        loss, vgrads, dxs, dlp = run(vparams, microbatches, targets, lp_in)
        grads = jax.tree_util.tree_map(
            lambda g, p: g.reshape(p.shape), vgrads, stage_params)
        if aux:
            return loss, grads, dxs, dlp
        return loss, grads

    return pipelined


def _1f1b_schedule_tables(S: int, V: int, M: int,
                          fwd_only: bool = False):
    """Host-side supertick schedule for (interleaved) 1F1B.

    Global stage j = k*S + s (chunk k on device s), J = V*S stages.
    Microbatches run in groups of S (Megatron interleaving):
      fwd(j, m) at u = j + (m % S) + (m // S) * V*S
      bwd(j, m) at u = (2J - 2 - j) + (m % S) + (m // S) * V*S
    Consecutive stages are exactly one supertick apart (device +1 ring for
    activations, -1 for grads), each device has at most one fwd and one bwd
    unit per supertick, and the final chunk's last stage turns a microbatch
    around within its own supertick.  Returns [U, S] int32/bool lookup
    tables plus the residual ring size (max in-flight microbatches per
    (device, chunk) — the O(S·V) 1F1B working set).
    """
    import numpy as np

    J = V * S
    stride = V * S

    def u_f(j, m):
        return j + (m % S) + (m // S) * stride

    def u_b(j, m):
        return (2 * J - 2 - j) + (m % S) + (m // S) * stride

    U = u_f(J - 1, M - 1) + 1 if fwd_only else u_b(0, M - 1) + 1
    m_f = np.zeros((U, S), np.int32)
    k_f = np.zeros((U, S), np.int32)
    f_ok = np.zeros((U, S), bool)
    m_b = np.zeros((U, S), np.int32)
    k_b = np.zeros((U, S), np.int32)
    b_ok = np.zeros((U, S), bool)
    ring = 1
    for s in range(S):
        for k in range(V):
            j = k * S + s
            for m in range(M):
                uf = u_f(j, m)
                assert not f_ok[uf, s], "fwd slot conflict"
                m_f[uf, s], k_f[uf, s], f_ok[uf, s] = m, k, True
                if fwd_only:
                    continue
                ub = u_b(j, m)
                assert not b_ok[ub, s], "bwd slot conflict"
                m_b[ub, s], k_b[ub, s], b_ok[ub, s] = m, k, True
            if fwd_only:
                continue
            # max in-flight microbatches for this (device, chunk): FIFO, so
            # the live set is a contiguous m-window and `m % ring` is unique
            live = max(
                sum(1 for m2 in range(M) if u_f(j, m2) <= u_b(j, m1))
                - m1 for m1 in range(M))
            ring = max(ring, live)
    tables = {"m_f": m_f, "k_f": k_f, "f_ok": f_ok,
              "m_b": m_b, "k_b": k_b, "b_ok": b_ok,
              "n_superticks": U, "ring": ring}

    # build-time schedule lint (easydist_tpu.analyze SCHED rules): the
    # lockstep scan runs masked garbage ticks rather than crashing on a
    # bad table, so dependency/stash bugs must be caught HERE
    from easydist_tpu import config as edconfig

    if edconfig.enable_analyze:
        from easydist_tpu.analyze import check_schedule_tables

        check_schedule_tables(
            tables, S, V, M, fwd_only=fwd_only,
            node="pipeline/interleaved-fwd" if fwd_only
            else "pipeline/1f1b")
    return tables
