"""Profiling & cost analysis on compiled programs.

TPU replacement for the reference's profiling stack: per-op runtime
benchmarking (passes/runtime_prof.py) becomes XLA cost analysis + the
`easydist.step.call` spans of `runtime/spans.py`; the CUPTI C++ stream tracer
(csrc/stream_tracer.cpp) becomes `jax.profiler` traces (XLA already exposes
per-op scheduling); allocator profiling becomes `memory_analysis()` on the
compiled executable.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from .perfdb import PerfDB


def _as_executable(compiled):
    """Accepts a jax Compiled object or our CompileResult."""
    if hasattr(compiled, "executable"):  # CompileResult
        return compiled.executable()
    return compiled


def op_cost_analysis(compiled) -> Dict[str, float]:
    """FLOPs / bytes-accessed / estimated seconds from XLA for a compiled
    function (jax `Compiled` object or our CompileResult)."""
    compiled = _as_executable(compiled)
    if hasattr(compiled, "cost_analysis"):
        cost = compiled.cost_analysis()
    else:
        raise TypeError("expected a lowered+compiled jax function")
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def memory_analysis(compiled) -> Dict[str, int]:
    """Per-device memory breakdown of the compiled executable."""
    compiled = _as_executable(compiled)
    mem = compiled.memory_analysis()
    out = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = getattr(mem, attr)
    return out


def serving_history(sub_key: str = "engine",
                    db: Optional[PerfDB] = None) -> list:
    """Recorded serving-metrics snapshots for one engine (the export
    target of `easydist_tpu.serve.ServeMetrics.export`): bounded history
    of {counters, gauges, latency percentiles, batch_occupancy,
    compile_cache_hit_rate} dicts, oldest first.  Step and phase times are
    not kept here: they are spans (`runtime/spans.py`)."""
    if db is None:
        db = PerfDB()
    return db.get_op_perf("serving", sub_key) or []


def measure_collective_overlap(mesh, axis: Optional[str] = None,
                               n_elems: int = 1 << 22,
                               compute_dim: int = 256,
                               iters: int = 12,
                               repeats: int = 3) -> Dict[str, float]:
    """Measure how much of an all-reduce's wire time this backend hides
    under independent compute.

    Times three compiled programs on `mesh` over `axis`:
      t_comm     an all-reduce of an ``n_elems`` f32 vector, alone;
      t_compute  a chained matmul on an independent operand, alone;
      t_both     both in ONE program with no data dependence between them,
                 so the latency-hiding scheduler MAY overlap them.

    overlap_fraction = clamp((t_comm + t_compute - t_both)
                             / min(t_comm, t_compute), 0, 1):
    0 means fully serialized (every wire second exposed), 1 means the
    shorter of the two is fully hidden.  This is the ground truth behind
    the solver's overlap discount (`autoflow.cost_model.
    overlap_discount_ratio`); `runtime.calibrate.calibrate_overlap`
    persists it per backend.

    Each timing is the MIN over ``repeats`` independent samples:
    scheduler noise only inflates wall time, and a transient spike on
    t_both alone would otherwise read as negative overlap.  The default
    sizes put t_comm and t_compute within ~2x of each other on both the
    virtual CPU mesh and a single TPU host — the numerator is a
    DIFFERENCE, so wildly imbalanced operands would bury the overlap
    signal in the larger term's noise floor.
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from easydist_tpu.utils.timer import time_per_call

    axis = axis or mesh.axis_names[0]
    world = mesh.shape[axis]
    n_elems = max(world, n_elems - n_elems % world)

    def matmuls(a):
        for _ in range(4):
            a = a @ a * 1e-3
        return a

    def comm_body(v):
        return jax.lax.psum(v, axis)

    def both_body(v, a):
        return jax.lax.psum(v, axis), matmuls(a)

    comm_fn = jax.jit(shard_map(comm_body, mesh=mesh, in_specs=P(axis),
                                out_specs=P(), check_vma=False))
    comp_fn = jax.jit(shard_map(matmuls, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False))
    both_fn = jax.jit(shard_map(both_body, mesh=mesh,
                                in_specs=(P(axis), P()),
                                out_specs=(P(), P()), check_vma=False))

    v = jnp.ones((n_elems,), jnp.float32)
    a = jnp.ones((compute_dim, compute_dim), jnp.float32) * 1e-2
    repeats = max(1, repeats)
    # interleaved rounds so slow machine-load drift hits all three alike
    t_comm = t_compute = t_both = float("inf")
    for _ in range(repeats):
        t_comm = min(t_comm, time_per_call(comm_fn, (v,), iters=iters))
        t_compute = min(t_compute, time_per_call(comp_fn, (a,), iters=iters))
        t_both = min(t_both, time_per_call(both_fn, (v, a), iters=iters))

    hidden = t_comm + t_compute - t_both
    frac = hidden / max(min(t_comm, t_compute), 1e-12)
    return {"t_comm": float(t_comm), "t_compute": float(t_compute),
            "t_both": float(t_both),
            "overlap_fraction": float(min(max(frac, 0.0), 1.0))}
