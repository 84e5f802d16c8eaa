"""Profile-guided cost-model calibration (reference: the per-op profiling
DB feeding solver costs, easydist/utils/graph_profile_db.py + SURVEY §7
step 8d).

`calibrate(mesh)` microbenchmarks THIS backend — HBM-bound elementwise
bandwidth, collective launch latency (alpha) and wire bandwidth (beta) —
and persists the fit in the PerfDB.  `calibrate_overlap(mesh)` measures
the achieved comm/compute overlap fraction (what the backward-ordered
flush in `comm.overlap` actually hides) and persists it alongside.
`apply_calibration()` loads the stored fit into the solver's config so
strategy costs reflect measured hardware instead of datasheet defaults;
`apply_device_constants()` swaps the configured `peak_flops`/
`hbm_bandwidth` defaults for the REAL device kind's datasheet values
(prefix-matched; a CPU host keeps the configured constants, a TPU kind the
table does not know is an error).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from easydist_tpu import config as edconfig

logger = logging.getLogger(__name__)

_CAL_KEY = "cost_model_calibration"
# None = unchecked, False = checked & absent, True = applied
_applied = None
# same tri-state for the device-kind datasheet swap
_device_applied = None

# per-chip datasheet constants by device-kind prefix (lowercased; first
# match wins, so more specific prefixes come first).  peak_flops is the
# bf16 MXU peak — the bound on how fast independent compute can hide a
# collective; hbm_bandwidth in bytes/s.  The ONE table of peaks: bench.py
# and chip_smoke.py read it through `detect_device_constants`.  Source:
# Google Cloud TPU documentation, per-generation system architecture
# pages (v5e: 197 TFLOP/s bf16, 819 GB/s HBM).
_DEVICE_DATASHEET = (
    ("tpu v6 lite", {"peak_flops": 918e12, "hbm_bandwidth": 1.6e12}),
    ("tpu v5 lite", {"peak_flops": 197e12, "hbm_bandwidth": 8.1e11}),
    ("tpu v5", {"peak_flops": 459e12, "hbm_bandwidth": 2.765e12}),  # v5p
    ("tpu v4", {"peak_flops": 275e12, "hbm_bandwidth": 1.2e12}),
    ("tpu v3", {"peak_flops": 123e12, "hbm_bandwidth": 9.0e11}),
    ("tpu v2", {"peak_flops": 45e12, "hbm_bandwidth": 7.0e11}),
)


def _backend_key() -> str:
    return f"{jax.default_backend()}:{len(jax.devices())}"


def detect_device_constants(device_kind: Optional[str] = None
                            ) -> Optional[Dict[str, float]]:
    """Datasheet constants for `device_kind` (default: the first visible
    device).  None for a host that is not a TPU (CPU meshes keep the
    configured defaults); a TPU kind missing from the table raises — a
    cost model or an MFU priced against another chip's peak is wrong
    without saying so."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).lower()
    for prefix, consts in _DEVICE_DATASHEET:
        if kind.startswith(prefix):
            return dict(consts)
    if kind.startswith("tpu"):
        raise ValueError(
            f"unknown TPU device kind {device_kind!r}: add its datasheet "
            f"peaks to runtime/calibrate.py::_DEVICE_DATASHEET")
    return None


def device_memory_limit(mesh) -> Optional[int]:
    """Per-device memory limit in bytes as the mesh's first device of this
    process reports it (`memory_stats()["bytes_limit"]`; another host's
    devices cannot be asked), or None on a backend that reports none (CPU
    virtual meshes).  A TPU that reports none raises: the HBM cap drives
    remat and the MEM004 gate, and an assumed capacity there plans for a
    chip that is not the one in the machine."""
    dev = mesh.local_devices[0]
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if limit:
        return int(limit)
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no memory limit (memory_stats() = {stats!r})")
    return None


def apply_device_constants(force: bool = False) -> bool:
    """Replace the hardcoded `peak_flops`/`hbm_bandwidth` defaults with the
    real device kind's datasheet values.  Explicit env overrides
    (EASYDIST_PEAK_FLOPS / EASYDIST_HBM_BANDWIDTH) always win; unknown
    device kinds change nothing.  Returns True when a value was applied."""
    global _device_applied
    if _device_applied is not None and not force:
        return _device_applied
    if not edconfig.auto_device_constants:
        _device_applied = False
        return False
    consts = detect_device_constants()
    if not consts:
        _device_applied = False
        return False
    env_guard = {"peak_flops": "EASYDIST_PEAK_FLOPS",
                 "hbm_bandwidth": "EASYDIST_HBM_BANDWIDTH"}
    applied = False
    for name, value in consts.items():
        if env_guard.get(name) in os.environ:
            continue
        setattr(edconfig, name, float(value))
        applied = True
    _device_applied = applied
    if applied:
        logger.info("device constants from datasheet: %s",
                    {k: f"{v:.3e}" for k, v in consts.items()})
    return applied


def calibrate(mesh=None, axis: Optional[str] = None,
              persist: bool = True) -> Dict[str, float]:
    """Measure and (optionally) persist cost-model parameters.

    Returns {"hbm_bandwidth", "ici_bandwidth", "ici_latency"} in the
    solver's units (bytes/s, seconds/launch).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from easydist_tpu.utils.timer import time_per_call

    # HBM-bound bandwidth: big elementwise op, bytes moved = read + write
    n = 1 << 24  # 64 MiB f32
    x = jnp.ones((n,), jnp.float32)
    mul = jax.jit(lambda a: a * 1.000001)
    t = time_per_call(mul, (x,))
    hbm = 2 * 4 * n / max(t, 1e-9)

    result = {"hbm_bandwidth": float(hbm)}

    if mesh is not None and mesh.devices.size > 1:
        axis = axis or mesh.axis_names[0]
        world = mesh.shape[axis]

        # build ONE jitted collective; time_per_call warms each shape before
        # timing, so the loop measures dispatch+collective, never retracing
        ar = jax.jit(shard_map(lambda v: jax.lax.psum(v, axis), mesh=mesh,
                               in_specs=P(axis), out_specs=P(),
                               check_vma=False))

        big_elems = 1 << 22  # 16 MiB f32 global
        small_elems = world  # one element per shard
        t_big = time_per_call(ar, (jnp.ones((big_elems,), jnp.float32),))
        t_small = time_per_call(ar, (jnp.ones((small_elems,), jnp.float32),))
        # alpha-beta fit: t = alpha + bytes_wire / bw, with all_reduce wire
        # bytes = 2 * size * (n-1)/n
        alpha = max(t_small, 1e-9)
        result["ici_latency"] = float(alpha)
        if t_big > 1.25 * alpha:
            wire = 2 * 4 * big_elems * (world - 1) / world
            # plausibility clamp: a noisy denominator must not persist a
            # bandwidth that makes collectives near-free in every solve
            bw = min(wire / (t_big - alpha), 1e13)
            result["ici_bandwidth"] = float(bw)
        else:
            logger.warning(
                "collective timing is launch-dominated (t_big %.3es ~ "
                "alpha %.3es): keeping the configured ici_bandwidth", t_big,
                alpha)

    if persist:
        _persist_calibration(result)
    # fresh measurements take effect NOW, even if an earlier compile
    # already latched older (or default) values
    global _applied
    for name, value in result.items():
        if value > 0:
            setattr(edconfig, name, value)
    _applied = True
    logger.info("calibration (%s): %s", _backend_key(),
                {k: f"{v:.3e}" for k, v in result.items()})
    return result


def _persist_calibration(result: Dict[str, float]) -> None:
    """Merge `result` into this backend's PerfDB calibration entry — a
    calibrate() run must not drop a previously measured overlap fraction
    and vice versa."""
    from .perfdb import PerfDB

    db = PerfDB()
    try:
        entry = dict(db.get_op_perf(_CAL_KEY, _backend_key()) or {})
    except Exception:
        entry = {}
    entry.update(result)
    db.record_op_perf(_CAL_KEY, _backend_key(), entry)
    try:
        db.persist()
    except Exception:
        logger.warning("could not persist calibration")


def calibrate_overlap(mesh, axis: Optional[str] = None,
                      persist: bool = True,
                      n_elems: int = 1 << 22) -> Dict[str, float]:
    """Measure the achieved comm/compute overlap fraction on THIS backend
    (see `runtime.profiler.measure_collective_overlap`) and persist it as
    ``comm_overlap_ratio_measured``.

    This is what gates the solver's overlap discount: with
    ``comm_overlap_ratio_source="auto"`` (default) or ``"measured"``,
    `autoflow.cost_model.overlap_discount_ratio` uses this fraction
    instead of the flat `comm_overlap_ratio` guess, so
    ``predict_comm_overlap=1`` discounts by what the backward-ordered
    flush actually hides.
    """
    from .profiler import measure_collective_overlap

    measured = measure_collective_overlap(mesh, axis, n_elems=n_elems)
    frac = measured["overlap_fraction"]
    result = {"comm_overlap_ratio_measured": float(frac),
              "overlap_t_comm": measured["t_comm"],
              "overlap_t_compute": measured["t_compute"],
              "overlap_t_both": measured["t_both"]}
    if persist:
        _persist_calibration(result)
    global _applied
    edconfig.comm_overlap_ratio_measured = float(frac)
    _applied = True
    logger.info("overlap calibration (%s): fraction=%.3f (t_comm=%.3es "
                "t_compute=%.3es t_both=%.3es)", _backend_key(), frac,
                measured["t_comm"], measured["t_compute"],
                measured["t_both"])
    return result


def apply_calibration(force: bool = False) -> bool:
    """Load a stored calibration for this backend into the solver config.
    Returns True when values were applied.  Called automatically at the
    start of each fresh compile (cheap after the first lookup)."""
    global _applied
    # datasheet constants first so a measured hbm_bandwidth (below) can
    # still override the datasheet value; caches itself after one probe
    apply_device_constants(force=force)
    if _applied is not None and not force:
        return _applied
    try:
        from .perfdb import PerfDB

        entry = PerfDB().get_op_perf(_CAL_KEY, _backend_key())
    except Exception:
        entry = None
    if not entry:
        _applied = False  # negative result cached: no repeated DB reads
        return False
    for name in ("hbm_bandwidth", "ici_bandwidth", "ici_latency"):
        if name in entry and entry[name] > 0:
            setattr(edconfig, name, entry[name])
    if entry.get("comm_overlap_ratio_measured") is not None:
        # 0.0 is a VALID measurement (nothing overlapped) — keep it
        edconfig.comm_overlap_ratio_measured = float(
            entry["comm_overlap_ratio_measured"])
    _applied = True
    logger.info("applied cost-model calibration for %s", _backend_key())
    return True
