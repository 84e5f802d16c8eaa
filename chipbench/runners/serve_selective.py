"""Serving cells of models with selective-state (Mamba-1) layers (Jamba,
`jamba`): `serve_delta.serve_family` — one `GenerationSession` on one chip
under the open loop of `runners/serve.py`, with the window, ramp, tail,
traced part and ONE replayed order of arrivals of `serve_latent.py` — with
this file's family, `JAMBA`: the weights, the decoder, the reference and its
two controls, the gauges and counters read, the readers logged unlisted and
the invariant of its pools.  An UNTRACED run logs the host's readers too
(`_log_host`): they read the program's span recorder, not a device trace,
and a run's p95 is read beside what the host took of its steps."""

import importlib
import statistics
import sys

from chipbench import kernel_costs_selective, session_timeline, weights_jamba
from chipbench.runners.serve_delta import Family, serve_family


def model_config(sizes: dict):
    from easydist_tpu.models.jamba import JambaConfig

    d = weights_jamba.dims(sizes)
    return JambaConfig(
        vocab=d["vocab"], dim=d["hidden"], layers=len(d["kinds"]),
        attn_period=sizes["attn_layer_period"],
        attn_offset=sizes["attn_layer_offset"], heads=d["q"],
        kv_heads=d["kv"], head_dim=d["hd"], ffn_dim=d["ffn"],
        d_state=d["state"], d_conv=d["taps"], expand=sizes["mamba_expand"],
        dt_rank=d["rank"], eps=float(sizes["rms_norm_eps"]),
        dtype="bfloat16")


def _decoder(sizes: dict):
    from easydist_tpu.models import jamba

    cfg = model_config(sizes)
    return jamba.decoder(cfg), cfg.vocab


def _selective_pools(sizes, pool, gauge_steps, window, log) -> dict:
    """A selective layer keeps one [state, inner] matrix a SLOT, whatever
    the sequences' lengths: the gauge, read off the leaves after every
    round, is one number all run long, and it is what the shapes say."""
    n_slots = pool.state.n_slots
    seen = {g["selective_state_bytes"] for _, g in gauge_steps
            if g["selective_state_bytes"] is not None}
    want = kernel_costs_selective.stored_state_bytes(n_slots, sizes)
    used = [g["state_slots_in_use"] for t, g in gauge_steps
            if window[0] <= t < window[1]
            and g["state_slots_in_use"] is not None]
    log(f"selective_state_bytes over the run: {sorted(seen)} (the shapes "
        f"give {want}: {n_slots} slots x "
        f"{kernel_costs_selective.state_layers(sizes)} layers x "
        f"{kernel_costs_selective.state_bytes(sizes)} bytes, stored as they "
        f"are needed); a sequence also holds "
        f"{kernel_costs_selective.conv_tail_bytes(sizes)} bytes of conv tail "
        f"a layer and {pool.page_bytes // pool.chunk} bytes of K/V a token "
        f"over the attention layers; state slots in use mean "
        f"{statistics.mean(used or [0]):.1f} max {max(used or [0])} of "
        f"{n_slots}")
    if seen != {want}:
        raise RuntimeError("the selective states moved, or hold another "
                           "size than one matrix a slot a layer")
    return {"state_slots_in_use": used, "state_slots": n_slots}


JAMBA = Family(
    weights=weights_jamba.jamba_params, decoder=_decoder, reference="jamba",
    controls=(("fp8 operands", "fp8_operands"),
              ("bf16 recurrence", "bf16_recurrence")),
    gauges=("selective_state_bytes", "state_slots_in_use", "kv_tokens_live"),
    counters=("tokens_generated", "decode_steps", "prefill_chunks",
              "selective_rows_updated", "selective_scan_positions",
              "prefill_pages_walked", "prefill_pages_bucket",
              "prefill_attn_pairs"),
    # the pool's share is the Granite cell's, unlisted; the host's share of
    # a step is listed for the Mistral cell alone and the seven of the
    # session's timeline for the three serving cells a test of the
    # benchmark's holds their lists to (PERF.md section 7 (a)): this cell's
    # name waits for a `benchmark` PR.  The last is this PR's: the paged
    # decode kernel at TWENTY query rows on one KV head
    unlisted=("state_pool_use_pct", "session_host_ms_per_step",
              "session_empty_pct", "decode_gap_host_ms",
              "prefill_gap_host_ms", "step_caller_ms",
              "decode_launch_readback_ms", "serve_compile_s",
              "serve_xla_compiles", "mqa_paged_decode_roofline"),
    pools=_selective_pools)
UNLISTED = JAMBA.unlisted


def _log_scan_rate(raw: dict) -> None:
    """The chunk scan is bound by `exp` and vector work: the state elements
    it updates a second, beside its share of the bytes' roofline."""
    from chipbench.metrics.selective_scan_roofline import scan_seconds

    positions = ((raw.get("trace") or {}).get("counted") or {}).get(
        "selective_scan_positions")
    secs = scan_seconds(raw)
    if positions and secs:
        rate = kernel_costs_selective.state_elements(
            positions, raw["sizes"]) / secs
        print(f"[chipbench] not reported: selective_scan_elements_per_s = "
              f"{rate:.4g} ({positions} real positions x layers in "
              f"{secs:.5f} s of the scan kernel)", file=sys.stderr,
              flush=True)


# the unlisted readers that take the span recorder alone (a traced run has
# them from `serve_family`, among `UNLISTED`)
HOST = ("session_host_ms_per_step", "session_empty_pct", "decode_gap_host_ms",
        "prefill_gap_host_ms", "step_caller_ms")


def _log_host(ctx, raw: dict) -> None:
    """What the host took of an untraced run's steps, and how long each
    program was in flight (dispatch to the end of its `.call`, the median
    over the run): a p95 that moved with the first and not the second moved
    on the host."""
    run = dict(raw, cell=ctx.cell, mix=ctx.mix)
    for name in HOST:
        reader = importlib.import_module("chipbench.metrics." + name)
        print(f"[chipbench] not reported: {name} = {reader.read(run)}",
              file=sys.stderr, flush=True)
    snap = session_timeline.snapshot(run)
    records = snap["spans"] if snap else []
    for label, name in (("decode", session_timeline.DECODE_CALL),
                        ("chunk", session_timeline.PREFILL_CALL)):
        flight = session_timeline.in_flight_ms(records, name)
        if flight:
            print(f"[chipbench] {label} program: in flight {flight[0]:.3f} "
                  f"ms over the run", file=sys.stderr, flush=True)


def run(ctx) -> dict:
    raw = serve_family(ctx, JAMBA)
    _log_scan_rate(raw)
    if not raw["trace"]:
        _log_host(ctx, raw)
    return raw

