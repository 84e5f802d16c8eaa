"""Serving cells of models whose state layers are gated short convolutions
(LFM2-MoE, `lfm2_moe`): `serve_delta.serve_family` — one `GenerationSession`
on one chip under the open loop of `runners/serve.py`, with the window,
ramp, tail, traced part and ONE replayed order of arrivals of
`serve_latent.py` — with this file's family, `LFM2`: the weights, the
decoder, the reference and its two controls, the gauges and counters read,
the readers logged unlisted and the invariant of its pools.  An UNTRACED run
logs the host's readers too (`serve_selective._log_host`), and every run how
the routed pairs fell on the held experts' blocks."""

import statistics
import sys

from chipbench import kernel_costs_shortconv, weights_lfm2
from chipbench.runners.serve_delta import Family, serve_family
from chipbench.runners.serve_selective import _log_host


def model_config(sizes: dict):
    from easydist_tpu.models.lfm2_moe import Lfm2MoeConfig

    d = weights_lfm2.dims(sizes)
    return Lfm2MoeConfig(
        vocab=d["vocab"], dim=d["hidden"], layer_types=d["kinds"],
        dense_layers=d["dense_layers"], heads=d["q"], kv_heads=d["kv"],
        rope_theta=float(sizes["rope_theta"]), conv_taps=d["taps"],
        ffn_dim=d["dense"], experts=d["experts"], top_k=d["top_k"],
        experts_held=(d["first"], d["held"]), expert_dim=d["expert"],
        routed_scale=float(sizes["routed_scaling_factor"]),
        eps=float(sizes["norm_eps"]), dtype="bfloat16")


def _decoder(sizes: dict):
    from easydist_tpu.models import lfm2_moe

    cfg = model_config(sizes)
    return lfm2_moe.decoder(cfg), cfg.vocab


def _shortconv_pools(sizes, pool, gauge_steps, window, log) -> dict:
    """A conv layer keeps ONE tail a SLOT and nothing else, whatever the
    sequences' lengths: the gauge, read off the leaves after every round,
    is one number all run long, and it is what the shapes say."""
    n_slots = pool.state.n_slots
    seen = {g["shortconv_state_bytes"] for _, g in gauge_steps
            if g["shortconv_state_bytes"] is not None}
    want = kernel_costs_shortconv.stored_state_bytes(n_slots, sizes)
    used = [g["state_slots_in_use"] for t, g in gauge_steps
            if window[0] <= t < window[1]
            and g["state_slots_in_use"] is not None]
    live = [g["kv_tokens_live"] for t, g in gauge_steps
            if window[0] <= t < window[1]
            and g["kv_tokens_live"] is not None]
    log(f"shortconv_state_bytes over the run: {sorted(seen)} (the shapes "
        f"give {want}: {n_slots} slots x "
        f"{kernel_costs_shortconv.layers(sizes, 'conv')} conv layers x "
        f"{kernel_costs_shortconv.conv_tail_bytes(sizes)} bytes of tail, "
        f"and no state beside it); a sequence also holds "
        f"{pool.page_bytes // pool.chunk} bytes of K/V a token over the "
        f"attention layers; state slots in use mean "
        f"{statistics.mean(used or [0]):.1f} max {max(used or [0])} of "
        f"{n_slots}; live K/V tokens mean "
        f"{statistics.mean(live or [0]):.0f} max {max(live or [0])}")
    if seen != {want}:
        raise RuntimeError("the conv tails moved, or hold another size "
                           "than one tail a slot a conv layer")
    return {"state_slots_in_use": used, "state_slots": n_slots}


LFM2 = Family(
    weights=weights_lfm2.lfm2_params, decoder=_decoder,
    reference="lfm2_moe",
    controls=(("fp8 operands", "fp8_operands"),
              ("bf16 router scores", "bf16_router")),
    gauges=("shortconv_state_bytes", "state_slots_in_use", "kv_tokens_live"),
    counters=("tokens_generated", "decode_steps", "prefill_chunks",
              "shortconv_rows_updated", "shortconv_chunk_positions",
              "moe_rounds", "moe_pairs_routed", "moe_experts_hit",
              "moe_max_expert_pairs", "moe_pair_slots", "moe_prefill_calls",
              "moe_prefill_pairs_routed", "moe_prefill_experts_hit",
              "moe_prefill_max_expert_pairs", "moe_prefill_pair_slots",
              "prefill_pages_walked", "prefill_pages_bucket",
              "prefill_attn_pairs", "decode_pages_walked",
              "decode_pages_bucket"),
    # the pool's share and the experts' load are the Granite cell's,
    # unlisted; the host's share of a step is listed for the Mistral cell
    # alone and the seven of the session's timeline for the three serving
    # cells a test of the benchmark's holds their lists to (PERF.md section
    # 7 (a)): this cell's name waits for a `benchmark` PR
    unlisted=("state_pool_use_pct", "session_host_ms_per_step",
              "session_empty_pct", "decode_gap_host_ms",
              "prefill_gap_host_ms", "step_caller_ms",
              "decode_launch_readback_ms", "serve_compile_s",
              "serve_xla_compiles", "expert_load_max_over_mean"),
    pools=_shortconv_pools)
UNLISTED = LFM2.unlisted


def _log_chunk_attention(raw: dict) -> None:
    """The paged chunk kernel's share of its FLOP roofline at heads of 64:
    the score and value products of every (query, key) pair the traced
    chunk calls attended (`prefill_attn_pairs`, counted on the host from
    the rows' real extents: 4 x head_dim FLOPs a pair a query head, on the
    attention layers) at the matrix peak, over the kernel's seconds inside
    the chunk program's executions."""
    from chipbench import kernel_costs, programs, shortconv_trace

    counted = (raw.get("trace") or {}).get("counted") or {}
    pairs = counted.get("prefill_attn_pairs")
    secs = shortconv_trace.attention_seconds(raw, programs.PREFILL_CHUNK)
    if not pairs or not secs:
        return
    sizes = raw["sizes"]
    flops = 4.0 * pairs * sizes["num_attention_heads"] \
        * kernel_costs_shortconv.head_dim(sizes) \
        * kernel_costs_shortconv.layers(sizes, "full_attention")
    peak = kernel_costs.peaks(raw["trace"]["trace"]["device_kind"])
    share = 100.0 * flops / peak["bf16_flops_per_s"] / secs
    print(f"[chipbench] not reported: shortconv_attn_chunk_roofline = "
          f"{share:.4g} ({pairs} attended pairs in {secs:.5f} s of the "
          f"chunk kernel)", file=sys.stderr, flush=True)


def run(ctx) -> dict:
    raw = serve_family(ctx, LFM2)
    if raw["trace"]:
        _log_chunk_attention(raw)
    else:
        _log_host(ctx, raw)
    return raw
