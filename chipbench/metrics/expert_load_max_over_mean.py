"""How unevenly the traced decode rounds loaded the held experts: the
busiest held expert's (token, expert) pairs over the mean over the held
experts, each summed over layers and rounds (`moe_max_expert_pairs`,
`moe_pairs_routed`: counted on the device, read back with the round's
tokens).  1 is an even load; the grouped matmul's blocks, and so a round's
expert time, follow the busiest expert."""

META = {"layer": "emitted program", "unit": "ratio",
        "moves": "token_gap_p95_ms", "source": "program_counter"}


def read(run):
    n = (run.get("trace") or {}).get("counted")
    if not n or not n.get("moe_pairs_routed"):
        return None
    held = run["sizes"]["experts_held"][1]
    return n["moe_max_expert_pairs"] * held / n["moe_pairs_routed"]
