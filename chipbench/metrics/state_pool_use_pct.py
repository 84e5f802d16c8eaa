"""Mean share of the recurrent-state pool's slots in use over the window:
the session's `state_slots_in_use` gauge (set every decode round; a slot is
held from admission to retirement), read by the runner after every step,
over the pool's slots.  4.19 MB a slot a state layer: this pool, not the
arena, is the memory that bounds concurrency."""

META = {"layer": "kv", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_counter"}


def read(run):
    s = run.get("serve") or {}
    used, slots = s.get("state_slots_in_use"), s.get("state_slots")
    if not used or not slots:
        return None
    return 100.0 * sum(used) / len(used) / slots
