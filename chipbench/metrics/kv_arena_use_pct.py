"""Mean share of the KV arena's pages in use over the window: the session's
`kv_pages_in_use` gauge (set every decode round), read by the runner after
every step, over the arena's pages."""

META = {"layer": "kv", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_counter"}


def read(run):
    s = run.get("serve") or {}
    used, pages = s.get("kv_pages_in_use"), s.get("arena_pages")
    if not used or not pages:
        return None
    return 100.0 * sum(used) / len(used) / pages
