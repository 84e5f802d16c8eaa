"""Mean wait for admission over the window's requests: from when a request
was due to the end of the first `step()` after which `snapshot_inflight()`
no longer shows it queued — the runner's clock, from outside the session."""

META = {"layer": "session", "unit": "ms", "moves": "token_gap_p95_ms",
        "source": "host_clock"}


def read(run):
    waits = (run.get("serve") or {}).get("admit_wait_s")
    return 1e3 * sum(waits) / len(waits) if waits else None
