"""Seconds `easydist_compile` spent planning in this run: trace + discovery
+ solve, as `CompileResult.phase_seconds` has them (a strategy served from
`.easydist_cache` leaves discovery and solve at 0)."""

META = {"layer": "compile", "unit": "s", "moves": "setup_s",
        "source": "program_counter"}


def read(run):
    phases = (run.get("train") or {}).get("phase_seconds")
    return sum(phases.values()) if phases else None
