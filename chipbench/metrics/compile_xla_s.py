"""Seconds XLA took to compile the emitted step (or to load it from the
persistent cache): what the first steps took over the median later step, by
the host clock, summed over the first steps — the step compiles twice,
because the donated state comes back with other shardings than it was born
with (PERF.md section 5)."""

META = {"layer": "compile", "unit": "s", "moves": "setup_s",
        "source": "host_clock"}


def read(run):
    t = run.get("train")
    if not t:
        return None
    return sum(max(0.0, s - t["median_step_s"]) for s in t["first_steps_s"])
