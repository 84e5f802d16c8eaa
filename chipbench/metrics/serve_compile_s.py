"""Seconds of set-up a serving run spent compiling: the sum over the run of
every `easydist.compile.trace | .discovery | .solve | .emit` span
(`compile_step`: tracing the program, the strategy or its cache, emission)
and every `easydist.step.compile` span (XLA's compile, or its load from the
persistent cache), whatever `fn`.  Logs the seconds by `fn` on stderr: which
program the seconds belong to."""

import sys

from chipbench import session_timeline

META = {"layer": "compile", "unit": "s", "moves": "setup_s",
        "source": "program_span"}


def read(run):
    snap = session_timeline.snapshot(run)
    by_fn = {}
    for r in snap["spans"] if snap else ():
        if r["name"] in session_timeline.COMPILE_SPANS:
            key = (r["attrs"].get("fn"), r["name"].rsplit(".", 1)[-1])
            by_fn[key] = by_fn.get(key, 0.0) + (r["t1_ns"] - r["t0_ns"]) / 1e9
    if not by_fn:
        return None
    print("[chipbench] compile seconds by (fn, phase): " + ", ".join(
        f"{fn} {phase} {s:.2f}" for (fn, phase), s in sorted(
            by_fn.items(), key=lambda kv: -kv[1])), file=sys.stderr,
        flush=True)
    return sum(by_fn.values())
