"""Share of each chip's busy time spent in collectives (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute), mean over the
chips, from the device trace.  Overlapping collectives count once."""

from chipbench import trace_reduce

META = {"layer": "emitted program", "unit": "%",
        "moves": "train_tokens_per_s_per_chip", "source": "device_trace"}


def read(run):
    if not run.get("trace") or run["chips"] < 2:
        return None
    trace = run["trace"]["trace"]
    coll = trace_reduce.op_seconds(trace, trace_reduce.COLLECTIVE)
    busy = run["busy"]["per_chip_s"]
    shares = [c / b for c, b in zip(coll, busy) if b > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None
