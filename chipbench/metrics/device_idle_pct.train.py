"""Share of the traced part of the run in which no op ran on the device:
1 - (mean over the chips of each chip's union-busy time) / traced window."""

META = {"layer": "device", "unit": "%", "moves": "train_tokens_per_s_per_chip",
        "source": "device_trace"}


def read(run):
    if not run.get("busy"):
        return None
    return 100.0 * (1.0 - run["busy"]["busy_s"] / run["trace"]["window_s"])
