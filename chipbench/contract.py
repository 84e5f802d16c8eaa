"""The last line, checked before it is printed.  `check_last_line` holds
the object to what the driver reads: a violation is an exception (run.py
turns it into a nonzero exit with the reason on stderr), never a malformed
line."""

import json
import math
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)


class ContractError(Exception):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, trace: bool) -> dict:
    """{metric name: unit} the last line of this cell must give: its
    end-to-end metrics, and in a traced run its per-layer metrics too."""
    if cell not in [w["name"] for w in bench["workloads"]]:
        raise ContractError(f"BENCHMARK.json names no workload {cell!r}")
    kinds = ("end_to_end", "per_layer") if trace else ("end_to_end",)
    return {m["name"]: m["unit"] for kind in kinds for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]}


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def check_last_line(obj: dict, cell: dict, trace: bool,
                    bench: dict = None) -> None:
    """`cell` is the workload's entry of BENCHMARK.json (name, chips)."""
    bench = bench or load_benchmark()
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            raise ContractError(f"last line lacks the key {key!r}")
    if not isinstance(obj["correct"], bool):
        raise ContractError(f"correct is {obj['correct']!r}, not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(obj[key], int) or isinstance(obj[key], bool) \
                or obj[key] < 0:
            raise ContractError(f"{key} is {obj[key]!r}, not a count")
    if obj["failed"] > obj["attempted"]:
        raise ContractError("failed is above attempted")

    want = cell_metrics(bench, cell["name"], trace)
    got = obj["metrics"]
    for name, unit in want.items():
        if name not in got:
            raise ContractError(
                f"metric {name!r} of workload {cell['name']!r} is missing "
                f"(--trace {int(trace)})")
        entry = got[name]
        if not isinstance(entry, dict) or set(entry) != {"value", "unit"}:
            raise ContractError(f"metric {name!r} is {entry!r}, not "
                                f"{{value, unit}}")
        if not _number(entry["value"]):
            raise ContractError(f"metric {name!r} has the value "
                                f"{entry['value']!r}, not a finite number")
        if entry["unit"] != unit:
            raise ContractError(f"metric {name!r} has the unit "
                                f"{entry['unit']!r}, BENCHMARK.json says "
                                f"{unit!r}")
        if name.endswith("_roofline") or "mfu" in name:
            if entry["value"] > 105.0:
                raise ContractError(f"{name} reads {entry['value']}% of a "
                                    f"peak: the count or the time is wrong")
    for name in got:
        if name not in want:
            raise ContractError(f"metric {name!r} is not one of workload "
                                f"{cell['name']!r} in BENCHMARK.json")

    dev = obj["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            raise ContractError(f"device lacks the key {key!r}")
    if not isinstance(dev["platform"], str) or not isinstance(dev["kind"],
                                                               str):
        raise ContractError("device.platform and device.kind are strings")
    if dev["count"] != cell["chips"]:
        raise ContractError(f"device.count is {dev['count']!r}, the cell "
                            f"has {cell['chips']} chips")
    if not _number(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] <= 0:
        raise ContractError(f"device.memory_peak_bytes is "
                            f"{dev['memory_peak_bytes']!r}")
    if trace:
        for key in ("busy_s", "window_s"):
            if not _number(dev.get(key)):
                raise ContractError(f"device.{key} is {dev.get(key)!r} in a "
                                    f"traced run")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise ContractError(
                f"device.busy_s {dev['busy_s']} is not above 0 and at most "
                f"window_s {dev['window_s']}")
        bd = obj.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key)
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _number(r[1]))
                        for r in rows):
                    raise ContractError(f"breakdown.{key} is not a list of "
                                        f"at most 10 [name, seconds]")
    line = json.dumps(obj)
    if "\n" in line or json.loads(line) != obj:
        raise ContractError("the object does not survive json.dumps")


def check_benchmark(bench: dict, root: str = ROOT) -> list:
    """What the harness itself needs of BENCHMARK.json: every name it looks
    up is a file.  Returns the missing paths (an empty list is sound)."""
    missing = []

    def need(*parts):
        path = os.path.join(*parts)
        if not os.path.exists(os.path.join(root, path)):
            missing.append(path)

    for cfg in bench["configs"]:
        need(cfg["file"])
    for w in bench["workloads"]:
        need("chipbench", "cells", w["name"] + ".json")
        need("chipbench", "traffic", w["traffic"] + ".json")
    for m in bench["per_layer"]:
        need("chipbench", "metrics", m["name"] + ".py")
    return missing
