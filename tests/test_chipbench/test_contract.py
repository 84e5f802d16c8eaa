"""BENCHMARK.json against the harness's own needs, and `check_last_line`
against the faults PR 22's traced four-chip run may have had."""

import copy
import json
import os
import re

import pytest

from chipbench import contract

BENCH = contract.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def good_line(cell: str, trace: bool) -> dict:
    units = contract.cell_metrics(BENCH, cell, trace)
    dev = {"platform": "tpu", "kind": "TPU v5 lite",
           "count": CELLS[cell]["chips"], "memory_peak_bytes": 9_000_000_000}
    obj = {"correct": True, "attempted": 40, "failed": 0,
           "metrics": {n: {"value": 12.5, "unit": u}
                       for n, u in units.items()},
           "device": dev}
    if trace:
        dev.update(busy_s=2.5, window_s=4.0)
        obj["breakdown"] = {"device_ops": [["fusion.1", 1.5]],
                            "idle_gaps": [["chipbench.session_step", 0.5]]}
    return obj


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_line_is_accepted(cell, trace):
    contract.check_last_line(good_line(cell, trace), CELLS[cell], trace,
                             BENCH)


def _first_per_layer(obj):
    per_layer = {m["name"] for m in BENCH["per_layer"]}
    return next(n for n in obj["metrics"] if n in per_layer)


FAULTS = {
    # 1: busy summed over four device planes
    "busy_above_window": lambda o: o["device"].update(busy_s=9.6),
    # 2: the reduction found no TPU plane or no op line
    "busy_zero": lambda o: o["device"].update(busy_s=0.0),
    "busy_missing": lambda o: o["device"].pop("busy_s"),
    # 3: a per-layer metric came out None / NaN / was left out
    "metric_none": lambda o: o["metrics"][_first_per_layer(o)].update(
        value=None),
    "metric_nan": lambda o: o["metrics"][_first_per_layer(o)].update(
        value=float("nan")),
    "metric_left_out": lambda o: o["metrics"].pop(_first_per_layer(o)),
    "metric_wrong_unit": lambda o: o["metrics"]["setup_s"].update(unit="ms"),
    "metric_not_in_benchmark": lambda o: o["metrics"].update(
        made_up={"value": 1.0, "unit": "s"}),
    # 5: the run died: whatever was printed lacks the keys
    "key_missing": lambda o: o.pop("device"),
    "count_is_not_the_cells": lambda o: o["device"].update(count=8),
    "correct_not_boolean": lambda o: o.update(correct="yes"),
    "failed_above_attempted": lambda o: o.update(failed=41),
    "breakdown_too_long": lambda o: o["breakdown"].update(
        device_ops=[["op", 0.1]] * 11),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_is_refused(fault):
    cell = "train-gpt2xl-4chip"
    obj = good_line(cell, True)
    FAULTS[fault](obj)
    with pytest.raises(contract.ContractError):
        contract.check_last_line(obj, CELLS[cell], True, BENCH)


def test_a_roofline_share_over_105_is_refused():
    cell = "serve-mistral7b-chat-1chip"
    obj = good_line(cell, True)
    obj["metrics"]["paged_decode_roofline"]["value"] = 131.0
    with pytest.raises(contract.ContractError, match="peak"):
        contract.check_last_line(obj, CELLS[cell], True, BENCH)


def test_the_untraced_line_carries_the_end_to_end_metrics_only():
    cell = "serve-mistral7b-chat-1chip"
    assert set(contract.cell_metrics(BENCH, cell, False)) == {
        "setup_s", "token_gap_p95_ms"}
    traced = set(contract.cell_metrics(BENCH, cell, True))
    assert {"paged_decode_roofline", "device_idle_pct.chat",
            "ttft_p90_ms"} <= traced
    assert "collective_share_pct" not in traced


# ------------------------------------------------------- BENCHMARK.json


def test_every_name_the_benchmark_looks_up_is_a_file():
    assert contract.check_benchmark(BENCH) == []
    broken = copy.deepcopy(BENCH)
    broken["workloads"][0]["traffic"] = "no-such-mix"
    assert contract.check_benchmark(broken) == [
        os.path.join("chipbench", "traffic", "no-such-mix.json")]


def test_benchmark_json_keeps_to_the_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for k in ("end_to_end", "per_layer")
             for m in BENCH[k]]
    assert len(names) == len(set(names))
    for m in list(e2e.values()) + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        with open(os.path.join(contract.ROOT, c["file"])) as f:
            sizes = json.load(f)
        assert set(c["reduced"]) == set(sizes["reduced"])
        assert sizes["assumed"] and sizes["source"] == c["source"]
        for key in c["reduced"]:   # a width is never cut
            assert not re.search(r"hidden_size|intermediate|_dim$|_rank$|"
                                 r"head_dim|n_embd", key)


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        cells = m.get("workloads") or [
            w["name"] for w in BENCH["workloads"]
            if "workloads" not in moved or w["name"] in moved["workloads"]]
        for cell in cells:
            assert cell in CELLS
            assert "workloads" not in moved or cell in moved["workloads"], (
                m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.add(m["layer"])
    for cell in CELLS:   # setup_s, another end-to-end one, a per-layer one
        assert len(contract.cell_metrics(BENCH, cell, False)) >= 2
        assert len(contract.cell_metrics(BENCH, cell, True)) \
            > len(contract.cell_metrics(BENCH, cell, False))
    assert layers == {"compile", "emitted program", "kernels", "session",
                      "kv", "device"}


def test_every_reader_states_what_benchmark_json_states():
    import importlib.util

    for m in BENCH["per_layer"]:
        path = os.path.join(contract.ROOT, "chipbench", "metrics",
                            m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert {k: m[k] for k in ("layer", "unit", "moves", "source")} \
            == mod.META, m["name"]
        assert mod.read({"chips": 1}) is None   # nothing to read: nothing
