"""The reduction from trace to numbers: on a trace made by hand, and on the
one-chip and four-chip traces recorded on the v5e."""

import glob
import os

import pytest

from chipbench import contract, trace_reduce

RECORDED = os.path.join(contract.ROOT, "chipbench", "recorded")


def plane(name, events, line="XLA Ops"):
    return {"name": name, "lines": [{"name": line, "events": events}]}


HAND = {"planes": [
    # chip 0: 0-4 and 6-9 s busy (the 1-2 event is nested in 0-4)
    plane("/device:TPU:0", [["fusion.1", 0, 4_000_000_000],
                            ["all-gather.3", 1_000_000_000, 1_000_000_000],
                            ["flash_fwd", 6_000_000_000, 3_000_000_000]]),
    # chip 1: 0-2 s busy
    plane("/device:TPU:1", [["fusion.1", 0, 2_000_000_000]]),
    {"name": "/device:TPU:0 SparseCore", "lines": []},
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["chipbench.session_step", 0, 10_000_000_000],
        ["chipbench.snapshot_inflight", 4_500_000_000, 1_000_000_000]]}]},
]}


def test_busy_is_the_mean_over_chips_of_each_chips_union():
    b = trace_reduce.busy(HAND, 2)
    assert b["per_chip_s"] == [7.0, 2.0]
    assert b["busy_s"] == 4.5          # never the sum, 9.0
    assert trace_reduce.busy(HAND, 1)["busy_s"] == 7.0


def test_a_missing_plane_or_an_empty_op_line_is_an_error_not_a_zero():
    with pytest.raises(ValueError, match="2 device planes"):
        trace_reduce.busy(HAND, 4)
    empty = {"planes": [plane("/device:TPU:0", [["x", 0, 5]], "Steps")]}
    with pytest.raises(ValueError, match="no events"):
        trace_reduce.busy(empty, 1)


def test_ops_are_found_by_name_and_nested_time_counts_once():
    assert trace_reduce.op_seconds(HAND, "flash") == [3.0, 0.0]
    assert trace_reduce.op_seconds(HAND, trace_reduce.COLLECTIVE) \
        == [1.0, 0.0]
    assert trace_reduce.op_count(HAND, "fusion") == [1, 1]
    assert sorted(trace_reduce.self_times(
        [["while", 0, 10], ["a", 1, 3], ["b", 2, 1], ["c", 6, 2]])) == [
        ("a", 2), ("b", 1), ("c", 2), ("while", 5)]
    bd = trace_reduce.breakdown(HAND)
    ops = dict(bd["device_ops"])
    # the all-gather nested in the fusion takes its second out of it
    assert ops == {"fusion": 5.0 / 2, "all-gather": 0.5, "flash_fwd": 1.5}
    # the 4-6 s gap falls under the snapshot span, the innermost over it
    assert bd["idle_gaps"] == [["chipbench.snapshot_inflight", 2.0]]


def test_union_merges_overlaps():
    total, merged = trace_reduce.union_ns([(0, 5), (3, 4), (20, 1)])
    assert total == 8 and merged == [[0, 7], [20, 21]]


@pytest.mark.parametrize("name,chips,kernel", [
    ("serve-1chip.json.gz", 1, "paged_decode_roofline"),
    ("train-4chip.json.gz", 4, "flash_train_roofline"),
])
def test_the_recorded_traces_reduce(name, chips, kernel):
    import importlib.util

    path = os.path.join(RECORDED, name)
    assert os.path.exists(path), sorted(glob.glob(RECORDED + "/*"))
    trace = trace_reduce.load_recorded(path)
    planes = trace_reduce.device_planes(trace)
    assert [p["name"] for p in planes] \
        == [f"/device:TPU:{i}" for i in range(chips)]
    b = trace_reduce.busy(trace, chips)
    spans = [max(s + d for _, s, d in trace_reduce.op_events(p))
             - min(s for _, s, d in trace_reduce.op_events(p))
             for p in planes]
    assert all(0 < busy <= span / 1e9 for busy, span
               in zip(b["per_chip_s"], spans))
    assert min(b["per_chip_s"]) <= b["busy_s"] <= max(b["per_chip_s"])
    # the kernel this kind of cell's roofline metric reads is there by name
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(contract.ROOT, "chipbench", "metrics",
                               kernel + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert all(s > 0 for s in trace_reduce.op_seconds(trace, mod.KERNEL))
    if chips == 4:
        assert all(s > 0 for s in trace_reduce.op_seconds(
            trace, trace_reduce.COLLECTIVE))
    bd = trace_reduce.breakdown(trace)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
