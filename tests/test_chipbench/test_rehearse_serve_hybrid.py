"""The Granite 4.0-H serving cell end to end under `--rehearse` (its tiny
twin on the CPU: one period [mamba, attention, mamba], 8 experts top-2 of
which 4 are held): the last line is the contract's, and a traced run logs
the cell's nine unlisted readers (PERF.md section 7) read from the cell's
own recorded trace; a token altered where it is produced turns `correct`
false; the fp8 control reads worse than the program."""

import re

import pytest

from chipbench import contract
from chipbench.runners.serve_hybrid import UNLISTED as NEW

from ._rehearse import BENCH, CELLS, last_line, run_cell

CELL = "serve-granite4hs-chat-1chip"
ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 29), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0 and obj["device"]["platform"] == "cpu"
    assert "correct: deficit_max" in err and "limit" in err
    assert set(obj["metrics"]) >= {"setup_s", "token_gap_p95_ms"}
    logged = {name: float(value) for name, value in re.findall(
        r"not reported: (\S+) = ([0-9.e+-]+)$", err, re.M)}
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        for name in ("kv_arena_use_pct", "device_idle_pct.chat",
                     "admit_wait_mean_ms", "ttft_p90_ms"):
            assert obj["metrics"][name]["value"] >= 0, name
        for name in NEW:
            assert logged[name] >= 0, name
        # the recording is the cell's own: the kernels and both programs
        # are found in it
        for name in ("expert_ffn_share_pct", "ssm_update_share_pct",
                     "hybrid_decode_step_device_ms",
                     "hybrid_prefill_chunk_device_ms"):
            assert logged[name] > 0, name
        assert logged["expert_ffn_share_pct"] \
            + logged["ssm_update_share_pct"] <= 100.0
        assert logged["expert_ffn_roofline"] <= 100.0
        assert logged["ssm_decode_roofline"] <= 100.0
        # the other cells' program readers are not this cell's
        assert "decode_step_device_ms" not in obj["metrics"]
    else:
        assert not set(NEW) & set(logged)
    assert not set(NEW) & set(obj["metrics"])


def test_the_cell_joins_five_lists_and_its_own_readers_are_unlisted():
    """An entry put before the last five reads as a change to what was
    there, and `test_program_readers.py` keeps those five last: the nine
    readers ship without an entry, as the README's two do."""
    import importlib

    names = [m["name"] for m in BENCH["per_layer"]]
    assert not set(NEW) & set(names)
    for name in NEW:
        reader = importlib.import_module("chipbench.metrics." + name)
        assert reader.META["moves"] == "token_gap_p95_ms"
        assert reader.read({"chips": 1}) is None
    assert CELLS[CELL]["chips"] == 1 and BENCH["workloads"][-1]["name"] == CELL
    for name in ("token_gap_p95_ms", "admit_wait_mean_ms", "ttft_p90_ms",
                 "kv_arena_use_pct", "device_idle_pct.chat"):
        entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"][-1] == CELL


BREAK_A_TOKEN = """
import sys
from easydist_tpu.serve import GenerationSession
from chipbench import run
decode_round = GenerationSession._decode_round
def altered(self, pool, only=None):
    decode_round(self, pool, only)
    for slot in pool.slots.values():       # every live slot's newest token
        slot.generated[-1] = slot.token = (slot.token + 1) % 256
GenerationSession._decode_round = altered
run.main()
"""


def test_a_token_altered_where_it_is_produced_is_not_correct():
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=BREAK_A_TOKEN)
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    assert obj["correct"] is False
    assert "OVER THE LIMIT" in err


def test_the_fp8_control_reads_worse_than_the_program():
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse", "--control")
    assert rc == 0, err[-3000:]
    check = last_line(out)["check"]
    sound, control = check["numbers"], check["control"]
    assert control["deficit_mean"] > 3 * sound["deficit_mean"]
    assert control["deficit_mean"] > 0


def test_a_program_without_the_model_fails_at_once(tmp_path):
    """What the driver's check of the new cell on the parent commit sees:
    the benchmark's files laid over a program that lacks the model end in
    a nonzero exit before any weight is made."""
    import os
    import shutil

    ignore = shutil.ignore_patterns("__pycache__", "granite_hybrid.py")
    shutil.copy(contract.ROOT + "/BENCHMARK.json", tmp_path)
    shutil.copytree(contract.ROOT + "/chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(contract.ROOT + "/easydist_tpu",
                    tmp_path / "easydist_tpu", ignore=ignore)
    assert not os.path.exists(
        tmp_path / "easydist_tpu" / "models" / "granite_hybrid.py")
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            cwd=str(tmp_path))
    assert rc != 0 and out == ""
    assert "granite_hybrid" in err and "weights on the device" not in err
