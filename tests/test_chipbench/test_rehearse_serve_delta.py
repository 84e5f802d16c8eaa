"""The Olmo Hybrid serving cell end to end under `--rehearse` (its tiny twin
on the CPU: three delta-rule layers and a full one, 4 heads of an 8 x 64
state): the last line is the contract's and a traced one carries the five
delta readers, read from the cell's own recorded trace, while the readers
that are listed for other cells or not at all are logged; the update broken
underneath (half the correction in the decode round; the decay left out of
the chunked scan) turns `correct` false; the fp8 control fails the cell's
own limits; a program without the model fails at once."""

import re

import pytest

from chipbench import contract
from chipbench.runners.serve_delta import UNLISTED

from ._rehearse import BENCH, CELLS, last_line, run_cell

CELL = "serve-olmohybrid-longanswer-1chip"
MINE = ("delta_decode_roofline", "delta_update_share_pct",
        "delta_decode_step_device_ms", "delta_prefill_chunk_device_ms",
        "delta_chunk_us_per_position")
ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 41), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0 and obj["device"]["platform"] == "cpu"
    assert "correct: deficit_max" in err and "limit" in err
    # the sample holds a request with two chunk boundaries behind it
    sampled = re.search(r"sample of (\d+) from (\d+) finished requests "
                        r"longer than 32 tokens", err)
    assert sampled and int(sampled.group(2)) >= 1
    # one number all run long, and the one the shapes give
    held = re.search(r"delta_state_bytes over the run: \[(\d+)\] \(the "
                     r"shapes give (\d+):", err)
    assert held and held.group(1) == held.group(2) \
        == str(4 * 3 * 4 * 8 * 64 * 4)
    assert set(obj["metrics"]) >= {"setup_s", "token_gap_p95_ms"}
    logged = dict(re.findall(r"not reported: (\S+) = (\S+)$", err, re.M))
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        for name in ("kv_arena_use_pct", "device_idle_pct.chat",
                     "admit_wait_mean_ms", "ttft_p90_ms"):
            assert obj["metrics"][name]["value"] >= 0, name
        for name in MINE:     # none of them None: the recording is the
            assert obj["metrics"][name]["value"] > 0, name   # cell's own
        assert obj["metrics"]["delta_decode_roofline"]["value"] <= 100.0
        assert obj["metrics"]["delta_update_share_pct"]["value"] <= 100.0
        assert set(UNLISTED) <= set(logged)
        assert float(logged["state_pool_use_pct"]) > 0
        assert float(logged["serve_xla_compiles"]) == 2.0
        assert "decode_step_device_ms" not in obj["metrics"]
    else:
        assert not set(MINE) & set(obj["metrics"])
        assert not set(UNLISTED) & set(logged)


BREAK = """
from easydist_tpu.ops import delta_rule
from chipbench import run
{patch}
run.main()
"""
BROKEN = {
    # the decode round corrects by half of beta: every state drifts from
    # the first generated token on
    "half_the_correction_in_the_decode_round": """
sound = delta_rule.delta_decode_update
delta_rule.delta_decode_update = lambda state, q, k, v, g, beta, **kw: \\
    sound(state, q, k, v, g, 0.5 * beta, **kw)
""",
    # the chunked scan forgets nothing: what a prompt leaves in the state
    # is wrong before the first round
    "the_decay_left_out_of_the_chunked_scan": """
sound = delta_rule.delta_chunk_scan
delta_rule.delta_chunk_scan = lambda q, k, v, g, beta, state, **kw: \\
    sound(q, k, v, 0.0 * g, beta, state, **kw)
""",
}


@pytest.mark.parametrize("what", list(BROKEN))
def test_the_update_broken_underneath_is_not_correct(what):
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=BREAK.format(patch=BROKEN[what]))
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    assert obj["correct"] is False
    assert "OVER THE LIMIT" in err


def test_the_fp8_control_is_not_correct_by_the_cells_own_limits_and_bf16_is_read():
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse", "--control")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    sound, control = obj["check"]["numbers"], obj["check"]["control"]
    assert control["deficit_mean"] > 3 * sound["deficit_mean"]
    assert control["deficit_mean"] > 0
    assert obj["correct"] is True and control["correct"] is False
    assert re.search(r"control \(fp8 operands\) correct: deficit_mean = \S+"
                     r"  limit \S+  OVER THE LIMIT", err)
    # the second control — the recurrence's state, conv, decay and beta in
    # bfloat16 — is read against the same limits and reported beside it
    # (what it reads at the real size is in PERF.md section 4's table)
    state = control["bf16_recurrence"]
    assert set(state) == set(sound) | {"correct"}
    assert 0 <= state["deficit_mean"] < control["deficit_mean"]
    assert isinstance(state["correct"], bool)
    assert re.search(r"control \(bf16 recurrence\) correct: deficit_mean = ",
                     err)


def test_a_program_without_the_model_fails_at_once(tmp_path):
    """What the driver's check of the new cell on the parent commit sees:
    the benchmark's files laid over a program that lacks the model end in
    a nonzero exit before any weight is made."""
    import os
    import shutil

    ignore = shutil.ignore_patterns("__pycache__", "olmo_hybrid.py",
                                    "delta_rule.py")
    shutil.copy(contract.ROOT + "/BENCHMARK.json", tmp_path)
    shutil.copytree(contract.ROOT + "/chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(contract.ROOT + "/easydist_tpu",
                    tmp_path / "easydist_tpu", ignore=ignore)
    assert not os.path.exists(tmp_path / "easydist_tpu" / "models"
                              / "olmo_hybrid.py")
    assert os.path.exists(tmp_path / "chipbench" / "reference"
                          / "olmo_hybrid.py")
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            cwd=str(tmp_path))
    assert rc != 0 and out == ""
    assert "olmo_hybrid" in err and "weights on the device" not in err
