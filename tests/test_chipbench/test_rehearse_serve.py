"""The serving cell end to end under `--rehearse` (tiny sizes, CPU): the
last line is the contract's and nothing follows it; a token altered where
it is produced turns `correct` false; the fp8 control reads worse than the
program."""

import pytest

from chipbench import contract

from ._rehearse import BENCH, CELLS, last_line, run_cell

CELL = "serve-mistral7b-chat-1chip"
ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 17), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0 and obj["device"]["platform"] == "cpu"
    assert "correct: deficit_max" in err and "limit" in err
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        assert obj["metrics"]["paged_decode_roofline"]["value"] > 0


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


def test_without_a_tpu_there_is_no_result():
    rc, out, err = run_cell(*ARGS, "--trace", "0")
    assert rc != 0 and out == ""
    assert "needs a TPU" in err


def test_an_unknown_workload_is_an_error_that_names_it():
    rc, out, err = run_cell("--workload", "no-such-cell", "--seed", "1",
                            "--seconds", "1", "--rehearse")
    assert rc != 0 and out == "" and "no-such-cell" in err


def test_bench_run_in_the_environment_changes_nothing():
    from ._rehearse import ENV

    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            env={**ENV, "BENCH_RUN": "parent-3"})
    assert rc == 0, err[-3000:]
    assert last_line(out)["correct"] is True


def test_a_directory_with_only_the_benchmark_has_no_result(tmp_path):
    """BENCHMARK.json and the files under `paths` alone cannot run: the
    system under test is not there."""
    import shutil

    shutil.copy(contract.ROOT + "/BENCHMARK.json", tmp_path)
    shutil.copytree(contract.ROOT + "/chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            cwd=str(tmp_path))
    assert rc != 0 and out == ""
    assert "easydist_tpu" in err
