"""The training cell end to end under `--rehearse` (tiny sizes, four
virtual CPU devices on a (2, 2) mesh): the last line is the contract's and
nothing follows it; a step that returns its state unchanged turns `correct`
false."""

import pytest

from chipbench import contract

from ._rehearse import BENCH, CELLS, last_line, run_cell

CELL = "train-gpt2xl-4chip"
ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 23), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["device"]["count"] == 4
    assert obj["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    for name in ("loss_gap_step1", "grad_norm_gap", "update_norm_gap"):
        assert f"correct: {name}" in err
    if trace:
        dev = obj["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        for name in ("collective_share_pct", "flash_train_roofline",
                     "device_idle_pct.train", "compile_plan_s"):
            assert name in obj["metrics"]


STATE_UNCHANGED = """
import jax, jax.numpy as jnp
from chipbench import run
from chipbench.runners import train
def unchanged(compiled, state, tokens, targets):
    copy = jax.tree.map(jnp.copy, state)         # the step donates its input
    _, loss = compiled(copy, tokens, targets)
    return state, loss
train._call_step = unchanged
run.main()
"""


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=STATE_UNCHANGED)
    assert rc == 0, err[-3000:]
    assert last_line(out)["correct"] is False
    assert "update_norm_gap = 1 " in err and "OVER THE LIMIT" in err
