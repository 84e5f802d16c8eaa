"""The K-EXAONE serving cell end to end under `--rehearse` (its tiny twin
on the CPU: layers 0-4 [sliding x3, full, sliding] with a window of 8, 8
experts top-2 of which 4 are held): the last line is the contract's, and a
traced run logs the cell's seven unlisted readers (PERF.md section 7) read
from the cell's own recorded trace; a window mask broken underneath turns
`correct` false, and so does a token altered at single positions; the
fp8 control fails the cell's own limits; a program
without the model fails at once."""

import re

import pytest

from chipbench import contract
from chipbench.runners.serve_hybrid import UNLISTED as GRANITES
from chipbench.runners.serve_window import UNLISTED as NEW

from ._rehearse import BENCH, CELLS, last_line, run_cell

CELL = "serve-kexaone-mixedlen-1chip"
GRANITE = "serve-granite4hs-chat-1chip"
ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 33), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0 and obj["device"]["platform"] == "cpu"
    assert "correct: deficit_max" in err and "limit" in err
    # the sample holds requests that left the window (8) and a chunk (16)
    sampled = re.search(r"sample of (\d+) from (\d+) finished requests "
                        r"longer than 24 tokens", err)
    assert sampled and int(sampled.group(2)) >= 1
    assert "window_ring_bytes over the run: [" in err
    assert set(obj["metrics"]) >= {"setup_s", "token_gap_p95_ms"}
    logged = {name: float(value) for name, value in re.findall(
        r"not reported: (\S+) = ([0-9.e+-]+)$", err, re.M)}
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        for name in ("kv_arena_use_pct", "device_idle_pct.chat",
                     "admit_wait_mean_ms", "ttft_p90_ms"):
            assert obj["metrics"][name]["value"] >= 0, name
        for name in NEW:     # none of them None: the recording is the
            assert logged[name] > 0, name    # cell's own
        assert logged["expert_ffn_share_pct"] <= 100.0
        assert logged["expert_ffn_roofline"] <= 100.0
        assert logged["full_attn_decode_roofline"] <= 100.0
        assert "decode_step_device_ms" not in obj["metrics"]
    else:
        assert not set(NEW) & set(logged)
    assert not set(NEW) & set(obj["metrics"])


UNLISTED = {GRANITE: GRANITES, CELL: NEW}


@pytest.mark.parametrize("cell", [GRANITE, CELL])
def test_the_cell_is_in_five_lists_and_its_own_readers_are_unlisted(cell):
    """Of each of the two cells whose readers ship without an entry, all
    that `test_rehearse_serve_hybrid.py` says of the Granite cell — none of
    its own readers (Granite's nine, this cell's seven) is in `per_layer`,
    each moves the gap and reads nothing from an empty run, the cell takes
    one chip and is in the five lists — by MEMBERSHIP: that test says it
    with `[-1] == CELL`, which holds of no cell once another is appended
    (`tests/conftest.py`), and this one names no place, so that the next
    cell breaks nothing."""
    import importlib

    names = [m["name"] for m in BENCH["per_layer"]]
    assert not set(UNLISTED[cell]) & set(names)
    for name in UNLISTED[cell]:
        reader = importlib.import_module("chipbench.metrics." + name)
        assert reader.META["moves"] == "token_gap_p95_ms"
        assert reader.read({"chips": 1}) is None
    assert CELLS[cell]["chips"] == 1
    for name in ("token_gap_p95_ms", "admit_wait_mean_ms", "ttft_p90_ms",
                 "kv_arena_use_pct", "device_idle_pct.chat"):
        entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                     if m["name"] == name)
        assert cell in entry["workloads"]


BREAK_THE_WINDOW = """
import easydist_tpu.ops as ops
from chipbench import run
sound = ops.window_attention      # what `Ring.attend` looks up, each call
def one_key_more(q, k, v, q_pos, k_pos, window, scale=None):
    return sound(q, k, v, q_pos, k_pos, window + 1, scale)
ops.window_attention = one_key_more
run.main()
"""


def test_a_window_one_key_too_wide_is_not_correct():
    """The mask broken underneath — every sliding layer sees one position
    further back than the model says — in chunk steps and decode steps
    alike: the sampled requests are longer than the window, so the served
    tokens fall away from the reference's."""
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=BREAK_THE_WINDOW)
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    assert obj["correct"] is False
    assert "OVER THE LIMIT" in err


BREAK_A_TOKEN = """
from easydist_tpu.serve import GenerationSession
from chipbench import run
EVERY, VOCAB = {every}, {vocab}
decode_round = GenerationSession._decode_round
rounds = [0]
def altered(self, pool, only=None):
    decode_round(self, pool, only)
    rounds[0] += 1
    if rounds[0] % EVERY == 0:     # the newest token of every live slot
        for slot in pool.slots.values():
            if slot.generated:
                slot.generated[-1] = slot.token = (slot.token + 1) % VOCAB
GenerationSession._decode_round = altered
run.main()
"""


def test_a_token_altered_in_one_round_of_three_is_not_correct():
    """A plainly wrong token at single positions of a stream that is sound
    everywhere else (the stream goes on from the altered token, as the
    reference does): what `deficit_max` is kept for.  The same script with
    `every=40, vocab=19200` is what reads the limit's upper side on the
    chip (PERF.md section 4)."""
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=BREAK_A_TOKEN.format(every=3, vocab=256))
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    assert obj["correct"] is False
    assert re.search(r"correct: deficit_max = \S+  limit \S+  OVER THE LIMIT",
                     err)


def test_the_fp8_control_is_not_correct_by_the_cells_own_limits():
    """`--control` passes the control's numbers through the comparison that
    decides `correct`, against the cell's limits: it fails one at least,
    and the run's own verdict stays the program's."""
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse", "--control")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    sound, control = obj["check"]["numbers"], obj["check"]["control"]
    assert control["deficit_mean"] > 3 * sound["deficit_mean"]
    assert control["deficit_mean"] > 0
    assert obj["correct"] is True and control["correct"] is False
    assert re.search(r"control \(fp8 operands\) correct: deficit_mean = \S+"
                     r"  limit \S+  OVER THE LIMIT", err)


def test_a_program_without_the_model_fails_at_once(tmp_path):
    """What the driver's check of the new cell on the parent commit sees:
    the benchmark's files laid over a program that lacks the model end in
    a nonzero exit before any weight is made."""
    import os
    import shutil

    ignore = shutil.ignore_patterns("__pycache__", "exaone_moe.py")
    shutil.copy(contract.ROOT + "/BENCHMARK.json", tmp_path)
    shutil.copytree(contract.ROOT + "/chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(contract.ROOT + "/easydist_tpu",
                    tmp_path / "easydist_tpu", ignore=ignore)
    assert not os.path.exists(
        tmp_path / "easydist_tpu" / "models" / "exaone_moe.py")
    assert os.path.exists(tmp_path / "chipbench" / "reference"
                          / "exaone_moe.py")
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            cwd=str(tmp_path))
    assert rc != 0 and out == ""
    assert "exaone_moe" in err and "weights on the device" not in err
