"""The A.X-K1 serving cell end to end under `--rehearse` (its tiny twin on
the CPU: three layers, 4 heads over one cached row of 16 + 8 values, 8
experts top-2 of which 4 are held): the last line is the contract's and a
traced one carries the five latent readers, read from the cell's own
recorded trace, while the readers that are listed for other cells or not at
all are logged; the attention's scale broken underneath (m^2 left out) turns
`correct` false, and so does the shared rotary key left out of the cached
row; the fp8 control fails the cell's own limits; a program without the
model fails at once."""

import re

import pytest

from chipbench import contract
from chipbench.runners.serve_latent import UNLISTED

from ._rehearse import BENCH, CELLS, last_line, run_cell

CELL = "serve-axk1-longdoc-1chip"
FIVE = ("latent_decode_roofline", "latent_chunk_roofline",
        "latent_attn_share_pct", "latent_decode_step_device_ms",
        "latent_prefill_chunk_device_ms")
ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 39), "--seconds", "2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_last_line_is_the_contracts(trace):
    rc, out, err = run_cell(*ARGS, "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[CELL], bool(trace), BENCH)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0 and obj["device"]["platform"] == "cpu"
    assert "correct: deficit_max" in err and "limit" in err
    # the sample holds a request past the original context (16) and a chunk
    sampled = re.search(r"sample of (\d+) from (\d+) finished requests "
                        r"longer than 32 tokens", err)
    assert sampled and int(sampled.group(2)) >= 1
    assert re.search(r"latent_cache_bytes over the run: \[\d+\] ", err)
    assert set(obj["metrics"]) >= {"setup_s", "token_gap_p95_ms"}
    logged = dict(re.findall(r"not reported: (\S+) = (\S+)$", err, re.M))
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        for name in ("kv_arena_use_pct", "device_idle_pct.chat",
                     "admit_wait_mean_ms", "ttft_p90_ms"):
            assert obj["metrics"][name]["value"] >= 0, name
        for name in FIVE:     # none of them None: the recording is the
            assert obj["metrics"][name]["value"] > 0, name   # cell's own
        assert obj["metrics"]["latent_decode_roofline"]["value"] <= 100.0
        assert obj["metrics"]["latent_chunk_roofline"]["value"] <= 100.0
        assert obj["metrics"]["latent_attn_share_pct"]["value"] <= 100.0
        assert set(UNLISTED) <= set(logged)
        for name in ("expert_ffn_share_pct", "expert_ffn_roofline",
                     "expert_load_max_over_mean", "serve_xla_compiles"):
            assert float(logged[name]) > 0, name
        assert float(logged["serve_xla_compiles"]) == 2.0
        assert "decode_step_device_ms" not in obj["metrics"]
    else:
        assert not set(FIVE) & set(obj["metrics"])
        assert not set(UNLISTED) & set(logged)


BREAK = """
from easydist_tpu.models import axk1
from chipbench import run
{patch}
run.main()
"""
BROKEN = {
    # (nope + rope)^-0.5 alone: what `decoder(cfg)` folds into the queries
    "m_squared_left_out_of_the_scale": """
axk1.attention_scale = lambda cfg: (cfg.nope_dim + cfg.rope_dim) ** -0.5
""",
    # the rotary key zeroed on its way into the cached row: q_r . k_r is
    # not added to any score
    "the_rotary_key_not_added_to_the_score": """
sound = axk1._rope
axk1._rope = lambda x, pos, inv_freq, factor: sound(
    x, pos, inv_freq, factor) * (0.0 if x.ndim == pos.ndim + 1 else 1.0)
""",
}


@pytest.mark.parametrize("what", list(BROKEN))
def test_the_path_broken_underneath_is_not_correct(what):
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            code=BREAK.format(patch=BROKEN[what]))
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    assert obj["correct"] is False
    assert "OVER THE LIMIT" in err


def test_the_fp8_control_is_not_correct_by_the_cells_own_limits():
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

    ignore = shutil.ignore_patterns("__pycache__", "axk1.py")
    shutil.copy(contract.ROOT + "/BENCHMARK.json", tmp_path)
    shutil.copytree(contract.ROOT + "/chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(contract.ROOT + "/easydist_tpu",
                    tmp_path / "easydist_tpu", ignore=ignore)
    assert not os.path.exists(tmp_path / "easydist_tpu" / "models"
                              / "axk1.py")
    assert os.path.exists(tmp_path / "chipbench" / "reference" / "axk1.py")
    rc, out, err = run_cell(*ARGS, "--trace", "0", "--rehearse",
                            cwd=str(tmp_path))
    assert rc != 0 and out == ""
    assert "axk1" in err and "weights on the device" not in err
