"""Run `python -m chipbench.run --rehearse ...` in a child on the CPU."""

import json
import os
import subprocess
import sys

from chipbench import contract

ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
ENV.pop("BENCH_RUN", None)


def run_cell(*args, code=None, env=None, cwd=contract.ROOT, timeout=900):
    """-> (returncode, stdout, stderr).  `code` replaces `-m chipbench.run`
    with a `-c` script (the tests that break the timed path)."""
    cmd = [sys.executable] + (["-c", code] if code
                              else ["-m", "chipbench.run"]) + list(args)
    p = subprocess.run(cmd, cwd=cwd, env=env or ENV, timeout=timeout,
                       capture_output=True, text=True)
    return p.returncode, p.stdout, p.stderr


def last_line(stdout: str) -> dict:
    """The one line of stdout, parsed; nothing before or after it."""
    assert stdout.endswith("\n") and stdout.count("\n") == 1, stdout[-2000:]
    return json.loads(stdout)


BENCH = contract.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
