"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the TPU analog of the reference's mock-device-mesh trick
(easydist/utils/testing/mock.py:16-50): one process, N-device semantics, no
hardware.  Must configure jax BEFORE any backend initialization: the device
count flag is read once, when the CPU client is created.
"""

import os

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache: the suite is compile-dominated on small
# hosts (one ~14-min tier-1 run is mostly XLA:CPU compiles of the same
# tiny-model programs every run), and the executables are keyed by HLO
# hash + jax version + flags, so reuse across runs is exact.  First run
# pays a small serialization overhead; every run after starts warm.
# EASYDIST_TEST_NO_COMPILE_CACHE=1 disables (e.g. to time cold compiles).
if os.environ.get("EASYDIST_TEST_NO_COMPILE_CACHE") != "1":
    from easydist_tpu.utils.jax_cache import configure_jax_cache  # noqa: E402

    # the suite's compile load is thousands of TINY programs (the
    # solver's per-equation discovery probes compile in ~30ms each),
    # all below the default 1s write threshold — cache everything
    configure_jax_cache(min_compile_secs=0.0)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {len(devices)}"
    return devices


@pytest.fixture(autouse=True)
def _hermetic_perfdb(tmp_path, monkeypatch):
    """Tests must never read or write the user's persistent PerfDB — a
    calibration or op-time table from a previous run would silently change
    solver decisions under test."""
    from easydist_tpu import config as edconfig

    monkeypatch.setattr(edconfig, "prof_db_path",
                        str(tmp_path / "perf.db"))


# ONE test, and no list of them: `test_rehearse_serve_hybrid.py` says of
# `BENCHMARK.json` that the Granite cell is the LAST workload and the last
# name in five metrics' lists.  A later cell has to be appended (the driver
# reads an entry put anywhere else as a change to what was there), so those
# two assertions cannot hold beside ANY later cell, and the file is the
# benchmark's (`tests/test_chipbench` is under BENCHMARK.json's `paths`),
# which only a `benchmark` PR may edit: PERF.md section 7 asks it to turn
# `[-1] == CELL` into membership and to delete this.  Until then that test
# is expected to fail on an AssertionError — strictly, so that the repaired
# test takes this out — and only while the Granite cell is not the last;
# every other assertion it makes (the nine readers unlisted, what they
# move, that they read nothing from an empty run, the five lists) is made
# of both cells in `test_rehearse_serve_window.py::test_the_cell_is_in_
# five_lists_and_its_own_readers_are_unlisted`.
_GRANITE_IS_THE_LAST_CELL = (    # the node id's end, whatever the rootdir
    "test_chipbench/test_rehearse_serve_hybrid.py::"
    "test_the_cell_joins_five_lists_and_its_own_readers_are_unlisted")


# The second such pin, and the last: `test_program_readers.py` says that
# PR 24's five entries are the LAST of `per_layer`, and the driver reads an
# entry put anywhere but the end as a change to what was there, so that
# assertion cannot hold beside ANY later entry.  PR 36 appended seven (the
# session's timeline between programs, set-up's compile seconds); the same
# route is open to the readers that ship unlisted.  A `benchmark` PR turns
# the pin into membership and deletes this (PERF.md section 7); what else
# that test says of the five (each lists one cell, its reader's; each is
# better lower) is held by name meanwhile, in `test_chipbench/
# test_session_timeline.py::test_the_five_entries_before_them_are_as_they_were`.
_FIVE_ENTRIES_ARE_THE_LAST = (
    "test_chipbench/test_program_readers.py::"
    "test_the_five_entries_are_the_last_of_benchmark_json")
_THE_FIVE = ["decode_step_device_ms", "prefill_chunk_device_ms",
             "session_host_ms_per_step", "step_xla_compiles",
             "step_dispatch_ms"]


# The third, of the same kind: `test_axk1.py` says that the A.X-K1 cell is
# the LAST workload, the last name in five metrics' lists, and that PR 39's
# five `latent_*` entries are the last of `per_layer`.  PR 41 appended a
# cell, its name to those lists and five entries (the only place the driver
# lets them go), so those assertions cannot hold beside ANY later cell; the
# file is the benchmark's.  What else that test says (each of the five
# lists that cell alone and states what its reader states; the twins'
# namesakes stay the Mistral cell's; PR 36's seven keep their three cells)
# is held of both cells in `test_chipbench/test_olmo_hybrid.py::
# test_the_five_are_listed_for_this_cell_alone_and_nothing_before_them_
# moved` — by membership and relative order, never by position from the
# end, so that the next appended cell needs no fourth pin.  A `benchmark`
# PR turns `test_axk1.py`'s positions into membership and deletes this
# (PERF.md section 7 (a)).
_AXK1_IS_THE_LAST_CELL = (
    "test_chipbench/test_axk1.py::"
    "test_the_five_are_the_last_entries_and_list_this_cell_alone")
_THE_LATENT_FIVE = ["latent_decode_roofline", "latent_chunk_roofline",
                    "latent_attn_share_pct", "latent_decode_step_device_ms",
                    "latent_prefill_chunk_device_ms"]


def pytest_collection_modifyitems(config, items):
    import json

    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    last = bench["workloads"][-1]["name"]
    tail = [m["name"] for m in bench["per_layer"][-5:]]
    pins = {}
    if last != "serve-granite4hs-chat-1chip":
        pins[_GRANITE_IS_THE_LAST_CELL] = (
            f"asserts BENCHMARK.json's workloads[-1] is the Granite cell; "
            f"{last} was appended after it")
    if tail != _THE_FIVE:
        pins[_FIVE_ENTRIES_ARE_THE_LAST] = (
            f"asserts BENCHMARK.json's per_layer[-5:] are PR 24's five "
            f"entries; entries were appended after them, the last five are "
            f"now {', '.join(tail)}")
    if last != "serve-axk1-longdoc-1chip" or tail != _THE_LATENT_FIVE:
        pins[_AXK1_IS_THE_LAST_CELL] = (
            f"asserts BENCHMARK.json's workloads[-1] is the A.X-K1 cell and "
            f"per_layer[-5:] its five entries; {last} and its entries were "
            f"appended after them")
    for item in items:
        for node, reason in pins.items():
            if item.nodeid.endswith(node):
                item.add_marker(pytest.mark.xfail(
                    raises=AssertionError, strict=True, reason=reason))


_EXIT_STATUS = [None]


def pytest_sessionfinish(session, exitstatus):
    _EXIT_STATUS[0] = int(exitstatus)


def pytest_unconfigure(config):
    """Skip interpreter finalization once the summary has printed.

    A full run leaves thousands of compiled XLA executables and device
    buffers behind; tearing them down in atexit takes ~20s on a 1-core
    host — dead time between pytest's summary line and the process
    actually exiting, which a CI wall-clock timeout still bills for.
    unconfigure runs after every sessionfinish hook (the terminal
    reporter prints its summary in one), so nothing left matters to any
    consumer of this suite: flush and exit hard.
    EASYDIST_TEST_FULL_EXIT=1 restores the normal interpreter shutdown
    (e.g. to profile atexit hooks themselves).
    """
    if os.environ.get("EASYDIST_TEST_FULL_EXIT") == "1":
        return
    if _EXIT_STATUS[0] is None:  # collection-only / early abort paths
        return
    import sys

    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_EXIT_STATUS[0])
