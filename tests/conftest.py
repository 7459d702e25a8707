"""Test harness: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's testing philosophy (SURVEY.md §4): no real cluster in
CI — multi-chip behavior is exercised on host-platform virtual devices, the
distributed control plane on paused/injected clocks, and protocol logic on an
in-process fake transport.

Tests run on the CPU: JAX_PLATFORMS=cpu is set before jax is imported,
unless HYPHA_ALLOW_TPU=1 targets the on-chip kernel tests.
"""

import os
import sys
import traceback

import pytest

# Escape hatch for the ON-HARDWARE kernel tests (tests/test_tpu_hw.py):
# `HYPHA_ALLOW_TPU=1 pytest tests/test_tpu_hw.py` leaves the default backend
# alone so the pallas kernels are validated on the chip. The hatch only opens
# when the hardware tests are the TARGETED paths — a leftover exported var
# must not send the whole suite onto the chip.
_ALLOW_TPU = os.environ.get("HYPHA_ALLOW_TPU") == "1" and any(
    "test_tpu_hw" in a for a in sys.argv
)

if not _ALLOW_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Entry points turn the persistent compile cache on (hw.enable_compile_cache)
# and several run inside the test process; one test's compile must not reach
# another through the disk, so the cache itself stays off here and in the
# subprocesses tests start.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-node end-to-end tests (tens of seconds)"
    )
    config.addinivalue_line(
        "markers",
        "fault: chaos/fault-injection tests (hypha_tpu.ft) — filter with "
        "-m fault / -m 'not fault'",
    )
    if not hasattr(config, "workerinput"):
        # Every xdist worker imports tests/test_native.py, whose skipif asks
        # for the library while it is collected, and ``native._load`` compiles
        # straight into the library's path: in a checkout without
        # ``native/build/`` the workers raced, one loaded a half-written file
        # and skipped the file's 72 tests as "no native toolchain". Built here
        # once, in the process that starts the workers, before it starts them.
        from hypha_tpu import native

        native.native_available()


# Two tests under ``tests/perfbench/`` each hold one literal count of the
# manifest's entries that stops being true when a cell is added, and a PR that
# adds a cell may not edit a file the benchmark already has (PR 40; PERF.md 7
# asks the next ``benchmark`` PR to make both counts relative and to take this
# hook out). Only a failure *at that one line* is expected: any other assertion
# of either test fails as it always did. What stands after the line in each test
# does not run while the line fails, so ``tests/perfbench/test_relative_counts.py``
# holds every assertion of both again, over the same fixtures, with the counts
# relative to the manifest. A repaired test passes and the hook does nothing.
#
# Since PR 43 a third line of a file the benchmark has is outdated the same way,
# by the program and not by the manifest: ``test_journey_metrics.py`` counts the
# worker's ``push received:`` lines that read ``pages=fresh path=loop``, which
# every round's did until the worker's node began to save a broadcast over the
# file the last one left (``pages=recycled path=thread`` from round 1 on).
# ``tests/test_receive_recycled.py`` holds every assertion of that test again,
# over one more run of the same rehearsal, with the line as it reads now.
#
# Since PR 44 a fourth: ``test_journey_metrics.py`` holds that PR 42's twelve are
# the manifest's *last* entries and that it has 62, which the fifth cell's nine
# entries, appended as the contract asks, outdate.
# ``tests/perfbench/test_phi4flash_counts.py`` holds every assertion of that test
# again with the twelve found where they stand.
#
# Since PR 46 a fifth: ``test_rehearsal_phi4flash.py`` holds that Phi-4's nine
# are the manifest's *last* entries (and its cell and configuration the last of
# their lists), which the sixth cell's ten entries, appended, outdate.
# ``tests/perfbench/test_rehearsal_nemotron_h.py`` holds every assertion of that
# test again, by name and not by place.
#
# Since PR 48 a sixth, outdated by the program as the third was:
# ``test_rehearsal_phi4flash.py`` holds the worker's ``operators:`` line to end
# ``head_dim=8 scan_chunk=128``, and the line now states the width of the value
# the attention kernel is handed between the two (``value_dim=16``).
# ``tests/test_phi4flash_operators_line.py`` holds both assertions of that test
# again, over one more run of the same rehearsal, with the line as it reads now.
_COUNTS_A_NEW_CELL_OUTDATES = {
    "tests/perfbench/test_rehearsal.py::test_a_traced_run_reports_the_per_layer_metrics_the_cpu_can_give":
        ('len(manifest["per_layer"]) - 7',
         "Trinity's seven were the only entries with a workloads list"),
    "tests/perfbench/test_fourth_cell.py::test_nothing_that_was_there_is_touched_and_the_manifest_gains_entries_only":
        ('len(m["workloads"]) == 4 and len(m["configs"]) == 3',
         "the manifest had 3 workloads and 2 configurations before the toy cell"),
    "tests/perfbench/test_journey_metrics.py::test_the_rehearsals_roles_wrote_the_spans_from_both_ends":
        ("pages=fresh path=loop",
         "only round 0's broadcast lands in fresh pages through the loop since PR 43"),
    "tests/perfbench/test_journey_metrics.py::test_the_twelve_are_the_last_entries_and_list_one_dense_and_one_sparse_cell":
        ("len(per_layer) == 62",
         "the twelve were the last of 62 entries until a fifth cell appended its own"),
    "tests/perfbench/test_rehearsal_phi4flash.py::test_the_manifest_lists_the_nine_metrics_for_the_one_cell":
        ('m["per_layer"][-len(NEW):]',
         "Phi-4's nine were the last entries until a sixth cell appended its own"),
    "tests/perfbench/test_rehearsal_phi4flash.py::test_the_worker_says_which_operators_it_holds_and_the_scans_chunk":
        ("assert re.search(",
         "the line ended `head_dim=8 scan_chunk=128` until it stated the value's width between the two (PR 48)"),
}


@pytest.hookimpl(wrapper=True)
def pytest_pyfunc_call(pyfuncitem):
    try:
        return (yield)
    except AssertionError as e:
        stale = _COUNTS_A_NEW_CELL_OUTDATES.get(pyfuncitem.nodeid)
        at = traceback.extract_tb(e.__traceback__)[-1]
        if stale and at.name == pyfuncitem.name and stale[0] in (at.line or ""):
            pytest.xfail(f"holds the literal `{stale[0]}`: {stale[1]}")
        raise


@pytest.fixture(params=["native", "numpy"])
def kernel_backend(request, monkeypatch):
    """Both implementations of the flat f32 kernels: the C++ library, and
    the numpy fallback a host without a toolchain runs."""
    from hypha_tpu import native

    if request.param == "numpy":
        monkeypatch.setattr(native, "_load", lambda: None)
    assert native.native_available() == (request.param == "native")
    return request.param
