"""The worker's ``operators:`` line of a phi4flash job states the width of the
value its attention kernel is handed (``value_dim``, twice ``head_dim``: one
call a layer over the pair's whole value, PR 48), next to ``head_dim``.

``tests/perfbench/test_rehearsal_phi4flash.py`` holds the line as it read
before, in a file a PR that changes the program may not edit
(``tests/conftest.py`` expects that one failure); here both assertions of that
test again, over one more run of the same CPU rehearsal of the harness, with
the line as it reads now."""

from __future__ import annotations

import re
import sys
from pathlib import Path

# The harness's tests' helpers, and the fixture of the test held again.
sys.path.append(str(Path(__file__).resolve().parent / "perfbench"))

from test_rehearsal_phi4flash import ran  # noqa: E402,F401  (the fixture: one more traced tiny run)


def test_the_worker_says_which_operators_it_holds_the_values_width_and_the_scans_chunk(ran):  # noqa: F811
    from hypha_tpu.ops.selective_scan import CHUNK

    _, r, w0, _, _ = ran
    assert r.returncode == 3, r.stderr[-3000:]  # a rehearsal: it ran, and the device is no TPU
    assert re.search(
        r"operators: window_attention=1 mamba=1 full_attention=1 gmu=1 cross_attention=1 "
        rf"head_dim=8 value_dim=16 scan_chunk={CHUNK}$", w0, re.M)
    assert "routing:" not in w0  # a dense model: the chunked step has no counters


def test_the_rehearsal_is_correct_but_for_its_device(ran):  # noqa: F811
    """One call a layer changes no number the harness compares: the reference
    still decides the first loss, and only the two checks a CPU cannot pass fail."""
    from perfbench_helpers import failing_checks, notes

    _, r, _, _, _ = ran
    assert failing_checks(r.stdout) == {"attention_is_compiled_flash", "device_is_tpu"}
    checks = notes(r.stdout)["checks"]
    assert checks["reference_ran"] is True and checks["first_loss_as_reference"] is True
