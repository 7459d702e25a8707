"""The witness's replay (``outer_step_witness.py``) on small files: the
published recurrence passes, and the faults it is there to tell from the
algorithm (PERF.md 7: the PS's momentum and sum live in buffers it keeps
from round to round since PR 26 and PR 30) do not."""

from __future__ import annotations

import numpy as np
import pytest
from safetensors.numpy import save_file

import outer_step_witness as witness

LR, MU = 0.7, 0.9


def deltas(rounds: int) -> list[dict]:
    rng = np.random.default_rng(7)
    # round 0 is the large one; after it the worker has nothing left to learn
    scales = [1.0, 1e-3, 1e-3, 1e-3][:rounds]
    return [{"embed": (s * rng.standard_normal((64, 8))).astype(np.float32),
             "head/kernel": (s * rng.standard_normal(96)).astype(np.float32)} for s in scales]


def write(tmp_path, fault: str | None, rounds: int = 4) -> None:
    """What a PS would write, by the recurrence in float64, or with a fault."""
    m = None
    ds = deltas(rounds)
    for r, d in enumerate(ds):
        g = {k: v.astype(np.float64) for k, v in d.items()}
        if fault == "sum_not_cleared" and r > 0:  # the round's sum still holds the last round's
            g = {k: g[k] + ds[r - 1][k] for k in g}
        if fault == "momentum_lost" or m is None:
            m = {k: np.zeros_like(v) for k, v in g.items()}
        m = {k: MU * m[k] + g[k] for k in g}
        sign = -1.0 if fault == "wrong_sign" and r == 2 else 1.0
        u = {k: (sign * LR * (MU * m[k] + g[k])).astype(np.float32) for k in g}
        save_file(d, str(tmp_path / f"delta-{r}.safetensors"))
        save_file(u, str(tmp_path / f"update-{r}.safetensors"))


def test_the_published_outer_step_replays_to_rounding(tmp_path):
    write(tmp_path, None)
    rows = witness.replay(tmp_path, 4)
    assert [x["round"] for x in rows] == [0, 1, 2, 3] and all(x["leaves"] == 2 for x in rows)
    assert max(x["worst_gap"] for x in rows) < 1e-6
    # momentum alone: with nothing to learn the update is lr * mu^(r+1) of round 0's delta
    first = rows[0]["delta_norm"]
    assert rows[0]["update_norm"] / first == pytest.approx(LR * (1 + MU), rel=1e-5)  # 1.33
    assert rows[1]["update_norm"] / first == pytest.approx(LR * MU * MU, rel=1e-2)  # 0.567
    assert rows[2]["update_norm"] / first == pytest.approx(LR * MU ** 3, rel=1e-2)  # 0.510


@pytest.mark.parametrize("fault,first_bad_round", [
    ("sum_not_cleared", 1), ("momentum_lost", 1), ("wrong_sign", 2)])
def test_a_fault_in_the_ps_state_is_told_from_the_algorithm(tmp_path, fault, first_bad_round):
    write(tmp_path, fault)
    rows = witness.replay(tmp_path, 4)
    assert all(x["worst_gap"] < 1e-6 for x in rows[:first_bad_round])
    assert rows[first_bad_round]["worst_gap"] > 0.1 and rows[first_bad_round]["worst_leaf"]


def test_only_whole_pairs_of_files_are_replayed(tmp_path):
    write(tmp_path, None, rounds=3)
    (tmp_path / "update-2.safetensors").unlink()
    assert [x["round"] for x in witness.replay(tmp_path, 3)] == [0, 1]
    assert witness.replay(tmp_path / "nothing-here", 3) == []
