"""The control of ``correct``: the plain reference put in the program's
place in bfloat16 has to come out as not correct, while the program's own
answers come out as correct: at rehearsal size on the CPU (the chip
readings at the cells' own size are in PERF.md)."""

import pytest

import control


@pytest.mark.parametrize("cell", ["criteo-train", "criteo63-train"])
def test_bfloat16_control_is_not_correct(cell):
    rows, limits, _ = control.readings(cell, bf16=True, rehearse_cpu=True)
    (row,) = rows
    assert row["sound_correct"], row["sound"]
    assert not row["control_correct"], row["control"]
    # it fails by the numbers that hold the precision, not by a count or
    # by the splits, which it keeps
    assert row["control"]["leaf_value_gap_median"] > 3 * limits["leaf_value_gap_median"]
    assert row["control"]["train_score_gap"] > 3 * limits["train_score_gap"]
    assert row["control"]["leaf_count_mismatch"] == 0
    assert row["control"]["split_regret_mean"] <= limits["split_regret_mean"]
