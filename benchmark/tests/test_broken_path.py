"""A run whose timed path is broken underneath has to report
``correct: false``.  The harness's look for a chip is skipped (rehearsal
size, CPU backend); everything after it is the run's own code, and the
fault is planted in the program (tools/faults.py) before the run
starts."""

import argparse

import pytest

import faults
import run as bench


def _strongest(cell: str) -> int:
    from harness import load_module
    _, _, cfg, _ = bench.find_cell(cell, rehearse_cpu=True)
    gen = load_module("datagen", cfg["data"]["generator"])
    return gen.strongest_feature(cfg["data"], int(cfg["features"]))


@pytest.mark.parametrize("cell,breakage,fails", [
    ("criteo-train", lambda s, f: faults.drop_score_update(s), "train_score_gap"),
    ("criteo-train", lambda s, f: faults.alter_leaf_values(s), "leaf_value_gap_median"),
    # the histogram kernels and the split search: nothing but the search
    # of the stated splits sees these
    ("criteo-train", lambda s, f: faults.hist_zero_feature(s, f), "split_regret_mean"),
    ("criteo-train", lambda s, f: faults.hist_drop_upper_bins(s), "split_regret_mean"),
    ("criteo63-train", lambda s, f: faults.hist_scale_feature(s, f), "split_regret_mean"),
])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, breakage, fails):
    import lightgbm_tpu  # noqa: F401  (what the faults are planted in)
    breakage(monkeypatch.setattr, _strongest(cell))
    args = argparse.Namespace(workload=cell, seed=2147483659, seconds=0.0,
                              trace=0, rehearse_cpu=True)
    result = bench.run_cell(args)
    assert result["correct"] is False, result["compared"]
    gap = result["compared"][fails]
    assert gap["value"] > gap["limit"]
