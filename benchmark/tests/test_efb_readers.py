"""The ``efb_*`` readers of the cell ``allstate-train`` (layers/efb_*.py:
eight thin over ``harness/scoped.py``, twelve that hand the run to the
accepted reader of the same layer): on test_scoped's
hand-written table, whose answers are plain arithmetic; on a recorded
slice of a real trace of the cell (data/scoped_slice_allstate-train.json.gz,
cut with tools/trace_slice.py from PR 34's traced chip run); and against
a run or a program that has nothing for them to read."""

import gzip
import json
import os

import pytest

from harness import load_module, scoped
from test_scoped import hand_written

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE = ("efb_find_splits_ms", "efb_hist_ms", "efb_partition_ms",
          "efb_valid_score_ms", "efb_unscoped_device_ms",
          "efb_device_idle_share")
#: the cell's name for an accepted reader: the same number from the same run
SAME_AS = {"efb_" + n: n for n in (
    "hist_compact_ms", "hist_kernel_ms", "hist_fill_share", "tree_root_ms",
    "score_update_ms", "gradients_ms", "quantize_ms", "between_dispatch_ms",
    "job_start_ms", "compile_s", "lower_s", "compile_or_load_s")}


def read(name, run):
    return load_module("layers", name).read(run)


def test_readers_of_a_hand_written_reduction(monkeypatch):
    r = scoped.reduce_table(hand_written(), rows_full=1000)
    monkeypatch.setattr(scoped, "_THIS_RUN", [r])
    run = {"rounds": 1, "phases": {"construct_s": 58.5},
           "setup_spans_s": {"bundle_plan": 8.25, "construct": 50.0}}
    assert read("efb_find_splits_ms", run) == pytest.approx(5.0)
    assert read("efb_partition_ms", run) == pytest.approx(10.0)
    assert read("efb_valid_score_ms", run) == pytest.approx(5.0)
    # everything under round_hist: compaction 8 + 4, kernels 16 + 25,
    # updates 5 + 2
    assert read("efb_hist_ms", run) == pytest.approx(60.0)
    assert read("efb_unscoped_device_ms", run) == pytest.approx(3.0)
    assert read("efb_device_idle_share", run) == pytest.approx(
        100.0 * (1.0 - r["busy_s"] / r["window_s"]))
    assert read("efb_construct_s", run) == 58.5
    assert read("efb_bundle_plan_s", run) == 8.25


def _runs(reduction):
    """A traced run over ``reduction`` and an untraced one, as
    ``train_jobs.measure`` hands them to the readers."""
    yield {"rounds": 8, "dispatch_s": [6.0, 6.2],
           "phases": {"warmup_first_dispatch_s": 90.0},
           "trace": {"between_dispatch_s": [0.017, 0.018]}}, reduction
    yield {"rounds": 8, "dispatch_s": [], "trace": None,
           "phases": {"warmup_first_dispatch_s": 90.0}}, None


@pytest.mark.parametrize("name", sorted(SAME_AS))
def test_a_renamed_reader_reads_what_the_accepted_one_reads(monkeypatch, name):
    with gzip.open(os.path.join(HERE, "data",
                                "scoped_slice_allstate-train.json.gz"), "rt") as fh:
        sliced = scoped.reduce_table(json.load(fh), 13184290)
    for reduction in (scoped.reduce_table(hand_written(), rows_full=1000), sliced):
        for run, red in _runs(reduction):
            monkeypatch.setattr(scoped, "_THIS_RUN", [red])
            assert read(name, run) == read(SAME_AS[name], run), name
    # the slice is a cut of device operations: it holds no program span,
    # so no job's start and no count of selected rows
    monkeypatch.setattr(scoped, "_THIS_RUN", [sliced])
    assert read(name, next(_runs(sliced))[0]) is not None or name in (
        "efb_job_start_ms", "efb_hist_fill_share", "efb_gradients_ms")


def test_nothing_to_read_reads_as_none(monkeypatch):
    """No trace of this run; and the parent's program, which has no
    ``bundle_plan`` span for the driver to hand over."""
    monkeypatch.setattr(scoped, "_THIS_RUN", [None])
    run = {"rounds": 8, "trace": None, "phases": {}}
    for name in DEVICE + ("efb_construct_s", "efb_bundle_plan_s"):
        assert read(name, run) is None, name
    assert read("efb_bundle_plan_s", dict(run, setup_spans_s={})) is None


def test_recorded_slice_of_the_cell():
    path = os.path.join(HERE, "data", "scoped_slice_allstate-train.json.gz")
    with gzip.open(path, "rt") as fh:
        table = json.load(fh)
    r = scoped.reduce_table(table, 13184290)
    expect = table["expect"]
    assert r["passes"] == expect["passes"]
    assert r["partition_passes"] == expect["partition_passes"] > 0
    assert sum(r["passes"].values()) \
        == r["partition_passes"] + expect["root_passes"]
    # the split search's time is under find_splits, the partition kernel's
    # under partition, and next to nothing of the slice is unnamed
    assert r["scope_s"]["find_splits"] > 0 and r["scope_s"]["partition"] > 0
    unnamed = r["busy_s"] - sum(r["scope_s"].values())
    assert 0 <= unnamed < 0.15 * r["busy_s"]
    kinds = {o[3].split(".")[0].lstrip("%") for o in table["ops"]
             if "partition" in o[4].split("/")}
    assert scoped.PARTITION_KERNEL in kinds
    assert not any("gather" in k for k in kinds)
