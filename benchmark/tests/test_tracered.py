"""The trace reduction, checked twice: on a table written by hand, whose
answers are plain arithmetic, and on a small recorded slice of a real
device trace (data/trace_slice.json.gz), against a second, slower way of
getting the same numbers."""

import gzip
import json
import os

import numpy as np
import pytest

from harness import tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def test_hand_written_table():
    ms = 1_000_000
    path = "jit(run)/while/body/jit(grow_tree_batched)/"
    table = {
        "spans": [["bench.window", 0, 100 * ms], ["bench.job", 5 * ms, 90 * ms]],
        "modules": [[0, 10 * ms, 40 * ms, "jit_run(1)"], [0, 60 * ms, 30 * ms, "jit_run(1)"],
                    [0, 55 * ms, 1 * ms, "jit_iota(2)"]],
        "ops": [
            [0, 10 * ms, 40 * ms, "%while.1", "jit(run)/while"],            # holds the next three
            [0, 10 * ms, 20 * ms, "%hist_kernel.3", path + "round_hist/k"],
            [0, 30 * ms, 10 * ms, "%fusion.7", path + "partition/sort"],
            [0, 40 * ms, 5 * ms, "%fusion.8", path + "find_splits/argmax"],
            [0, 55 * ms, 1 * ms, "%iota.1", "jit(iota)/iota"],
            [0, 60 * ms, 30 * ms, "%hist_kernel.3", path + "round_hist/k"],
        ]}
    r = tracered.reduce_table(table)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.071)                  # 40 + 1 + 30 ms
    assert r["scope_s"] == pytest.approx(
        {"round_hist": 0.050, "partition": 0.010, "find_splits": 0.005})
    # the while's own 5 ms and the iota are outside every scope
    assert r["busy_s"] - sum(r["scope_s"].values()) == pytest.approx(0.006)
    assert r["between_dispatch_s"] == pytest.approx([0.010])   # 50 -> 60 ms
    assert r["gap_s"] == pytest.approx({
        "job:booster_and_first_dispatch": 0.010,         # 0-10 ms: its middle is in the job
        "job:trees_to_host_and_next_dispatch": 0.009,    # 50-55 and 56-60 ms
        "outside_a_job": 0.010})                         # 90-100 ms: the job ended at 95
    top = tracered.breakdown(r)
    assert top["device_ops"][0] == ["round_hist:hist_kernel", pytest.approx(0.050)]


def test_table_without_a_window_is_refused():
    with pytest.raises(ValueError):
        tracered.reduce_table({"ops": [[0, 0, 1, "%a", ""]], "modules": [], "spans": []})


def test_recorded_slice_against_a_sampled_clock():
    with gzip.open(os.path.join(HERE, "data", "trace_slice.json.gz"), "rt") as fh:
        table = json.load(fh)
    r = tracered.reduce_table(table)
    (_, w0, dur), = [s for s in table["spans"] if s[0] == "bench.window"]
    # the slow way: mark every microsecond in which some operation runs
    busy = np.zeros(dur // 1000 + 1, bool)
    for _, start, d, _, _ in table["ops"]:
        a, b = max(start, w0) - w0, min(start + d, w0 + dur) - w0
        if b > a:
            busy[a // 1000:-(-b // 1000)] = True
    assert r["busy_s"] == pytest.approx(busy.sum() / 1e6, rel=2e-3)
    # read by hand off the trace: one boundary, 9.8 ms between the programs
    assert r["between_dispatch_s"] == pytest.approx([0.009800171])
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.0098 / 0.3, rel=0.02)
    assert set(r["scope_s"]) == {"round_hist", "partition", "find_splits"}
    assert sum(r["scope_s"].values()) < r["busy_s"]
