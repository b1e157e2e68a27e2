"""``harness/rank_trace.py``, checked on a table written by hand, whose
answers are plain arithmetic; on the recorded ranking part of a real
traced window of ``istella-rank-train`` (data/rank_slice_*.json.gz, cut
with tools/rank_slice.py from PR 37's chip run), where the scopes' times
are held against the operations' own self times; and on what a reader does
when handed no trace of this run or a program without the scopes."""

import gzip
import json
import os

import pytest

from harness import load_module, rank_trace, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
RUN = "jit(run)/while/body/closed_call/"


def hand_written():
    return {"spans": [["bench.window", 10 * MS, 100 * MS]], "ops": [
        [0, 0, 5 * MS, "%fusion.0", RUN + "gradients/rank_pairs/mul"],   # before
        [0, 10 * MS, 60 * MS, "%while.1", ""],
        [0, 10 * MS, 4 * MS, "%gather.1", RUN + "gradients/rank_gather/gather"],
        [0, 14 * MS, 12 * MS, "%sort.1", RUN + "gradients/rank_sort/sort"],
        # an operation nested in the sort's time with a path of its own
        [0, 16 * MS, 2 * MS, "%fusion.9", RUN + "gradients/rank_sort/compare"],
        [0, 26 * MS, 20 * MS, "%fusion.1", RUN + "gradients/rank_pairs/reduce"],
        [0, 46 * MS, 6 * MS, "%sort.2", RUN + "gradients/rank_sort/sort"],
        [0, 52 * MS, 3 * MS, "%gather.2", RUN + "gradients/rank_accumulate/gather"],
        [0, 55 * MS, 1 * MS, "%fusion.2", RUN + "gradients/mul"],
        [0, 56 * MS, 7 * MS, "%fusion.3", RUN + "round_hist/hist_kernel/k"],
        [0, 70 * MS, 9 * MS, "%sort.3",
         RUN + "valid_metric/jit(run)/ndcg_sort/sort"],
        [0, 79 * MS, 2 * MS, "%fusion.4", RUN + "valid_metric/jit(run)/div"],
        [0, 200 * MS, 5 * MS, "%fusion.5", RUN + "gradients/rank_pairs/mul"]]}


def test_time_goes_to_the_innermost_ranking_scope():
    got = rank_trace.reduce_table(hand_written())
    assert {k: round(v * 1e3, 6) for k, v in got["scope_s"].items()} == {
        "rank_gather": 4, "rank_sort": 18, "rank_pairs": 20,
        "rank_accumulate": 3, "gradients": 1, "ndcg_sort": 9, "valid_metric": 2}
    assert round(got["op_s"]["rank_sort:sort"] * 1e3, 6) == 16
    assert "round_hist" not in got["scope_s"]


def test_a_table_without_a_window_is_refused():
    table = hand_written()
    table["spans"] = []
    with pytest.raises(ValueError):
        rank_trace.reduce_table(table)


def slices():
    return sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.startswith("rank_slice_"))


@pytest.mark.parametrize("name", slices())
def test_a_recorded_slice_reads_what_its_operations_hold(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as fh:
        table = json.load(fh)
    got = rank_trace.reduce_table(table)["scope_s"]
    assert got == pytest.approx(table["expect"])
    assert set(rank_trace.RANK_SCOPES) <= set(got)
    # every operation of the slice sits under gradients or valid_metric:
    # the scopes' times add up to the operations' self times
    rows = tracered._self_times(table["ops"])
    assert sum(got.values()) == pytest.approx(sum(r[2] for r in rows) / 1e9)
    grads = sum(v for k, v in got.items()
                if k.startswith("rank_") or k == "gradients")
    under = sum(r[2] for r in rows if "gradients" in r[4].split("/")) / 1e9
    assert grads == pytest.approx(under)
    # one round of the cell: the two gathers are most of its gradients,
    # the sorts and the pair step next to nothing (PERF.md section 5)
    assert got["rank_accumulate"] > got["rank_gather"] > 5 * got["rank_sort"] > 0
    assert got["rank_sort"] > got["rank_pairs"] > 0


def test_the_readers_return_none_without_a_trace(monkeypatch):
    monkeypatch.setattr(rank_trace, "_THIS_RUN", [])
    monkeypatch.setattr(rank_trace.scoped, "find_trace", lambda: None)
    run = {"rounds": 8}
    for name in ("rank_grad_gather_ms", "rank_grad_sort_ms", "rank_grad_pairs_ms",
                 "rank_grad_accumulate_ms", "rank_ndcg_ms"):
        assert load_module("layers", name).read(run) is None
    assert load_module("layers", "rank_slot_fill_share").read(run) is None
    assert load_module("layers", "rank_slot_fill_share").read(
        {"rank_counts": {"rank_docs": 75, "rank_slot_rows": 100}}) == 75.0


def test_a_program_without_the_scopes_reads_none(monkeypatch, tmp_path):
    """The parent of PR 37 traces ``gradients`` and ``valid_metric`` but
    none of the ranking scopes: every reader finds nothing to read."""
    table = hand_written()
    for op in table["ops"]:
        for scope in rank_trace.RANK_SCOPES:
            op[4] = op[4].replace(scope + "/", "")
    monkeypatch.setattr(rank_trace, "_THIS_RUN", [])
    monkeypatch.setattr(rank_trace.scoped, "find_trace", lambda: "a.xplane.pb")
    monkeypatch.setattr(rank_trace.scoped, "table_of", lambda path: table)
    assert rank_trace.of_this_run() is None
    assert load_module("layers", "rank_ndcg_ms").read({"rounds": 8}) is None
