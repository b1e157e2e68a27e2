"""``harness/cat_trace.py``, checked on a table written by hand, whose
answers are plain arithmetic (a scope opened under the children's ``vmap``
reads ``vmap(cat_subset)`` on an operation's path; the root's search
opens the scopes under ``tree_root``; the packing sits under
``partition`` > ``find_splits``), and on what a reader does when handed
no trace of this run or a program without the scopes."""

import pytest

from harness import cat_trace, load_module

MS = 1_000_000
RUN = "jit(run)/while/body/closed_call/"
BODY = RUN + "tree_select/while/body/"


def hand_written():
    return {"spans": [["bench.window", 10 * MS, 100 * MS]], "ops": [
        [0, 0, 5 * MS, "%sort.0", BODY + "find_splits/vmap(cat_subset)/sort"],  # before
        [0, 10 * MS, 70 * MS, "%while.1", ""],
        [0, 10 * MS, 6 * MS, "%sort.1", RUN + "tree_root/cat_subset/sort"],
        [0, 16 * MS, 1 * MS, "%fusion.1", RUN + "tree_root/cat_bitset/reduce_or"],
        [0, 17 * MS, 12 * MS, "%sort.2", BODY + "find_splits/vmap(cat_subset)/sort"],
        # an operation nested in the sort's time with a path of its own
        [0, 19 * MS, 2 * MS, "%fusion.9", BODY + "find_splits/vmap(cat_subset)/compare"],
        [0, 29 * MS, 3 * MS, "%fusion.2", BODY + "find_splits/vmap(cat_bitset)/reduce_or"],
        [0, 32 * MS, 4 * MS, "%fusion.3", BODY + "find_splits/vmap(argmax)/reduce"],
        [0, 36 * MS, 2 * MS, "%fusion.4",
         BODY + "partition/find_splits/cat_bitset/shift_left"],
        [0, 38 * MS, 9 * MS, "%custom-call.1", BODY + "partition/pallas_call"],
        [0, 50 * MS, 7 * MS, "%fusion.5", BODY + "round_hist/hist_kernel/k"],
        [0, 200 * MS, 5 * MS, "%sort.3", BODY + "find_splits/vmap(cat_subset)/sort"]],
        "program": [
            ["lgbtpu.dispatch_done", 5 * MS, 1, {"rounds": 8, "trees": 8,
             "splits": 2032, "cat_splits": 1000, "cat_subset_splits": 900,
             "cat_left_levels": 9000}],                                    # before
            ["lgbtpu.dispatch_done", 60 * MS, 1, {"rounds": 8, "trees": 8,
             "splits": 2032, "cat_splits": 1016, "cat_subset_splits": 800,
             "cat_left_levels": 12000}],
            ["lgbtpu.dispatch_done", 90 * MS, 1, {"rounds": 8, "trees": 8,
             "splits": 2032, "cat_splits": 1016, "cat_subset_splits": 800,
             "cat_left_levels": 8000}],
            ["lgbtpu.train", 10 * MS, 95 * MS, {}]]}


def test_time_goes_to_the_innermost_categorical_scope():
    got = cat_trace.reduce_table(hand_written())
    assert {k: round(v * 1e3, 6) for k, v in got["scope_s"].items()} == {
        "cat_subset": 6 + 12, "cat_bitset": 1 + 3 + 2, "find_splits": 4}
    assert round(got["op_s"]["cat_subset:sort"] * 1e3, 6) == 16
    assert got["counts"] == {"splits": 4064, "cat_splits": 2032,
                             "cat_subset_splits": 1600,
                             "cat_left_levels": 20000}


def test_the_readers_divide_the_window_s_counts(monkeypatch):
    monkeypatch.setattr(cat_trace, "_THIS_RUN",
                        [cat_trace.reduce_table(hand_written())])
    run = {"rounds": 16}
    assert load_module("layers", "cat_split_share").read(run) == 50.0
    assert load_module("layers", "cat_left_levels_mean").read(run) == 12.5
    assert load_module("layers", "cat_subset_search_ms").read(run) == \
        pytest.approx(18 / 16)
    assert load_module("layers", "cat_bitset_ms").read(run) == \
        pytest.approx(6 / 16)
    other = load_module("layers", "cat_other_row_share").read
    assert other({"cat_counts": {"cat_features": 17, "cat_other_rows": 340,
                                 "rows": 1000}}) == pytest.approx(2.0)
    assert other({}) is None


def test_a_table_without_a_window_is_refused():
    table = hand_written()
    table["spans"] = []
    with pytest.raises(ValueError):
        cat_trace.reduce_table(table)


def test_without_a_trace_or_the_scopes_every_reader_returns_nothing(monkeypatch):
    run = {"rounds": 16}
    monkeypatch.setattr(cat_trace, "_THIS_RUN", [None])
    for name in ("cat_subset_search_ms", "cat_bitset_ms", "cat_split_share",
                 "cat_left_levels_mean"):
        assert load_module("layers", name).read(run) is None
    assert load_module("layers", "cat_bin_mappers_s").read(run) is None
    # a program without the scopes: a reduction that found none of them
    table = hand_written()
    table["ops"] = [o for o in table["ops"] if "cat_" not in o[4]]
    got = cat_trace.reduce_table(table)
    assert not any(s in got["scope_s"] for s in cat_trace.CAT_SCOPES)
