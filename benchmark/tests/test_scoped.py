"""``harness/scoped.py``, checked three ways: on a table written by hand,
whose answers are plain arithmetic; on a recorded sub-second slice of a
real trace of each cell (data/scoped_slice_*.json.gz, cut with
tools/trace_slice.py from PR 28's chip runs), where the count of
histogram passes is held against the executions of the partition kernel;
and on what a reader does when handed no trace of this run."""

import gzip
import json
import os
import time

import pytest

from harness import load_module, scoped

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
RUN = "jit(run)/while/body/closed_call/"
TREE = RUN + "jit(grow_tree_batched)/"
BODY = TREE + "tree_select/while/body/"
LADDER = BODY + "round_hist/cond/"


def hand_written():
    ops = [
        [0, 40 * MS, 120 * MS, "%while.1", ""],
        [0, 40 * MS, 5 * MS, "%fusion.1", RUN + "gradients/mul"],
        [0, 45 * MS, 5 * MS, "%fusion.2", RUN + "quantize/jit(f)/round"],
        [0, 50 * MS, 5 * MS, "%transpose.1", TREE + "tree_root/transpose"],
        [0, 55 * MS, 10 * MS, "%root_kernel.1",
         TREE + "tree_root/hist_rows_full/hist_kernel/jit(k)/pallas_call:"],
        # first round: the 2048-row branch of the ladder
        [0, 65 * MS, 5 * MS, "%partition_select_pallas.1", BODY + "partition/k"],
        [0, 70 * MS, 30 * MS, "%conditional.1", ""],
        [0, 70 * MS, 8 * MS, "%sort.1",
         LADDER + "branch_1_fun/hist_rows_2048/hist_compact/sort"],
        [0, 78 * MS, 20 * MS, "%payload_kernel.1",
         LADDER + "branch_1_fun/hist_rows_2048/hist_kernel/jit(k)/pallas_call:"],
        # an operation nested in the kernel's time whose own path sits in
        # the other scope: time follows the operation's own path
        [0, 80 * MS, 4 * MS, "%fusion.3",
         LADDER + "branch_1_fun/hist_rows_2048/hist_kernel/jit(k)/hist_compact/pad"],
        [0, 100 * MS, 5 * MS, "%fusion.4", BODY + "round_hist/hist_update/sub"],
        [0, 105 * MS, 5 * MS, "%fusion.5", BODY + "find_splits/argmax"],
        # second round: the full pass
        [0, 110 * MS, 5 * MS, "%partition_select_pallas.1", BODY + "partition/k"],
        [0, 115 * MS, 25 * MS, "%conditional.1", ""],
        [0, 115 * MS, 25 * MS, "%full_kernel.1",
         LADDER + "branch_0_fun/hist_rows_full/hist_kernel/jit(k)/pallas_call:"],
        [0, 140 * MS, 2 * MS, "%fusion.4", BODY + "round_hist/hist_update/sub"],
        [0, 142 * MS, 3 * MS, "%fusion.6", BODY + "top_k"],
        [0, 145 * MS, 5 * MS, "%fusion.7", RUN + "leaf_renew/scatter-add"],
        [0, 150 * MS, 4 * MS, "%take.1", RUN + "score_update/jit(take)/k"],
        [0, 154 * MS, 3 * MS, "%fusion.8", RUN + "valid_score/dot"],
        [0, 157 * MS, 2 * MS, "%sort.2", "jit(run)/while/body/valid_metric/sort"],
    ]
    program = [
        ["lgbtpu.train", 5 * MS, 170 * MS, {}],
        ["lgbtpu.booster_init", 5 * MS, 20 * MS, {}],
        ["lgbtpu.train_fused", 26 * MS, 146 * MS, {}],
        ["lgbtpu.fused_prepare", 26 * MS, 4 * MS, {}],
        ["lgbtpu.fused_round_scan", 30 * MS, 2 * MS, {}],
        ["lgbtpu.fused_chunk_transfer", 32 * MS, 130 * MS, {}],
        ["lgbtpu.tree_finalize", 163 * MS, 1 * MS, {}],
        ["lgbtpu.dispatch_done", 170 * MS, 1000,
         {"rounds": 1, "trees": 1, "hist_rows_selected": 1400}],
    ]
    return {"spans": [["bench.window", 0, 200 * MS], ["bench.job", 4 * MS, 172 * MS]],
            "modules": [[0, 40 * MS, 120 * MS, "jit_run(1)"],
                        [0, 165 * MS, 1000, "jit_iota(2)"]],
            "ops": ops, "program": program}


def test_hand_written_table():
    r = scoped.reduce_table(hand_written(), rows_full=1000)
    assert r["window_s"] == pytest.approx(0.200)
    assert r["busy_s"] == pytest.approx(0.120)
    assert r["scope_s"] == pytest.approx({
        "gradients": 0.005, "quantize": 0.005, "tree_root": 0.005,
        "hist_kernel": 0.051,            # root 10 + payload 20 - 4 + full 25
        "hist_compact": 0.012,           # the sort 8 + the nested pad 4
        "hist_update": 0.007, "partition": 0.010, "find_splits": 0.005,
        "tree_select": 0.003, "leaf_renew": 0.005, "score_update": 0.004,
        "valid_score": 0.003, "valid_metric": 0.002})
    # the root pass's kernel is not under round_hist
    assert r["round_hist_s"] == pytest.approx(
        {"hist_kernel": 0.041, "hist_compact": 0.012, "hist_update": 0.007})
    # the while's own 1 ms and the first conditional's own 2 ms
    assert r["unnamed_s"] == pytest.approx({"while": 0.001, "conditional": 0.002})
    assert r["busy_s"] - sum(r["scope_s"].values()) == pytest.approx(0.003)
    # passes: the root pass and the second round's are full passes
    assert r["passes"] == {"hist_rows_full": 2, "hist_rows_2048": 1}
    assert r["partition_passes"] == 2 and r["trees"] == 1 and r["rounds"] == 1
    assert sum(r["passes"].values()) == r["partition_passes"] + r["trees"]
    assert r["rows_selected"] == 1400
    assert r["rows_handed"] == 2 * 1000 + 2048
    # the job starts at 5 ms, its round program first runs at 40 ms
    assert r["job_start_s"] == pytest.approx([0.035])
    assert r["job_start_by_span_s"] == pytest.approx(
        {"lgbtpu.booster_init": 0.020, "lgbtpu.train_fused": 0.014})
    # idle 0-40 ms: its middle is in booster_init; 160-200 ms: past the job
    assert r["gap_s"] == pytest.approx(
        {"lgbtpu.booster_init": 0.040, "outside_the_program": 0.040})
    assert r["gaps_over_1ms"] == [(40.0, "outside_the_program"),
                                  (40.0, "lgbtpu.booster_init")]
    assert r["program_spans"]["lgbtpu.dispatch_done"][0] == 1
    json.dumps(scoped.summary(r))


def test_full_rows_unknown_gives_no_rows_handed():
    assert scoped.reduce_table(hand_written(), None)["rows_handed"] is None


def test_a_program_without_the_names_reads_as_nothing():
    """The parent of PR 28: PR 27's three scopes, no program span."""
    table = hand_written()
    table["program"] = []
    keep = ("partition", "round_hist", "find_splits")
    for op in table["ops"]:
        parts = [p for p in op[4].split("/")
                 if p not in scoped.NEW_SCOPES and not p.startswith("hist_rows_")]
        op[4] = "/".join(parts)
    r = scoped.reduce_table(table, rows_full=1000)
    assert set(r["scope_s"]) == set(keep)
    assert r["passes"] == {} and r["rows_selected"] is None
    assert r["rows_handed"] is None and r["job_start_s"] == []
    assert set(r["gap_s"]) == {"outside_the_program"}


def test_table_without_a_window_is_refused():
    with pytest.raises(ValueError):
        scoped.reduce_table({"ops": [[0, 0, 1, "%a", ""]], "modules": [],
                             "spans": [], "program": []})


# ----------------------------------------------------- recorded real slices
@pytest.mark.parametrize("cell, rows_full", [("criteo-train", 13281250),
                                             ("criteo63-train", 13281250)])
def test_recorded_slice(cell, rows_full):
    path = os.path.join(HERE, "data", f"scoped_slice_{cell}.json.gz")
    with gzip.open(path, "rt") as fh:
        table = json.load(fh)
    r = scoped.reduce_table(table, rows_full)
    expect = table["expect"]
    # what "one pass" is in the trace: every round body runs the
    # partition kernel once and one branch of the row ladder once, and
    # the slice starts at a tree's root pass
    assert sum(r["passes"].values()) == r["partition_passes"] + expect["root_passes"]
    assert r["passes"] == expect["passes"]
    assert r["partition_passes"] == expect["partition_passes"]
    # every branch's name is its static row count, a multiple of the
    # 2048-row block, below the cell's rows
    for branch in r["passes"]:
        if branch != "hist_rows_full":
            s = int(branch[len("hist_rows_"):])
            assert s % 2048 == 0 and s < rows_full
    # compact + kernel + update are all of round_hist but the
    # conditional's own time
    named = sum(r["round_hist_s"].get(k, 0.0) for k in
                ("hist_compact", "hist_kernel", "hist_update"))
    assert named == pytest.approx(sum(r["round_hist_s"].values()), rel=0.01)
    assert named == pytest.approx(expect["round_hist_s"], rel=1e-6)
    # the old reduction of the same rows agrees on the old scopes
    from harness import tracered
    old = tracered.reduce_table(table)
    assert old["busy_s"] == pytest.approx(r["busy_s"])
    assert old["scope_s"]["round_hist"] == pytest.approx(
        sum(r["round_hist_s"].values()))
    unnamed = r["busy_s"] - sum(r["scope_s"].values())
    assert 0 <= unnamed < 0.05 * r["busy_s"]


# ------------------------------------------------ no trace of this run
def test_no_trace_of_this_run_reads_as_none(tmp_path, monkeypatch):
    root = str(tmp_path)
    assert scoped.find_trace(root, "criteo-train", since=0.0) is None
    d = tmp_path / ".bench_cache" / "trace" / "criteo-train" / "plugins"
    d.mkdir(parents=True)
    f = d / "host.xplane.pb"
    f.write_bytes(b"")
    # another run's trace: written before this process started
    old = time.time() - 3600
    os.utime(f, (old, old))
    assert scoped.find_trace(root, "criteo-train", since=time.time() - 60) is None
    # this run's, but another cell's directory
    assert scoped.find_trace(root, "criteo63-train", since=0.0) is None
    assert scoped.find_trace(root, "criteo-train", since=0.0) == str(f)
    # no --workload on the command line: the newest file since the start
    assert scoped.find_trace(root, "", since=0.0) == str(f)

    monkeypatch.setattr(scoped, "ROOT", root)
    monkeypatch.setattr(scoped, "find_trace", lambda *a, **k: None)
    monkeypatch.setattr(scoped, "_THIS_RUN", [])
    run = {"rounds": 8, "trace": None, "phases": {}, "dispatch_s": [1.0]}
    for name in ("gradients_ms", "quantize_ms", "tree_root_ms",
                 "score_update_ms", "valid_score_ms", "hist_compact_ms",
                 "hist_kernel_ms", "hist_fill_share", "unnamed_device_ms",
                 "job_start_ms"):
        assert load_module("layers", name).read(run) is None, name


def test_readers_of_a_reduction(monkeypatch):
    r = scoped.reduce_table(hand_written(), rows_full=1000)
    monkeypatch.setattr(scoped, "_THIS_RUN", [r])
    run = {"rounds": 1}
    read = lambda name: load_module("layers", name).read(run)
    assert read("gradients_ms") == pytest.approx(5.0)
    assert read("score_update_ms") == pytest.approx(9.0)     # with leaf_renew
    assert read("valid_score_ms") == pytest.approx(5.0)      # with valid_metric
    assert read("hist_kernel_ms") == pytest.approx(51.0)
    assert read("hist_compact_ms") == pytest.approx(12.0)
    assert read("unnamed_device_ms") == pytest.approx(3.0)
    assert read("job_start_ms") == pytest.approx(35.0)
    assert read("hist_fill_share") == pytest.approx(100 * 1400 / 4048)


def test_compile_seconds_read_the_programs_counters():
    from lightgbm_tpu.obs import compile_events
    from lightgbm_tpu.obs.metrics import global_metrics
    run = {}
    lower = load_module("layers", "lower_s")
    load = load_module("layers", "compile_or_load_s")
    a, b = lower.read(run), load.read(run)
    compile_events._on_duration_event("/jax/core/compile/jaxpr_trace_duration", 0.25)
    compile_events._on_duration_event(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5)
    compile_events._on_duration_event(
        "/jax/compilation_cache/cache_retrieval_time_sec", 1.0)
    compile_events._on_duration_event(
        "/jax/core/compile/backend_compile_duration", 1.5)
    assert lower.read(run) - a == pytest.approx(0.75)
    assert load.read(run) - b == pytest.approx(1.5)    # 1.0 loading + 0.5 left
    assert scoped.program_counter("no_such_counter") is None
    assert global_metrics.counter("xla_cache_load_s") >= 1.0
