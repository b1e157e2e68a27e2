"""``harness/mesh_trace.py``, checked three ways: on a table of two chips
written by hand, whose answers are plain arithmetic; on a recorded slice
of a real four-plane trace of ``criteo-dp4-train``
(data/mesh_slice_criteo-dp4-train.json.gz, cut with tools/trace_slice.py
from PR 30's traced chip run); and on what a reader does when handed no
trace of this run."""

import gzip
import json
import os

import pytest

from harness import load_module, mesh_trace, scoped

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
TREE = "jit(local)/shard_map/jit(grow_tree_batched)/"
BODY = TREE + "tree_select/while/body/"
PSUM = "round_hist/hist_kernel/hist_allreduce/psum"
READERS = ("dp_hist_ms", "dp_collective_ms", "dp_chip_skew_ms",
           "dp_device_idle_share", "dp_between_programs_ms",
           "dp_valid_eval_ms")


def hand_written():
    """One round on two chips, window 0-200 ms.  Chip 0 computes 60 ms
    of histogram and waits 30 ms in the all-reduce; chip 1 computes 85
    and waits 5: busy alike, 25 ms apart outside the collective."""
    def chip(dev, kernel_ms, wait_ms):
        t = 20 * MS
        ops = [[dev, t, 100 * MS, "%while.1", ""],
               [dev, t, 10 * MS, "%fusion.1", BODY + "partition/k"]]
        t += 10 * MS
        ops.append([dev, t, kernel_ms * MS, "%hist_kernel.1", BODY
                    + "round_hist/hist_rows_full/hist_kernel/pallas_call:"])
        t += kernel_ms * MS
        ops.append([dev, t, wait_ms * MS, "%all-reduce.7", BODY + PSUM])
        # a small sum folded into no named scope: seen by opcode only
        ops.append([dev, 125 * MS, 2 * MS, "%all-reduce.9",
                    "jit(quantize)/reduce_max"])
        ops.append([dev, 140 * MS, 20 * MS, "%fusion.9",
                    "jit(predict_bins_tree_matmul)/dot_general"])
        return ops
    ops = chip(0, 60, 30) + chip(1, 85, 5)
    modules = [[d, a * MS, b * MS, name] for d in (0, 1) for a, b, name in
               ((20, 100, "jit_local"), (125, 2, "jit_quantize"),
                (140, 20, "jit_predict_bins_tree_matmul"))]
    program = [["lgbtpu.train", 0, 200 * MS, {}],
               ["lgbtpu.iteration", 10 * MS, 180 * MS, {}],
               ["lgbtpu.tree_growth", 12 * MS, 6 * MS, {}],
               ["lgbtpu.tree_finalize", 100 * MS, 30 * MS, {}],
               ["lgbtpu.valid_eval", 132 * MS, 3 * MS, {}],
               ["lgbtpu.metric_eval", 150 * MS, 25 * MS, {}]]
    return {"ops": ops, "modules": modules, "program": program,
            "spans": [["bench.window", 0, 200 * MS], ["bench.job", 0, 200 * MS]]}


def test_hand_written_table_reduces_chip_by_chip():
    r = mesh_trace.reduce_table(hand_written())
    assert r["devices"] == [0, 1] and r["window_s"] == pytest.approx(0.2)
    c0, c1 = r["chips"]
    # busy: 100 (tree program) + 2 + 20 on both chips
    assert c0["busy_s"] == pytest.approx(0.122)
    assert c1["busy_s"] == pytest.approx(0.122)
    assert c0["hist_s"] == pytest.approx(0.090)      # kernel + psum
    assert c0["collective_s"] == pytest.approx(0.030)
    assert c1["collective_s"] == pytest.approx(0.005)
    assert c0["collective_by_scope_s"] == {"hist_allreduce":
                                           pytest.approx(0.030)}
    # the unnamed all-reduce shows by opcode only
    assert c0["collective_by_opcode_s"] == pytest.approx(0.032)
    assert c0["collective_executions"] == 1
    assert c0["compute_s"] == pytest.approx(0.092)
    assert c1["compute_s"] == pytest.approx(0.117)
    assert r["compute_spread_s"] == pytest.approx(0.025)
    # gaps between programs, each named by the innermost span open at
    # its middle: 0-20 (10: the iteration has begun, tree_growth has
    # not), 120-125 (tree_finalize), 127-140 (133.5: valid_eval), 160-200
    # (180: metric_eval is over, the iteration is not)
    assert c0["programs_run"] == 3
    assert c0["between_programs_s"] == pytest.approx(0.078)
    assert c0["between_programs_by_span_s"] == {
        "lgbtpu.tree_finalize": pytest.approx(0.005),
        "lgbtpu.valid_eval": pytest.approx(0.013),
        "lgbtpu.iteration": pytest.approx(0.060)}
    assert mesh_trace.span_s(r, *mesh_trace.VALID_SPANS) \
        == pytest.approx(0.028)


def test_readers_on_the_hand_written_table(monkeypatch):
    red = mesh_trace.reduce_table(hand_written())
    monkeypatch.setattr(mesh_trace, "_THIS_RUN", [red])
    monkeypatch.setattr(mesh_trace, "_SAID", [True])
    run = {"rounds": 1, "collective_bytes": 11_526_144}
    got = {n: load_module("layers", n).read(run) for n in READERS}
    assert got == {"dp_hist_ms": pytest.approx(90.0),
                   "dp_collective_ms": pytest.approx(30.0),
                   "dp_chip_skew_ms": pytest.approx(25.0),
                   "dp_device_idle_share": pytest.approx(39.0),
                   "dp_between_programs_ms": pytest.approx(78.0),
                   "dp_valid_eval_ms": pytest.approx(28.0)}


def test_the_line_for_stderr_names_gaps_and_payload(monkeypatch, capsys):
    red = mesh_trace.reduce_table(hand_written())
    monkeypatch.setattr(mesh_trace, "_SAID", [])
    mesh_trace.say(red, {"rounds": 1, "collective_bytes": 15_000_000})
    line = capsys.readouterr().err.strip()
    assert line.startswith("scoped: ")
    said = json.loads(line[len("scoped: "):])["mesh"]
    assert said["collective_gb_per_s"] == pytest.approx(0.5)
    assert said["between_programs_by_span_ms"]["lgbtpu.iteration"] == 60.0
    assert said["per_chip_ms_a_round"]["1"]["compute"] == 117.0


def test_a_program_without_the_names_reads_as_nothing(monkeypatch):
    """The parent of PR 30: no collective scope, no ``valid_eval`` span,
    one chip."""
    table = hand_written()
    table["ops"] = [[o[0], o[1], o[2], o[3], o[4].replace(
        "/hist_allreduce", "")] for o in table["ops"] if o[0] == 0]
    table["modules"] = [m for m in table["modules"] if m[0] == 0]
    table["program"] = [s for s in table["program"]
                        if s[0] != "lgbtpu.valid_eval"]
    red = mesh_trace.reduce_table(table)
    monkeypatch.setattr(mesh_trace, "_THIS_RUN", [red])
    monkeypatch.setattr(mesh_trace, "_SAID", [True])
    run = {"rounds": 1}
    for name in ("dp_collective_ms", "dp_chip_skew_ms", "dp_valid_eval_ms"):
        assert load_module("layers", name).read(run) is None, name


def test_no_trace_of_this_run_reads_as_nothing(monkeypatch, tmp_path):
    monkeypatch.setattr(scoped, "ROOT", str(tmp_path))
    monkeypatch.setattr(scoped.find_trace, "__defaults__",
                        (str(tmp_path), None, None))
    monkeypatch.setattr(mesh_trace, "_THIS_RUN", [])
    for name in READERS:
        assert load_module("layers", name).read({"rounds": 3}) is None


SLICE = os.path.join(HERE, "data", "mesh_slice_criteo-dp4-train.json.gz")


@pytest.mark.skipif(not os.path.exists(SLICE), reason="no recorded slice")
def test_recorded_slice_of_a_four_plane_trace():
    with gzip.open(SLICE, "rt") as fh:
        table = json.load(fh)
    r = mesh_trace.reduce_table(table)
    want = table["expect_mesh"]
    assert r["devices"] == [0, 1, 2, 3]
    assert r["busiest"] == want["busiest"]
    for chip, exp in zip(r["chips"], want["chips"]):
        for key, value in exp.items():
            assert chip[key] == pytest.approx(value, rel=1e-9), key
    # what holds in any real trace of this job: the slice crosses a round
    # boundary, so it has gaps between programs; the named scopes hold
    # every all-reduce the chips ran (and the copies XLA puts round them:
    # a little more than the opcodes' time); the first chip also runs the
    # small one-device programs of the loop's eager glue; the histogram
    # passes are most of what a chip does
    for chip in r["chips"]:
        assert chip["between_programs_s"] > 0
        assert 0 < chip["collective_by_opcode_s"] <= chip["collective_s"] \
            < 1.25 * chip["collective_by_opcode_s"]
        assert chip["programs_run"] <= r["chips"][0]["programs_run"]
        # (an operation that straddles the slice's edge keeps its whole
        # self time, as in scoped.py: hist can pass the clipped busy time)
        assert 0.5 * chip["busy_s"] < chip["hist_s"] < 1.05 * chip["busy_s"]
