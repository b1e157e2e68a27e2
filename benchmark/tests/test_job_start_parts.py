"""``harness/job_start.py`` and ``harness/mesh_job_start.py``, checked on
tables written by hand, whose answers are plain arithmetic; on the
recorded start of a real traced window of each cell
(data/job_start_slice_*.json.gz, cut with tools/job_start_slice.py from
PR 40's chip runs), where the four parts are held to add up to what
``scoped.py`` reads as ``job_start_ms``; and on what a reader does when
handed a program without the span ``place`` (the older recorded slices)
or no trace of this run."""

import gzip
import json
import os

import pytest

from harness import job_start, load_module, mesh_job_start, scoped

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def hand_written():
    program = [
        ["lgbtpu.train", 10 * MS, 900 * MS, {}],
        ["lgbtpu.booster_init", 11 * MS, 300 * MS, {}],
        ["lgbtpu.objective_init", 12 * MS, 40 * MS, {}],
        ["lgbtpu.place", 60 * MS, 50 * MS, {"bytes": 1000, "what": "rows"}],
        ["lgbtpu.valid_mirror", 120 * MS, 90 * MS, {}],
        ["lgbtpu.place", 130 * MS, 70 * MS, {"bytes": 4000, "what": "rows"}],
        ["lgbtpu.valid_mirror", 250 * MS, 30 * MS, {}],
        ["lgbtpu.place", 251 * MS, 20 * MS, {"bytes": 300, "what": "whole"}],
        ["lgbtpu.train_fused", 315 * MS, 590 * MS, {}],
        ["lgbtpu.fused_operands", 316 * MS, 4 * MS, {}],
        ["lgbtpu.fused_prepare", 321 * MS, 9 * MS, {}],
        ["lgbtpu.fused_round_scan", 330 * MS, 170 * MS, {}],
        ["lgbtpu.jit_cache_load", 400 * MS, 1000, {"program": "run", "ms": 60.0}],
        ["lgbtpu.fused_chunk_transfer", 500 * MS, 300 * MS, {}],
        ["lgbtpu.fused_round_scan", 820 * MS, 2 * MS, {}],
    ]
    return {"spans": [["bench.window", 0, 1000 * MS], ["bench.job", 9 * MS, 905 * MS]],
            "modules": [[0, 280 * MS, 3 * MS, "jit_valid_mirror(3)"],
                        [0, 640 * MS, 150 * MS, "jit_run(1)"],
                        [0, 830 * MS, 150 * MS, "jit_run(1)"]],
            "ops": [[0, 640 * MS, 150 * MS, "%while.1", ""]], "program": program}


def test_the_four_parts_are_contiguous_and_add_up():
    red = job_start.reduce_table(hand_written())
    (job,) = red["jobs"]
    # the job opens at 10, train_fused at 315, its first scan closes at
    # 500, the round program first runs at 640; 140 ms of placements, all
    # before train_fused
    assert {k: v / MS for k, v in job.items()} == {
        "job_start": 630, "init": 305 - 140, "place": 140, "call": 185,
        "wait": 140}
    assert job["init"] + job["place"] + job["call"] + job["wait"] \
        == job["job_start"]
    assert scoped.reduce_table(hand_written())["job_start_s"] \
        == [pytest.approx(0.630)]
    assert red["places"] == [["rows", 1000, 50.0], ["rows", 4000, 70.0],
                             ["whole", 300, 20.0]]
    assert red["children_s"]["objective_init"] == [1, 0.04]
    assert red["children_s"]["fused_round_scan"] == [1, 0.17]
    assert "place" not in red["children_s"]


def test_an_execution_that_starts_before_the_call_returns_ends_the_call():
    table = hand_written()
    table["modules"][1][1] = 450 * MS           # inside the first scan
    (job,) = job_start.reduce_table(table)["jobs"]
    assert {k: v / MS for k, v in job.items()} == {
        "job_start": 440, "init": 165, "place": 140, "call": 135, "wait": 0}


def test_a_placement_inside_train_fused_is_taken_out_of_its_part():
    table = hand_written()
    table["program"].append(
        ["lgbtpu.place", 322 * MS, 5 * MS, {"bytes": 8, "what": "rows"}])
    (job,) = job_start.reduce_table(table)["jobs"]
    assert job["place"] == 145 * MS and job["call"] == 180 * MS
    assert sum(job[k] for k in ("init", "place", "call", "wait")) \
        == job["job_start"]


def test_a_program_without_the_place_span_gives_nothing():
    table = hand_written()
    table["program"] = [s for s in table["program"] if s[0] != "lgbtpu.place"]
    assert job_start.reduce_table(table)["jobs"] == []
    table["spans"] = []
    with pytest.raises(ValueError):
        job_start.reduce_table(table)


def mesh_written():
    program = [
        ["lgbtpu.train", 10 * MS, 2000 * MS, {}],
        ["lgbtpu.booster_init", 11 * MS, 500 * MS, {}],
        ["lgbtpu.place", 20 * MS, 300 * MS, {"bytes": 3_000_000, "what": "rows"}],
        ["lgbtpu.place", 400 * MS, 50 * MS, {"bytes": 500_000, "what": "whole"}],
        ["lgbtpu.iteration", 520 * MS, 900 * MS, {}],
        ["lgbtpu.boosting_gradients", 521 * MS, 30 * MS, {}],
        ["lgbtpu.tree_growth", 560 * MS, 20 * MS, {}],
    ]
    modules = [[d, 530 * MS, 2 * MS, "jit_gradients(2)"] for d in (0, 1)] + \
              [[0, 700 * MS, 600 * MS, "jit_local(7)"],
               [1, 690 * MS, 610 * MS, "jit_local(7)"]]
    return {"spans": [["bench.window", 0, 2100 * MS]], "modules": modules,
            "ops": [[d, 700 * MS, 600 * MS, "%while.1", ""] for d in (0, 1)],
            "program": program}


def test_the_mesh_start_runs_to_the_tree_program_on_the_given_chip():
    red = mesh_job_start.reduce_table(mesh_written(), 1)
    (job,) = red["jobs"]
    assert job == {"job_start": 680 * MS, "place": 350 * MS,
                   "placed_bytes": 3_500_000}
    assert mesh_job_start.reduce_table(mesh_written(), 0)["jobs"][0][
        "job_start"] == 690 * MS
    assert red["under_the_job_s"] == {"booster_init": 0.5, "iteration": 0.17}
    bare = mesh_written()
    bare["program"] = [s for s in bare["program"] if s[0] != "lgbtpu.place"]
    assert mesh_job_start.reduce_table(bare, 1)["jobs"] == []


def recorded(prefix):
    return sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.startswith(prefix))


def load(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as fh:
        return json.load(fh)


def test_there_are_recorded_starts():
    assert recorded("job_start_slice_")


@pytest.mark.parametrize("name", recorded("job_start_slice_"))
def test_recorded_start_adds_up_to_job_start_ms(name):
    table = load(name)
    expect = table.pop("expect")
    if expect["mesh"] is not None:
        red = mesh_job_start.reduce_table(table, table["device"])
        assert json.loads(json.dumps(red)) == expect["mesh"]
        for job in red["jobs"]:
            assert 0 < job["place"] < job["job_start"]
            assert job["placed_bytes"] > 0
        return
    red = job_start.reduce_table(table)
    assert json.loads(json.dumps(red)) == expect["parts"]
    starts = scoped.reduce_table(table)["job_start_s"]
    assert starts == expect["job_start_s"] and len(starts) == len(red["jobs"])
    for job, whole in zip(red["jobs"], starts):
        parts = [job[k] for k in ("init", "place", "call", "wait")]
        assert all(p >= 0 for p in parts)
        # within 1 ms of what job_start_ms reads (it is exact)
        assert abs(sum(parts) - whole * 1e9) < 1e6
        assert sum(parts) == job["job_start"]


@pytest.mark.parametrize("name", recorded("scoped_slice_"))
def test_an_older_programs_trace_has_no_parts(name):
    # PR 28's recorded slices: a program without the span ``place``
    assert job_start.reduce_table(load(name))["jobs"] == []


READERS = ("job_start_init_ms", "job_start_call_ms", "job_start_wait_ms",
           "dp_job_start_ms")


def test_readers_read_none_without_a_trace_of_this_run(monkeypatch):
    for mod in (scoped, job_start, mesh_job_start):
        monkeypatch.setattr(mod, "_THIS_RUN", [])
    monkeypatch.setattr(scoped, "find_trace", lambda *a, **k: None)
    run = {"rounds": 8, "phases": {}}
    for name in READERS:
        assert load_module("layers", name).read(run) is None
