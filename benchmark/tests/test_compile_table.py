"""``harness/compile_table.py``, checked on a table written by hand,
whose answers are plain arithmetic; on the table a real run of a cell
left behind (data/compile_table_*.json, dumped after the comparison from
PR 40's chip runs), where the plain reference's rows, compiled under no
span, are held out of every ``program_*`` reading; against the program
itself on the CPU; and on what a reader does against a program without
the table."""

import json
import os

import pytest

from harness import compile_table, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = compile_table.OUTSIDE


def row(span, inside, program, stage, seconds, count=1):
    return {"span": span, "inside": inside, "program": program,
            "stage": stage, "seconds": seconds, "count": count}


def hand_written():
    return [
        row("train", "fused_round_scan", "run", "trace", 6.0, 2),
        row("train", "fused_round_scan", "run", "lower", 3.0, 2),
        row("train", "fused_round_scan", "run", "compile", 0.25, 2),
        row("train", "fused_round_scan", "run", "cache_load", 1.5, 2),
        row("train", "fused_round_scan", "convert_element_type", "trace", 0.5),
        row("train", "fused_round_scan", "convert_element_type", "lower", 0.25),
        row("train", "valid_mirror", "valid_mirror", "trace", 0.125),
        row("train", "valid_mirror", "valid_mirror", "lower", 0.0625),
        row("train", "valid_mirror", "valid_mirror", "compile", 0.5),
        row("construct", "dense_bin_matrix", "clip", "lower", 0.125),
        # the reference's program, after the window, under no span; and a
        # lowering of the round program by hand, outside every span too
        row(OUT, OUT, "follow_trees", "trace", 4.0),
        row(OUT, OUT, "follow_trees", "lower", 2.0),
        row(OUT, OUT, "follow_trees", "compile", 8.0),
        row(OUT, OUT, "run", "lower", 1.0),
    ]


def test_only_rows_under_a_program_span_count():
    t = hand_written()
    assert compile_table.stage_seconds(t, "trace") == 6.625
    assert compile_table.stage_seconds(t, "lower") == 3.4375
    assert compile_table.stage_seconds(t, "compile") == 0.75
    assert compile_table.stage_seconds(t, "cache_load") == 1.5
    assert compile_table.lowerings(t) == 5
    # what the older ``lower_s`` held besides: the reference's 6 s and
    # the 1 s by hand
    assert sum(r["seconds"] for r in t if r["stage"] in ("trace", "lower")) \
        - compile_table.stage_seconds(t, "trace") \
        - compile_table.stage_seconds(t, "lower") == 7.0


def test_the_round_program_is_found_by_the_span_it_is_called_in():
    t = hand_written()
    assert compile_table.round_program(t) == "run"
    # all its lowerings in the process, the one outside a span too
    assert compile_table.round_program_lower_s(t) == 10.0
    loop = [row("train", "tree_growth", "take", "trace", 9.0),
            row("train", "collective_grow_dispatch", "local", "trace", 2.0),
            row("train", "collective_grow_dispatch", "local", "lower", 1.0),
            row("train", "collective_grow_dispatch", "clip", "lower", 0.5)]
    assert compile_table.round_program(loop) == "local"
    assert compile_table.round_program_lower_s(loop) == 3.0
    assert compile_table.round_program(loop[:1]) == "take"
    assert compile_table.round_program([]) is None
    assert compile_table.round_program_lower_s([]) is None
    # which kind of process this is: one the cache served, or one whose
    # backend made the round program
    assert compile_table.round_program_kind(t) == "loaded"
    assert compile_table.round_program_kind(loop) == "compiled"
    assert compile_table.round_program_kind([]) is None
    assert compile_table.round_program_kind(None) is None


def test_summary_names_programs_counts_and_spans():
    s = compile_table.summary(hand_written())
    assert s["under_program_spans_s"] == {"trace": 6.625, "lower": 3.4375,
                                          "compile": 0.75, "cache_load": 1.5}
    assert s["outside_the_program_s"]["lower"] == 3.0
    assert s["programs_lowered"] == 5 and s["round_program"] == "run"
    top = s["top_programs"]
    assert [p["program"] for p in top[:2]] == ["run", "convert_element_type"]
    assert top[0] == {"program": "run", "trace_lower_s": 9.0,
                      "inside": ["fused_round_scan"], "trace_s": 6.0,
                      "trace_n": 2, "lower_s": 3.0, "lower_n": 2,
                      "compile_s": 0.25, "compile_n": 2,
                      "cache_load_s": 1.5, "cache_load_n": 2}
    assert "follow_trees" not in {p["program"] for p in top}
    assert s["trace_lower_by_innermost_span_s"]["fused_round_scan"] == 9.75


def test_none_against_a_program_without_the_table():
    for fn in (compile_table.lowerings, compile_table.round_program_lower_s):
        assert fn(None) is None
    assert compile_table.stage_seconds(None, "trace") is None


def recorded():
    return sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.startswith("compile_table_"))


def test_there_are_recorded_tables():
    assert recorded()


@pytest.mark.parametrize("name", recorded())
def test_recorded_table_leaves_the_reference_out(name):
    with open(os.path.join(HERE, "data", name)) as fh:
        t = json.load(fh)
    outside = [r for r in t if r["span"] == OUT]
    inside = compile_table.under_spans(t)
    assert outside and inside
    assert {r["span"] for r in inside} <= {"construct", "train"}
    for stage in ("trace", "lower", "compile", "cache_load"):
        mine = compile_table.stage_seconds(t, stage)
        everything = sum(r["seconds"] for r in t if r["stage"] == stage)
        theirs = sum(r["seconds"] for r in outside if r["stage"] == stage)
        assert mine == pytest.approx(everything - theirs)
    assert compile_table.lowerings(t) < sum(r["count"] for r in t
                                            if r["stage"] == "lower")
    name = compile_table.round_program(t)
    assert name is not None
    assert 0 < compile_table.round_program_lower_s(t) \
        <= compile_table.stage_seconds(t, "trace") \
        + compile_table.stage_seconds(t, "lower") + sum(
            r["seconds"] for r in outside if r["program"] == name)


def test_readers_read_the_programs_own_table(monkeypatch):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.obs import compile_events
    from lightgbm_tpu.utils.timer import phase
    compile_events.install()
    monkeypatch.setattr(compile_table, "_SAID", [True])

    def pr40_bench_fn(x):
        return (x * 3.0).sum()
    x = jnp.arange(4.0)
    names = ("program_trace_s", "program_lowering_s",
             "program_backend_compile_s", "program_cache_load_s",
             "programs_lowered", "round_program_lower_s")
    before = {n: load_module("layers", n).read({}) for n in names}
    with phase("train"):
        with phase("fused_round_scan"):
            jax.jit(pr40_bench_fn)(x).block_until_ready()
    jax.jit(lambda v: v - 1.0)(x).block_until_ready()      # under no span
    after = {n: load_module("layers", n).read({}) for n in names}
    assert after["programs_lowered"] == before["programs_lowered"] + 1
    assert after["program_trace_s"] > before["program_trace_s"]
    assert after["program_lowering_s"] > before["program_lowering_s"]
    assert after["program_backend_compile_s"] + after["program_cache_load_s"] \
        > before["program_backend_compile_s"] + before["program_cache_load_s"]
    assert after["round_program_lower_s"] > 0


def test_readers_read_none_where_the_program_keeps_no_table(monkeypatch):
    from lightgbm_tpu.obs import compile_events
    monkeypatch.delattr(compile_events, "table")
    for name in ("program_trace_s", "program_lowering_s",
                 "program_backend_compile_s", "program_cache_load_s",
                 "programs_lowered", "round_program_lower_s"):
        assert load_module("layers", name).read({}) is None


def test_construct_counters_read_none_where_undeclared(monkeypatch):
    from lightgbm_tpu.obs import metrics
    for name in ("construct_bin_mappers_s", "construct_bin_matrix_s"):
        assert load_module("layers", name).read({}) is not None
        monkeypatch.delitem(metrics.COUNTERS, name)
        assert load_module("layers", name).read({}) is None
