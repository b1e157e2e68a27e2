"""The compile-event listener's spans and table (obs/compile_events.py,
PR 40): each stage jax announces is a ``phase`` with the program's name,
nested under whatever span was open, and a row of a process-wide table
keyed by the outermost span, the innermost span, the program and the
stage.  The six counters read what they read before."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.obs import compile_events, trace as obs_trace
from lightgbm_tpu.obs.metrics import global_metrics
from lightgbm_tpu.utils.timer import open_spans, phase

COUNTERS = ("jaxpr_trace_s", "xla_lowering_s", "xla_backend_compile_s",
            "xla_cache_load_s", "xla_compile_events",
            "xla_program_lowerings")
STAGES = ("trace", "lower", "compile")


@pytest.fixture(autouse=True)
def _armed():
    assert compile_events.install() or compile_events.installed()


def _fresh(name):
    """A jitted function no test has compiled, under a name of its own."""
    salt = float(np.random.default_rng().integers(1, 1 << 30))

    def fn(x):
        return (x * salt + 1.0).sum()
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _rows(program):
    return {(r["span"], r["inside"], r["stage"]): (r["seconds"], r["count"])
            for r in compile_events.table() if r["program"] == program}


def _counters():
    return {c: global_metrics.counter(c) for c in COUNTERS}


def test_a_compile_inside_a_span_leaves_rows_with_the_programs_name():
    fn, x = _fresh("pr40_under_outer"), jnp.arange(5.0)
    with phase("outer"):
        with phase("inner"):
            fn(x).block_until_ready()
    rows = _rows("pr40_under_outer")
    loaded = ("outer", "inner", "cache_load") in rows
    assert set(rows) == {("outer", "inner", s) for s in STAGES} \
        | ({("outer", "inner", "cache_load")} if loaded else set())
    for key, (seconds, count) in rows.items():
        assert count == 1 and seconds >= 0.0, key
    assert rows[("outer", "inner", "trace")][0] > 0.0
    assert rows[("outer", "inner", "lower")][0] > 0.0


def test_a_second_call_adds_nothing():
    fn, x = _fresh("pr40_called_twice"), jnp.arange(5.0)
    with phase("outer"):
        fn(x).block_until_ready()
    first, counted = _rows("pr40_called_twice"), _counters()
    with phase("outer"):
        fn(x).block_until_ready()
    assert _rows("pr40_called_twice") == first
    assert _counters() == counted


def test_a_nested_jits_trace_is_not_counted_twice():
    inner = _fresh("pr40_nested_inner")

    def outer(x):
        return inner(x) * 2.0
    outer.__name__ = outer.__qualname__ = "pr40_nested_outer"
    x = jnp.arange(3.0)
    before = global_metrics.counter("jaxpr_trace_s")
    with phase("outer"):
        jax.jit(outer)(x).block_until_ready()
    got = _rows("pr40_nested_outer")
    assert got[("outer", "outer", "trace")][1] == 1
    # the inner function was traced inside the outer trace: no row, no
    # span and no seconds of its own, and it is lowered inside the outer
    # module, not as a program
    assert _rows("pr40_nested_inner") == {}
    assert global_metrics.counter("jaxpr_trace_s") - before \
        == pytest.approx(got[("outer", "outer", "trace")][0])


def test_a_compile_outside_any_span_lands_outside_the_program():
    assert open_spans() == []
    _fresh("pr40_outside")(jnp.arange(4.0)).block_until_ready()
    rows = _rows("pr40_outside")
    out = compile_events.OUTSIDE
    assert {k[:2] for k in rows} == {(out, out)}
    assert {k[2] for k in rows} >= set(STAGES)


def test_the_six_counters_read_what_the_events_hand_over():
    fn, x = _fresh("pr40_counted"), jnp.arange(6.0)
    before = _counters()
    with phase("outer"):
        fn(x).block_until_ready()
    after, rows = _counters(), _rows("pr40_counted")
    assert after["xla_program_lowerings"] == before["xla_program_lowerings"] + 1
    assert after["xla_compile_events"] == before["xla_compile_events"] + 1
    sec = {s: rows.get(("outer", "outer", s), (0.0, 0))[0]
           for s in STAGES + ("cache_load",)}
    assert after["jaxpr_trace_s"] - before["jaxpr_trace_s"] \
        == pytest.approx(sec["trace"])
    assert after["xla_lowering_s"] - before["xla_lowering_s"] \
        == pytest.approx(sec["lower"])
    assert after["xla_backend_compile_s"] - before["xla_backend_compile_s"] \
        == pytest.approx(sec["compile"])
    assert after["xla_cache_load_s"] - before["xla_cache_load_s"] \
        == pytest.approx(sec["cache_load"])


def test_the_stages_are_spans_of_the_recorder_nested_under_the_open_span():
    rec = obs_trace.start()
    assert rec is not None
    try:
        with phase("outer"):
            _fresh("pr40_recorded")(jnp.arange(8.0)).block_until_ready()
    finally:
        obs_trace.stop(rec)
    events = [e for e in rec.to_dict()["traceEvents"] if e.get("ph") == "X"]
    mine = [e for e in events
            if (e.get("args") or {}).get("program") == "pr40_recorded"]
    names = {e["name"] for e in mine}
    assert {"jit_trace", "jit_lower", "jit_compile"} <= names
    (outer,) = [e for e in events if e["name"] == "outer"]
    for e in mine:
        assert outer["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    for e in mine:
        if e["name"] == "jit_cache_load":
            assert e["args"]["ms"] >= 0.0
    assert open_spans() == []


def test_an_end_without_a_start_is_counted_and_breaks_nothing():
    # what a listener armed in the middle of a stage hears, and what
    # benchmark/tests hand it: a duration with no start and no name
    before = _counters()
    compile_events._on_duration_event(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25)
    compile_events._on_duration_event(
        "/jax/core/compile/jaxpr_trace_duration", 0.5)
    after = _counters()
    assert after["xla_lowering_s"] == pytest.approx(before["xla_lowering_s"] + 0.25)
    assert after["jaxpr_trace_s"] == pytest.approx(before["jaxpr_trace_s"] + 0.5)
    assert _rows("?")[(compile_events.OUTSIDE, compile_events.OUTSIDE,
                       "lower")][1] >= 1
    assert open_spans() == []


def test_a_threads_stages_land_under_its_own_spans():
    seen = {}

    def work():
        with phase("worker_span"):
            _fresh("pr40_in_thread")(jnp.arange(9.0)).block_until_ready()
        seen["open"] = list(open_spans())
    with phase("main_span"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert seen["open"] == []
    assert {k[:2] for k in _rows("pr40_in_thread")} \
        == {("worker_span", "worker_span")}


def test_the_table_rides_booster_telemetry(synthetic_binary):
    import lightgbm_tpu as lgb
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
    table = bst.telemetry()["compile_table"]
    assert table == sorted(table, key=lambda r: -r["seconds"])
    assert {"span", "inside", "program", "stage", "seconds", "count"} \
        == set(table[0])
    assert any(r["span"] == "train" for r in table)


def test_a_span_that_fails_does_not_fail_the_compile(monkeypatch):
    """The listener runs inside jax's tracing and compilation: a span
    that raises is reported once and the rows and counters are kept."""
    from lightgbm_tpu.utils import log, timer

    class broken(timer.phase):
        def __enter__(self):
            raise RuntimeError("no annotation today")
    said = []
    monkeypatch.setattr(timer, "phase", broken)
    monkeypatch.setattr(compile_events, "_span_failed", False)
    monkeypatch.setattr(log, "_callback", said.append)
    monkeypatch.setattr(log, "_verbosity", 1)
    before = _counters()
    fn, x = _fresh("pr40_broken_span"), jnp.arange(4.0)
    assert float(fn(x)) == float(fn(x))
    rows = _rows("pr40_broken_span")
    assert {k[2] for k in rows} >= set(STAGES)
    assert _counters()["xla_program_lowerings"] \
        == before["xla_program_lowerings"] + 1
    assert len([m for m in said if "jit_* span failed" in m]) == 1
    assert open_spans() == []


@pytest.mark.parametrize("first", ["lightgbm_tpu.utils.timer",
                                   "lightgbm_tpu.obs.compile_events",
                                   "lightgbm_tpu.obs"])
def test_either_half_can_be_imported_first(first):
    """``utils.timer`` imports ``obs``, whose listener uses ``phase``:
    a process may reach either module first."""
    import os
    import subprocess
    import sys
    # the package's own __init__ imports both in its order: drop them
    # and come again from ``first``
    code = (f"import sys, lightgbm_tpu\n"
            f"[sys.modules.pop(m) for m in list(sys.modules)"
            f" if m.startswith(('lightgbm_tpu.obs', 'lightgbm_tpu.utils.timer'))]\n"
            f"import {first}\n"
            f"from lightgbm_tpu.utils.timer import phase, open_spans\n"
            f"from lightgbm_tpu.obs import compile_events\n"
            f"assert compile_events._timer().phase is phase\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
