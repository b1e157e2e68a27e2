"""Phase-timer tests (reference Common::Timer / USE_TIMETAG aggregate
table, utils/common.h:973)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.timer import global_timer


@pytest.fixture(autouse=True)
def _clean_timer():
    global_timer.enabled = False
    global_timer.reset()
    yield
    global_timer.enabled = False
    global_timer.reset()


def test_phase_table_collected_when_verbose(synthetic_binary):
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbosity": 2, "metric": ["binary_logloss"]}
    lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=3)
    s = global_timer.summary()
    assert "tree_growth" in s
    assert "boosting_gradients" in s
    assert "metric_eval" in s


def test_timer_state_scoped_per_training(synthetic_binary):
    """A verbose run followed by a quiet run: the quiet run disables and
    clears the accumulator (no cross-run leakage)."""
    X, y = synthetic_binary
    pv = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": 2}
    lgb.train(pv, lgb.Dataset(X, label=y, params=pv), num_boost_round=2)
    assert global_timer.enabled and "tree_growth" in global_timer.summary()

    pq = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbose": -1}
    lgb.train(pq, lgb.Dataset(X, label=y, params=pq), num_boost_round=2)
    assert not global_timer.enabled
    assert global_timer.summary() == "no phases timed"


# ------------------------------------------------ the stack of open spans
def test_open_spans_follow_the_nesting():
    from lightgbm_tpu.utils.timer import open_spans, phase
    assert open_spans() == []
    with phase("a"):
        assert open_spans() == ["a"]
        with phase("b", rows=3):
            assert open_spans() == ["a", "b"]
        assert open_spans() == ["a"]
    assert open_spans() == []


def test_open_spans_balanced_after_an_exception():
    from lightgbm_tpu.utils.timer import PhaseTimer, open_spans, phase
    timer = PhaseTimer()
    timer.enable()
    with pytest.raises(KeyError):
        with phase("outer", timer):
            with phase("inner", timer):
                raise KeyError("boom")
    assert open_spans() == []
    # the spans were still timed, as before
    assert set(timer.as_dict()) == {"outer", "inner"}


def test_open_spans_are_per_thread():
    import threading
    from lightgbm_tpu.utils.timer import open_spans, phase
    seen, go, done = {}, threading.Event(), threading.Event()

    def work():
        seen["start"] = list(open_spans())
        with phase("theirs"):
            seen["inside"] = list(open_spans())
            done.set()
            go.wait(10)
        seen["end"] = list(open_spans())

    with phase("mine"):
        t = threading.Thread(target=work)
        t.start()
        assert done.wait(10)
        # the other thread's open span is not on this thread's stack
        assert open_spans() == ["mine"]
        go.set()
        t.join()
    assert seen == {"start": [], "inside": ["theirs"], "end": []}
    assert open_spans() == []


def test_a_span_with_a_seconds_counter_is_timed_with_everything_off():
    from lightgbm_tpu.obs import trace
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.utils.timer import phase
    assert trace.active() is None and not global_timer.enabled
    before = global_metrics.counter("construct_bin_mappers_s")
    with phase("dense_bin_mappers", global_timer,
               seconds="construct_bin_mappers_s") as span:
        pass
    assert span._t0 is not None
    assert global_metrics.counter("construct_bin_mappers_s") > before
    assert global_timer.summary() == "no phases timed"
