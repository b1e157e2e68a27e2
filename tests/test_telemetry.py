"""Telemetry subsystem tests (obs/ — trace spans, metrics registry,
memory observability; docs/OBSERVABILITY.md).

Covers the ISSUE-2 acceptance surface: trace export is valid Chrome trace
JSON with properly nested spans, counters are monotone across iterations,
the telemetry JSONL carries one record per iteration with host/device
memory fields, and disabled-mode training writes no files.
"""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import global_metrics, memory as obs_memory, trace
from lightgbm_tpu.utils.timer import PhaseTimer, global_timer

N_ROUNDS = 4


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """ONE observed training run shared by the trace/JSONL assertions
    (keeps the suite's added wall-clock to a single small training)."""
    d = tmp_path_factory.mktemp("telemetry")
    trace_path = str(d / "trace.json")
    tele_path = str(d / "tele.jsonl")
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6))
    y = (X[:, 0] - X[:, 1] + rng.normal(scale=0.3, size=400) > 0
         ).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "metric": ["binary_logloss"],
         "trace_output": trace_path, "telemetry_output": tele_path}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=N_ROUNDS,
                    valid_sets=[ds.create_valid(X, label=y)],
                    valid_names=["v0"])
    return bst, trace_path, tele_path


def test_trace_export_is_valid_chrome_trace(traced_run):
    _, trace_path, _ = traced_run
    with open(trace_path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events, "trace has no events"
    spans = [e for e in events if e["ph"] == "X"]
    assert spans, "trace has no complete span events"
    for e in spans:
        # required Chrome trace-event fields on every span
        for field in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert field in e, f"span missing {field}: {e}"
        assert e["dur"] >= 0
    names = {e["name"] for e in spans}
    assert {"train", "iteration", "tree_growth",
            "boosting_gradients"} <= names


def test_trace_spans_properly_nested(traced_run):
    """Container spans strictly contain their children on the same
    thread: every iteration inside train, every tree_growth inside an
    iteration (context-manager discipline must survive export)."""
    _, trace_path, _ = traced_run
    spans, covers = _spans_of(trace_path), _inside
    train_spans = [e for e in spans if e["name"] == "train"]
    iters = [e for e in spans if e["name"] == "iteration"]
    grows = [e for e in spans if e["name"] == "tree_growth"]
    assert len(train_spans) == 1
    assert len(iters) == N_ROUNDS
    assert len(grows) == N_ROUNDS
    for it in iters:
        assert covers(train_spans[0], it)
    for g in grows:
        assert any(covers(it, g) for it in iters), \
            "tree_growth span not nested in any iteration span"


def _spans_of(trace_path):
    with open(trace_path) as f:
        return [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]


def _inside(outer, inner):
    return (outer["ts"] <= inner["ts"] + 1e-3
            and outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"] - 1e-3)


def test_place_spans_count_the_arrays_bytes(traced_run):
    """Every placement of ``GBDT._place_rows`` / ``_place_whole`` is a
    span ``place`` inside ``booster_init`` whose ``bytes`` are the
    array's ``nbytes``."""
    bst, trace_path, _ = traced_run
    spans = _spans_of(trace_path)
    (init,) = [e for e in spans if e["name"] == "booster_init"]
    places = [e for e in spans if e["name"] == "place"]
    assert places and all(_inside(init, e) for e in places)
    gb = bst._gbdt
    n, k = gb.train_set.num_data, gb.num_tree_per_iteration
    by_what = {}
    for e in places:
        by_what.setdefault(e["args"]["what"], []).append(e["args"]["bytes"])
    # rows: the scores and the objective's row arrays (a serial learner
    # takes the bins without a placement); whole: a valid set's scores
    # and its bins
    assert n * k * 4 in by_what["rows"]
    (valid,) = gb.valid_sets
    scores, bins = sorted(by_what["whole"], key=lambda b: b != valid.bins.nbytes,
                          reverse=True)
    assert bins == valid.bins.nbytes
    assert scores in (valid.num_data * k * 4, valid.num_data * k * 8)
    assert all(e["args"]["bytes"] > 0 for e in places)


def test_fused_operands_is_a_span_of_train_fused(tmp_path):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(1200, 5))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    out = str(tmp_path / "fused.json")
    p = {"objective": "binary", "metric": ["auc"], "num_leaves": 7,
         "min_data_in_leaf": 5, "verbose": -1, "tpu_split_batch": 4,
         "use_quantized_grad": True, "quant_train_renew_leaf": True,
         "trace_output": out}
    ds = lgb.Dataset(X[:1000], label=y[:1000], params=p)
    bst = lgb.train(p, ds, num_boost_round=8,
                    valid_sets=[ds.create_valid(X[1000:], label=y[1000:])],
                    callbacks=[lgb.record_evaluation({})])
    assert bst._gbdt.metrics.counter("fused_rounds") == 8
    spans = _spans_of(out)
    (fused,) = [e for e in spans if e["name"] == "train_fused"]
    (operands,) = [e for e in spans if e["name"] == "fused_operands"]
    scans = [e for e in spans if e["name"] == "fused_round_scan"]
    assert _inside(fused, operands)
    assert operands["ts"] + operands["dur"] <= scans[0]["ts"] + 1e-3


def test_dense_construct_stages_are_spans_and_always_armed_counters():
    """The dense ``Dataset.construct`` in two stages, each a span
    inside ``construct`` and a seconds counter that is bumped with
    nothing switched on; together they are the call, within 5%."""
    from lightgbm_tpu.utils.timer import phase
    names = {"dense_bin_mappers": "construct_bin_mappers_s",
             "dense_bin_matrix": "construct_bin_matrix_s"}
    rng = np.random.default_rng(11)
    X = rng.normal(size=(200_000, 12))
    y = (X[:, 0] > 0).astype(np.float64)
    assert trace.active() is None and not global_timer.enabled
    before = {c: global_metrics.counter(c) for c in names.values()}
    import time
    t0 = time.perf_counter()
    # (no bundle plan: on dense columns it finds nothing in 15 ms, under
    # no span, which since PR 45 is 7% of this call)
    ds = lgb.Dataset(X, label=y, params={"verbose": -1,
                                         "enable_bundle": False}).construct()
    took = time.perf_counter() - t0
    delta = {c: global_metrics.counter(c) - before[c] for c in names.values()}
    assert all(v > 0.0 for v in delta.values()), delta
    assert sum(delta.values()) <= took
    assert sum(delta.values()) >= 0.95 * took, (delta, took)

    rec = trace.start()
    try:
        ds.create_valid(X[:50_000], label=y[:50_000]).construct()
    finally:
        trace.stop(rec)
    spans = [e for e in rec.to_dict()["traceEvents"] if e.get("ph") == "X"]
    (outer,) = [e for e in spans if e["name"] == "construct"]
    got = {e["name"]: e for e in spans if e["name"] in names}
    # a valid set takes the training set's mappers: no bin finder
    assert set(got) == {"dense_bin_matrix"}
    assert all(_inside(outer, e) for e in got.values())
    assert sum(e["dur"] for e in got.values()) >= 0.95 * outer["dur"]
    assert phase("anything")._seconds == ""


def test_telemetry_jsonl_one_record_per_iteration(traced_run):
    _, _, tele_path = traced_run
    with open(tele_path) as f:
        recs = [json.loads(ln) for ln in f.read().strip().splitlines()]
    assert len(recs) == N_ROUNDS
    assert [r["iteration"] for r in recs] == list(range(N_ROUNDS))
    for r in recs:
        # host/device memory fields present on every record
        assert "host_rss_mb" in r and "host_peak_rss_mb" in r
        assert "device_memory" in r
        assert r["counters"]["iterations"] >= 1
        assert any(k.startswith("v0.") for k in r["evals"])


def test_counters_monotone_across_iterations(traced_run):
    _, _, tele_path = traced_run
    with open(tele_path) as f:
        recs = [json.loads(ln) for ln in f.read().strip().splitlines()]
    keys = set().union(*(r["counters"] for r in recs))
    for key in keys:
        series = [r["counters"].get(key, 0) for r in recs]
        assert series == sorted(series), \
            f"counter {key} not monotone: {series}"
    # iterations advances by exactly one per record
    its = [r["counters"]["iterations"] for r in recs]
    assert its == list(range(1, N_ROUNDS + 1))


def test_booster_telemetry_snapshot(traced_run):
    bst, _, _ = traced_run
    tel = bst.telemetry()
    assert tel["counters"]["iterations"] == N_ROUNDS
    assert tel["counters"]["trees_grown"] == N_ROUNDS
    assert "tree_growth" in tel["phases"]
    assert tel["phases"]["tree_growth"]["count"] == N_ROUNDS
    assert tel["memory"]["host_rss_mb"] is None or \
        tel["memory"]["host_rss_mb"] > 0


def test_trace_report_tool(traced_run):
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _, trace_path, _ = traced_run
    out = mod.render(mod.load_trace(trace_path))
    assert "tree_growth" in out
    assert "total_s" in out


def test_disabled_mode_emits_no_files(tmp_path, synthetic_binary):
    """No trace/telemetry keys -> no recorder active and no files
    written anywhere under the working dir."""
    X, y = synthetic_binary
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
             "verbose": -1}
        lgb.train(p, lgb.Dataset(X[:300], label=y[:300], params=p),
                  num_boost_round=2)
        assert trace.active() is None
        assert list(tmp_path.iterdir()) == []
    finally:
        os.chdir(cwd)


def test_a_model_trained_with_everything_on_is_bit_identical(tmp_path,
                                                             synthetic_binary):
    """``trace_output``, ``profile_dir`` and ``verbosity=2`` observe a
    job and change nothing of it: the model text is the one a job with
    none of them writes."""
    X, y = synthetic_binary
    base = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
            "verbose": -1, "metric": ["auc"]}

    def model(extra):
        p = {**base, **extra}
        ds = lgb.Dataset(X[:400], label=y[:400], params=p)
        bst = lgb.train(p, ds, num_boost_round=4,
                        valid_sets=[ds.create_valid(X[400:], label=y[400:])])
        text = bst.model_to_string()
        # the parameters block names the output paths; the trees do not
        return text[:text.index("parameters:")] if "parameters:" in text \
            else text, bst.predict(X[:50])

    quiet_text, quiet_pred = model({})
    loud_text, loud_pred = model({
        "trace_output": str(tmp_path / "t.json"),
        "profile_dir": str(tmp_path / "prof"), "verbosity": 2})
    assert (tmp_path / "t.json").exists()
    assert loud_text == quiet_text
    assert np.array_equal(loud_pred, quiet_pred)


def test_per_booster_timer_isolation(synthetic_binary):
    """Satellite 1: each booster owns its PhaseTimer — training a second
    (quiet) booster must not clear or disable the first's table."""
    X, y = synthetic_binary
    pv = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": 2}
    b1 = lgb.train(pv, lgb.Dataset(X[:300], label=y[:300], params=pv),
                   num_boost_round=2)
    t1 = b1._gbdt.timer
    assert t1.enabled and "tree_growth" in t1.summary()
    pq = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbose": -1}
    lgb.train(pq, lgb.Dataset(X[:300], label=y[:300], params=pq),
              num_boost_round=2)
    # first booster's table survives the second training untouched
    assert t1.enabled
    assert t1.as_dict()["tree_growth"]["count"] == 2


def test_phase_timer_disable():
    t = PhaseTimer()
    t.enable()
    with t.timer("x"):
        pass
    t.disable()
    with t.timer("x"):
        pass
    assert not t.enabled
    assert t.as_dict()["x"]["count"] == 1


def test_batched_fallback_warns_and_counts(synthetic_binary):
    """Satellite 2: a config that requests the batched grower but must
    fall back to the strict learner warns once and bumps the
    batched_path_fallbacks counter (extra_trees under the data-parallel
    mode — the sharded batched wrapper has no per-node rng plumbing)."""
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 4, "extra_trees": True,
         "tree_learner": "data"}       # conftest mesh: 8 CPU devices
    before = global_metrics.counter("batched_path_fallbacks")
    ds = lgb.Dataset(X[:300], label=y[:300], params=p)
    bst = lgb.Booster(params=p, train_set=ds)
    assert bst._gbdt.parallel_mode == "data"
    assert bst._gbdt._use_batched_grower() is False
    assert bst._gbdt.metrics.counter("batched_path_fallbacks") == 1
    assert global_metrics.counter("batched_path_fallbacks") == before + 1
    # memoized: a second query must not double-count
    bst._gbdt._use_batched_grower()
    assert bst._gbdt.metrics.counter("batched_path_fallbacks") == 1


def test_forced_splits_pool_composes_no_fallback(tmp_path, synthetic_binary):
    """Forced splits COMPOSE with the bounded pool since round 6 (the
    batched forced phase derives evicted leaves' columns directly) — no
    hist_pool_fallbacks tally, pool slots engaged."""
    X, y = synthetic_binary
    forced = tmp_path / "forced.json"
    forced.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
    p = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
         "verbose": -1, "histogram_pool_size": 1e-4,
         "forcedsplits_filename": str(forced)}
    ds = lgb.Dataset(X[:300], label=y[:300], params=p)
    bst = lgb.Booster(params=p, train_set=ds)
    assert bst._gbdt.metrics.counter("hist_pool_fallbacks") == 0
    assert 0 < bst._gbdt.hp.hist_pool_slots < bst._gbdt.hp.num_leaves


def test_memory_snapshot_shape():
    snap = obs_memory.memory_snapshot()
    assert "host_rss_mb" in snap and "device_memory" in snap
    if snap["host_rss_mb"] is not None:        # Linux
        assert snap["host_rss_mb"] > 0
        assert snap["host_peak_rss_mb"] >= 0


def test_config_registers_observability_keys(tmp_path):
    cfg = lgb.Config({"trace_output": str(tmp_path / "t.json"),
                      "telemetry_output": str(tmp_path / "t.jsonl"),
                      "profile_dir": str(tmp_path / "prof")})
    assert cfg.trace_output.endswith("t.json")
    assert cfg.telemetry_output.endswith("t.jsonl")
    assert cfg.profile_dir.endswith("prof")


def test_cv_produces_one_trace_covering_all_folds(tmp_path,
                                                  synthetic_binary):
    """cv() opens ONE observability session the fold train() calls join:
    the exported trace carries every fold's train span instead of each
    fold overwriting the file."""
    X, y = synthetic_binary
    tp = str(tmp_path / "cv_trace.json")
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "metric": ["binary_logloss"], "trace_output": tp}
    lgb.cv(p, lgb.Dataset(X[:400], label=y[:400], params=p),
           num_boost_round=2, nfold=2, stratified=False)
    assert trace.active() is None
    with open(tp) as f:
        spans = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert sum(1 for e in spans if e["name"] == "train") == 2


def test_fused_replay_records_are_marked(tmp_path):
    """Telemetry records driven from a fused chunk's host replay carry
    fused_replay=true (iter_time_s there is replay cadence, not
    per-iteration device cost)."""
    rng = np.random.default_rng(2)
    X = rng.normal(size=(500, 5))
    y = (X[:, 0] > 0).astype(np.float64)
    jp = str(tmp_path / "fused_tele.jsonl")
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_split_batch": 3, "telemetry_output": jp}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=8)
    assert bst._gbdt.metrics.counter("fused_rounds") == 8
    with open(jp) as f:
        recs = [json.loads(ln) for ln in f.read().strip().splitlines()]
    assert len(recs) == 8
    assert all(r.get("fused_replay") for r in recs)


def test_unwritable_output_paths_never_take_training_down(synthetic_binary):
    """A typo'd trace/telemetry path degrades to a warning before round
    1 — it must not cost (or crash) the training run."""
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1,
         "trace_output": "/no/such/dir/trace.json",
         "telemetry_output": "/no/such/dir/tele.jsonl"}
    bst = lgb.train(p, lgb.Dataset(X[:300], label=y[:300], params=p),
                    num_boost_round=2)
    assert bst.num_trees() == 2
    assert trace.active() is None


def test_nested_trace_sessions_do_not_fight():
    """cv() folds train() inside an outer observed run: the inner start()
    must join (not steal or close) the outer recorder."""
    outer = trace.start()
    assert outer is not None
    inner = trace.start()
    assert inner is None
    trace.stop(inner)                 # no-op
    assert trace.active() is outer
    trace.stop(outer)
    assert trace.active() is None


@pytest.fixture(autouse=True)
def _restore_global_timer():
    yield
    global_timer.disable()
    global_timer.reset()


def test_telemetry_continuous_after_resume(tmp_path):
    """Regression (PR 9): a killed run leaves telemetry records for
    rounds PAST the checkpoint its successor resumes from; the resumed
    run must prune that stale tail so the file reads as ONE continuous
    per-iteration history — no duplicate or overlapping indices."""
    from lightgbm_tpu.robustness.faults import kill_training
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 5)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    tel = str(tmp_path / "tele.jsonl")
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "seed": 7, "deterministic": True, "verbosity": -1,
         "checkpoint_dir": str(tmp_path / "ck"), "checkpoint_interval": 3,
         "telemetry_output": tel}
    with pytest.raises(Exception):
        lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=12,
                  callbacks=[kill_training(at_iteration=7)])
    # the kill at iteration 7 post-dates the newest checkpoint (round 6):
    # iterations 6..7 in the file are stale relative to the resume point
    stale = [json.loads(ln)["iteration"] for ln in open(tel)]
    assert max(stale) >= 6
    bst = lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=12,
                    resume="auto")
    assert bst.num_trees() == 12
    iters = [json.loads(ln)["iteration"] for ln in open(tel)]
    assert iters == sorted(iters)                  # monotone
    assert len(iters) == len(set(iters))           # no duplicates
    assert iters == list(range(12))                # one continuous history


def test_telemetry_prune_keeps_unparseable_lines(tmp_path):
    from lightgbm_tpu.callback import _prune_stale_telemetry
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"iteration": 0}) + "\n")
        f.write("NOT JSON {{{\n")
        f.write(json.dumps({"iteration": 5}) + "\n")
        f.write(json.dumps({"no_iteration_key": True}) + "\n")
    assert _prune_stale_telemetry(path, cut=3) == 1
    lines = open(path).read().splitlines()
    assert len(lines) == 3
    assert lines[1] == "NOT JSON {{{"
    # records without an iteration index are kept (iteration -1 < cut)
    assert json.loads(lines[2]) == {"no_iteration_key": True}
