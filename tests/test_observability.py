"""One-pane-of-glass observability tests (obs/events.py, obs/merge.py,
tools/run_report.py — docs/OBSERVABILITY.md).

Covers the PR-10 acceptance surface: the structured event journal's
schema + declared-name discipline, cross-rank trace merging with
injected clock skew (monotonic, rank-0-aligned, Perfetto-valid), the
elastic kill drill narrated in journal AND trace, the serving metrics
snapshot, and the ``run_report`` CI gate's exit codes — plus off-by-default: no
configured outputs, no new files.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import events, merge, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ event journal
def test_event_journal_schema_and_tail(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with events.session(path, rank=3):
        events.emit_event("checkpoint_written", round_idx=2,
                          path="/tmp/x")
        events.emit_event("heartbeat_suspect", rank=1, age_s=0.5)
    rows = events.read_journal(path)
    assert [r["event"] for r in rows] == ["checkpoint_written",
                                          "heartbeat_suspect"]
    first = rows[0]
    for field in ("event", "severity", "rank", "round", "t_mono",
                  "unix_time", "payload"):
        assert field in first, field
    assert first["rank"] == 3 and first["round"] == 2
    assert first["payload"]["path"] == "/tmp/x"
    # explicit rank on emit overrides the journal default
    assert rows[1]["rank"] == 1
    # severity comes from the EVENTS declaration
    assert first["severity"] == events.EVENTS["checkpoint_written"][0]
    assert events.journal_tail(path, limit=1)[0]["event"] \
        == "heartbeat_suspect"


def test_undeclared_event_recorded_as_error(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with events.session(path):
        events.emit_event("not_a_declared_event", detail="x")
    rows = events.read_journal(path)
    assert rows and rows[0]["event"] == "not_a_declared_event"
    assert rows[0]["severity"] == "error"


def test_read_journal_skips_torn_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with events.session(path):
        events.emit_event("checkpoint_written", round_idx=0)
    with open(path, "a") as fh:
        fh.write('{"event": "torn')     # writer killed mid-append
    assert [r["event"] for r in events.read_journal(path)] \
        == ["checkpoint_written"]


def test_emit_without_session_is_a_noop(tmp_path):
    assert events.active() is None
    events.emit_event("checkpoint_written", round_idx=0)   # must not raise
    assert list(tmp_path.iterdir()) == []


def test_journal_counts_records(tmp_path):
    from lightgbm_tpu.obs.metrics import global_metrics
    before = global_metrics.snapshot()["counters"].get(
        "event_journal_records", 0)
    with events.session(str(tmp_path / "e.jsonl")):
        events.emit_event("checkpoint_written", round_idx=0)
    after = global_metrics.snapshot()["counters"]["event_journal_records"]
    assert after == before + 1


# --------------------------------------------------------------- trace merge
def _rank_trace(tmp_path, epoch, rank, anchor_ts, anchor_wall,
                offsets_us):
    """A per-rank trace file whose local clock origin and wall clock are
    both skewed; ``offsets_us`` are span starts relative to the anchor
    (the barrier), i.e. the cross-rank-comparable quantity."""
    evs = [{"name": "barrier_release", "ph": "i", "ts": anchor_ts,
            "pid": 1234 + rank, "tid": 0, "s": "t"}]
    for i, off in enumerate(offsets_us):
        evs.append({"name": f"round_{i}", "ph": "X",
                    "ts": anchor_ts + off, "dur": 500.0,
                    "pid": 1234 + rank, "tid": 0})
    path = merge.rank_file_path(str(tmp_path / "trace.json"), epoch, rank)
    with open(path, "w") as fh:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms",
                   "lgbtpu": {"rank": rank, "epoch": epoch,
                              "wall_t0": anchor_wall - 1.0,
                              "anchor_wall": anchor_wall,
                              "anchor_ts_us": anchor_ts}}, fh)
    return path


def test_merge_aligns_skewed_rank_clocks(tmp_path):
    base = str(tmp_path / "trace.json")
    # three ranks: wildly different monotonic origins AND wall clocks
    # (rank 2's wall is an hour off) — within one epoch only the
    # barrier anchor may matter
    offsets = [1000.0, 2000.0, 3000.0]
    _rank_trace(tmp_path, 0, 0, anchor_ts=500.0, anchor_wall=100.0,
                offsets_us=offsets)
    _rank_trace(tmp_path, 0, 1, anchor_ts=9.9e6, anchor_wall=100.02,
                offsets_us=offsets)
    _rank_trace(tmp_path, 0, 2, anchor_ts=123.0, anchor_wall=3700.0,
                offsets_us=offsets)
    paths = merge.find_rank_files(base)
    assert len(paths) == 3
    doc = merge.merge_rank_traces(paths, out_path=base)
    # written file is valid JSON and identical to the return value
    with open(base) as fh:
        assert json.load(fh) == json.loads(json.dumps(doc))
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    # monotonic, rank-0-aligned timeline
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    assert min(ts) >= 0.0
    # one track per rank
    assert {e["pid"] for e in evs} == {0, 1, 2}
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert {m["args"]["name"] for m in meta} \
        == {"rank 0", "rank 1", "rank 2"}
    # anchor alignment: every rank's round_i starts at the SAME merged
    # ts — the monotonic-origin and wall skews cancelled exactly
    for i in range(len(offsets)):
        starts = {e["ts"] for e in evs
                  if e.get("name") == f"round_{i}" and e.get("ph") == "X"}
        assert len(starts) == 1, (i, starts)
    # synthetic epoch scope on every track
    scopes = [e for e in evs if e.get("name") == "elastic_epoch"]
    assert {e["pid"] for e in scopes} == {0, 1, 2}
    assert doc["lgbtpu"]["merged"] is True
    assert doc["lgbtpu"]["ranks"] == [0, 1, 2]
    # Chrome-trace validity: required fields on every span
    for e in evs:
        if e.get("ph") == "X":
            for field in ("name", "ts", "dur", "pid", "tid"):
                assert field in e, e


def test_merge_chains_epochs_and_overlays_journal(tmp_path):
    base = str(tmp_path / "trace.json")
    _rank_trace(tmp_path, 0, 0, anchor_ts=100.0, anchor_wall=50.0,
                offsets_us=[1000.0])
    _rank_trace(tmp_path, 0, 1, anchor_ts=7.0e6, anchor_wall=50.01,
                offsets_us=[1000.0])
    # epoch 1 (post-reshape): barrier 2 wall-seconds later
    _rank_trace(tmp_path, 1, 0, anchor_ts=42.0, anchor_wall=52.0,
                offsets_us=[1000.0])
    journal = str(tmp_path / "events.jsonl")
    with open(journal, "w") as fh:
        fh.write(json.dumps({"event": "worker_evicted",
                             "severity": "warning", "rank": None,
                             "round": 3, "unix_time": 51.5,
                             "payload": {"ranks": [1]}}) + "\n")
        fh.write(json.dumps({"event": "barrier_release",
                             "severity": "info", "rank": 1,
                             "round": None, "unix_time": 50.01,
                             "payload": {}}) + "\n")
    doc = merge.merge_rank_traces(merge.find_rank_files(base),
                                  events_paths=[journal])
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    assert doc["lgbtpu"]["epochs"] == [0, 1]
    # epoch-1 events sit ~2 wall-seconds after epoch 0's anchor
    e1 = [e for e in evs if e.get("name") == "elastic_epoch"
          and e.get("args", {}).get("epoch") == 1]
    assert e1 and e1[0]["ts"] >= 1.9e6
    # journal overlay: rankless row -> coordinator track, ranked row ->
    # that rank's track, both between the epochs' extents
    inst = {e["name"]: e for e in evs if e.get("ph") == "i"
            and e.get("s") == "t" and e["name"] != "barrier_release"}
    assert inst["worker_evicted"]["pid"] == -1
    coord_meta = [m for m in doc["traceEvents"] if m.get("ph") == "M"
                  and m.get("pid") == -1]
    assert coord_meta and coord_meta[0]["args"]["name"] == "coordinator"
    evict_ts = inst["worker_evicted"]["ts"]
    assert 1.0e6 < evict_ts < 2.1e6        # 1.5 wall-s after epoch-0 anchor


def test_merge_rejects_non_trace(tmp_path):
    bad = tmp_path / "x.e0.r0.json"
    bad.write_text("{\"foo\": 1}")
    with pytest.raises(ValueError):
        merge.merge_rank_traces([str(bad)])


# ----------------------------------------------------------- elastic drill
@pytest.fixture(scope="module")
def elastic_kill_run(tmp_path_factory):
    """ONE in-process elastic kill drill with journal + trace enabled,
    shared by the ordering/trace/report assertions."""
    from lightgbm_tpu.robustness.elastic import ElasticSession
    from lightgbm_tpu.robustness.faults import kill_worker
    td = tmp_path_factory.mktemp("elastic_obs")
    ev_path = str(td / "events.jsonl")
    tr_path = str(td / "trace.json")
    rng = np.random.RandomState(0)
    X = rng.randint(0, 8, size=(200, 5)).astype(np.float64)
    y = (X[:, 0] + X[:, 1] > 7).astype(np.float64)
    params = dict(objective="binary", num_leaves=7, learning_rate=0.5,
                  min_data_in_leaf=5, deterministic=True, seed=7,
                  use_quantized_grad=True, stochastic_rounding=False,
                  tree_learner="data", checkpoint_interval=2,
                  heartbeat_interval_s=0.2, heartbeat_timeout_s=1.0,
                  elastic="on", verbosity=-1,
                  event_output=ev_path, trace_output=tr_path)
    session = ElasticSession(params, X, y, num_boost_round=8,
                             n_workers=4, workdir=str(td / "work"),
                             faults=[kill_worker(2, at_round=4)])
    booster = session.train()
    return booster, ev_path, tr_path, session.report.to_dict()


def test_kill_drill_journal_order(elastic_kill_run):
    _, ev_path, _, rep = elastic_kill_run
    assert len(rep["evictions"]) == 1
    seq = [r["event"] for r in events.read_journal(ev_path)]
    want = ["heartbeat_dead", "worker_evicted", "mesh_reshape",
            "training_resumed"]
    idx = [seq.index(w) for w in want]
    assert idx == sorted(idx), seq
    # resume continues from a checkpoint — the engine journals it too
    assert "checkpoint_resume" in seq and "checkpoint_written" in seq


def test_kill_drill_trace_narrates_recovery(elastic_kill_run):
    _, _, tr_path, _ = elastic_kill_run
    with open(tr_path) as fh:
        doc = json.load(fh)
    evs = doc["traceEvents"]
    instants = {e["name"] for e in evs if e.get("ph") == "i"}
    assert {"worker_evicted", "mesh_reshape",
            "training_resumed"} <= instants
    epochs = [e for e in evs if e.get("ph") == "X"
              and e.get("name") == "elastic_epoch"]
    assert len(epochs) >= 2          # pre-kill mesh + survivor mesh
    meshes = {e["args"]["mesh"] for e in epochs}
    assert {4, 3} <= meshes


def test_run_report_joins_kill_drill_artifacts(elastic_kill_run, capsys):
    _, ev_path, tr_path, _ = elastic_kill_run
    rr = _load_tool("run_report")
    rc = rr.main(["--trace", tr_path, "--events", ev_path,
                  "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["tool"] == "run_report"
    assert doc["findings"] == []
    assert doc["events"]["by_name"]["worker_evicted"] == 1
    assert any(t["event"] == "training_resumed"
               for t in doc["events"]["timeline"])


# ------------------------------------------------------------- run_report
def test_run_report_quick_gate_exit_codes(tmp_path, capsys):
    rr = _load_tool("run_report")
    trace_p = tmp_path / "t.json"
    trace_p.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "train", "ts": 0, "dur": 10.0,
         "pid": 0, "tid": 0}]}))
    ev_p = tmp_path / "e.jsonl"
    ev_p.write_text(json.dumps({"event": "checkpoint_written",
                                "severity": "info",
                                "unix_time": 1.0}) + "\n")
    tele_p = tmp_path / "tele.jsonl"
    tele_p.write_text(json.dumps({"iteration": 0, "counters": {
        "round_compile_misses": 1}}) + "\n")
    rc = rr.main(["--quick", "--trace", str(trace_p), "--events",
                  str(ev_p), "--telemetry", str(tele_p)])
    capsys.readouterr()
    assert rc == 0
    # empty journal -> findings
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    rc = rr.main(["--quick", "--events", str(empty)])
    capsys.readouterr()
    assert rc == 1
    # unusable trace -> error
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    rc = rr.main(["--quick", "--trace", str(bad)])
    capsys.readouterr()
    assert rc == 2
    # no artifacts at all -> error
    rc = rr.main(["--quick"])
    capsys.readouterr()
    assert rc == 2


def test_run_report_full_join_payload(tmp_path, capsys):
    rr = _load_tool("run_report")
    tele_p = tmp_path / "tele.jsonl"
    with open(tele_p, "w") as fh:
        fh.write(json.dumps({"iteration": 0, "counters": {
            "round_compile_misses": 2}}) + "\n")
        fh.write(json.dumps({"iteration": 3, "counters": {
            "round_compile_misses": 2, "round_compile_hits": 5},
            "gauges": {"rank_pad_rows": 12}}) + "\n")
    rc = rr.main(["--telemetry", str(tele_p), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    tel = doc["telemetry"]
    assert tel["rows"] == 2
    assert tel["first_round"] == 0 and tel["last_round"] == 3
    assert tel["compile"]["round_compile_hits"] == 5
    assert tel["rank"]["rank_pad_rows"] == 12


# ----------------------------------------------------------- trace_report
def test_trace_report_merged_and_events_overlay(tmp_path, capsys):
    tr = _load_tool("trace_report")
    base = str(tmp_path / "trace.json")
    _rank_trace(tmp_path, 0, 0, anchor_ts=0.0, anchor_wall=10.0,
                offsets_us=[1000.0])
    _rank_trace(tmp_path, 0, 1, anchor_ts=5.0e6, anchor_wall=10.0,
                offsets_us=[1000.0])
    merge.merge_rank_traces(merge.find_rank_files(base), out_path=base)
    journal = tmp_path / "events.jsonl"
    journal.write_text(json.dumps({"event": "mesh_reshape",
                                   "severity": "warning", "rank": None,
                                   "round": 2, "unix_time": 11.0,
                                   "payload": {}}) + "\n")
    rc = tr.main([base, "--events", str(journal), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["merged"]["ranks"] == [0, 1]
    assert {r["rank"] for r in doc["per_rank"]} == {0, 1}
    assert doc["events"]["by_name"] == {"mesh_reshape": 1}
    # unreadable --events file is the error exit, like an unreadable trace
    rc = tr.main([base, "--events", str(tmp_path / "missing.jsonl")])
    capsys.readouterr()
    assert rc == 2


# ------------------------------------------------------------ off by default
def test_event_journal_off_by_default(tmp_path, synthetic_binary, capsys):
    X, y = synthetic_binary
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        p = {"objective": "binary", "num_leaves": 7,
             "min_data_in_leaf": 5, "verbose": -1}
        lgb.train(p, lgb.Dataset(X[:256], label=y[:256], params=p),
                  num_boost_round=2)
    finally:
        os.chdir(cwd)
    assert events.active() is None
    assert list(tmp_path.iterdir()) == []     # zero new files


def test_event_output_param_writes_journal(tmp_path, synthetic_binary):
    X, y = synthetic_binary
    path = str(tmp_path / "events.jsonl")
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "checkpoint_dir": str(tmp_path / "ckpt"),
         "checkpoint_interval": 1, "event_output": path}
    lgb.train(p, lgb.Dataset(X[:256], label=y[:256], params=p),
              num_boost_round=2)
    assert events.active() is None            # session closed after train
    names = [r["event"] for r in events.read_journal(path)]
    assert "checkpoint_written" in names


# ------------------------------------------------------------- serving tier
def test_serving_metrics_snapshot_and_prometheus(tmp_path,
                                                 synthetic_binary):
    from lightgbm_tpu.serving.server import PredictionServer
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1}
    bst = lgb.train(p, lgb.Dataset(X[:256], label=y[:256], params=p),
                    num_boost_round=2)
    tele = str(tmp_path / "serve.jsonl")
    srv = PredictionServer({"serving_buckets": [8, 64],
                            "serving_telemetry_output": tele})
    srv.publish("m", booster=bst, warmup=False)
    for _ in range(3):
        srv.predict("m", X[:10])
    snap = srv.metrics_snapshot()
    assert snap["requests_in_window"] == 3
    lat = snap["latency_ms"]
    assert 0.0 <= lat["p50"] <= lat["p95"] <= lat["p99"]
    assert snap["rows_per_s"] > 0.0
    assert snap["inflight"] == 0 and snap["queue_depth"] == 0
    assert snap["models"][0]["name"] == "m"
    assert snap["counters"]["serve_requests"] >= 3
    text = srv.prometheus_text()
    assert "# TYPE lgbtpu_serve_latency_ms gauge" in text
    assert 'lgbtpu_serve_latency_ms{quantile="0.5"}' in text
    assert 'lgbtpu_serve_model_version{model="m"} 1.0' in text
    assert "lgbtpu_serve_inflight 0.0" in text
    assert "# TYPE lgbtpu_serve_requests counter" in text
    srv.close()
    rows = [json.loads(line) for line in open(tele)]
    assert rows and all("inflight" in r and "queue_depth" in r
                        for r in rows)


def test_serving_hot_swap_and_rejection_events(tmp_path, synthetic_binary):
    from lightgbm_tpu.serving.server import (PredictionServer,
                                             ServerOverloaded)
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1}
    bst = lgb.train(p, lgb.Dataset(X[:256], label=y[:256], params=p),
                    num_boost_round=2)
    path = str(tmp_path / "events.jsonl")
    with events.session(path):
        srv = PredictionServer({"serving_buckets": [8, 64]})
        srv.publish("m", booster=bst, warmup=False)
        srv.publish("m", booster=bst, warmup=False)    # hot swap
        with pytest.raises(ServerOverloaded):
            srv.predict("m", X[:8], deadline_ms=0.0)   # dead on arrival
        srv.close()
    names = [r["event"] for r in events.read_journal(path)]
    assert "serve_hot_swap" in names
    assert "serve_overload_rejected" in names
