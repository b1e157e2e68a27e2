"""Watchtower tests (obs/timeseries.py, obs/slo.py, obs/anomaly.py,
obs/prom.py, tools/obs_top.py — docs/OBSERVABILITY.md "watchtower").

Covers the PR-11 acceptance surface: deterministic rollup-window math
(gap synthesis, ring eviction, stride-doubling sample decimation, JSONL
persistence), the three JSONL feeders, burn-rate breach -> recover
sequencing through a REAL event journal, the ``run_report --quick``
exit-1 gate on an unrecovered breach, baseline-relative anomaly
detection (unit + an in-process training drill with an injected
round-time spike), the shared Prometheus exporter, ``obs_top --once``
in a jax-poisoned subprocess, ``bench_compare --trend`` exit codes —
plus all-off-by-default: no watchtower object, no rollup file, zero new
config behavior unless asked for.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import events
from lightgbm_tpu.obs.anomaly import AnomalyDetector, robust_z
from lightgbm_tpu.obs.slo import SLOS, SloEvaluator, parse_slo_config
from lightgbm_tpu.obs.timeseries import (Rollup, default_rollup_path,
                                         feed_journal_record,
                                         feed_serving_row,
                                         feed_telemetry_row)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ rollup ring
def test_rollup_window_math():
    r = Rollup(window_s=1.0)
    r.observe_counter("c", 5.0, t=100.2)
    r.observe_counter("c", 12.0, t=100.8)
    r.observe_gauge("g", 3.0, t=100.3)
    r.observe_gauge("g", 1.0, t=100.9)
    for v in range(1, 11):
        r.observe_sample("s", float(v), t=100.5)
    assert r.completed() == []                 # window still open
    r.observe_counter("c", 12.0, t=101.5)      # rolls the window
    (w,) = r.completed()
    assert (w["t_start"], w["t_end"], w["window_s"]) == (100.2, 101.2, 1.0)
    assert w["counters"]["c"] == {"delta": 12.0, "rate": 12.0}
    assert w["gauges"]["g"] == {"last": 1.0, "min": 1.0, "max": 3.0,
                                "n": 2}
    s = w["samples"]["s"]
    assert s["count"] == 10 and s["max"] == 10.0
    assert s["p50"] == 5.0 and s["p95"] == 10.0 and s["p99"] == 10.0
    # the new window saw the same cumulative value: delta 0, but the
    # counter is still marked observed ("0 misses" != "no data")
    assert r.current()["counters"]["c"] == {"delta": 0.0, "rate": 0.0}
    # everything a window carries is JSON-serializable
    json.dumps(w)


def test_rollup_gap_synthesis_and_ring_eviction():
    r = Rollup(window_s=1.0, max_windows=4)
    r.observe_delta("x", 1.0, t=0.0)
    r.observe_delta("x", 1.0, t=10.0)          # 9 empty windows in between
    r.flush()
    ws = r.completed()
    assert len(ws) == 4                        # ring bound held
    for a, b in zip(ws, ws[1:]):               # contiguous for burn-rate
        assert b["t_start"] == a["t_end"]
    assert ws[-1]["t_start"] == 10.0
    assert ws[-1]["counters"]["x"]["delta"] == 1.0
    assert all(not w["counters"] for w in ws[:-1])   # synthesized empty


def test_rollup_sample_decimation_bounded_and_deterministic():
    def build():
        r = Rollup(window_s=10.0)
        for i in range(2000):
            r.observe_sample("lat", float(i % 100), t=50.0)
        r.flush()
        return r.completed()[0]

    w = build()
    row = w["samples"]["lat"]
    assert row["count"] == 2000                # true count survives
    assert 90.0 <= row["max"] <= 99.0          # decimated, not wild
    assert 40.0 <= row["p50"] <= 60.0
    assert build() == w                        # replay is bit-identical


def test_rollup_persistence_and_counter_hook(tmp_path):
    out = tmp_path / "roll.jsonl"
    bumps = []
    r = Rollup(window_s=1.0, out_path=str(out),
               count=lambda n, v=1: bumps.append((n, v)))
    r.observe_delta("x", 1.0, t=0.0)
    r.observe_delta("x", 1.0, t=1.5)
    r.close()
    lines = [json.loads(line) for line in open(out)]
    assert len(lines) == 2
    assert lines[0]["counters"]["x"]["delta"] == 1.0
    assert bumps == [("rollup_windows_closed", 1)] * 2
    assert default_rollup_path("/a/tele.jsonl") == "/a/tele.rollup.jsonl"
    assert default_rollup_path("tele") == "tele.rollup.jsonl"


def test_feeders_map_the_three_row_shapes():
    r = Rollup(window_s=60.0)
    t0 = 1000.0
    feed_telemetry_row(r, {
        "unix_time": t0, "iteration": 3, "iter_time_s": 0.2,
        "counters": {"iterations": 3, "nan_guard_trips": 0},
        "gauges": {"overlap_efficiency": 0.5},
        "evals": {"v0.binary_logloss": 0.4}, "host_rss_mb": 100.0})
    feed_serving_row(r, {
        "ts": t0 + 1, "latency_s": 0.01, "rows": 8, "pad_rows": 2,
        "inflight": 1, "queue_depth": 0})
    feed_journal_record(r, {"event": "checkpoint_written",
                            "unix_time": t0 + 2})
    r.flush()
    (w,) = r.completed()
    assert w["samples"]["round_s"]["count"] == 1
    assert w["samples"]["latency_ms"]["p99"] == 10.0
    assert w["counters"]["iterations"]["delta"] == 3.0
    assert w["counters"]["serve_requests"]["delta"] == 1.0
    assert w["counters"]["serve_pad_waste_rows"]["delta"] == 2.0
    assert w["gauges"]["overlap_efficiency"]["last"] == 0.5
    assert w["gauges"]["eval.v0.binary_logloss"]["last"] == 0.4
    assert w["gauges"]["serve_inflight"]["last"] == 1.0
    assert w["gauges"]["host_rss_mb"]["last"] == 100.0
    assert w["events"]["checkpoint_written"] == 1


# ----------------------------------------------------------- slo_config
def test_parse_slo_config_forms():
    assert parse_slo_config("") == {}
    assert parse_slo_config("off") == {}
    assert parse_slo_config(None) == {}
    assert parse_slo_config("on") == {n: float(SLOS[n][2]) for n in SLOS}
    got = parse_slo_config("serving_p99_ms:75, heartbeat_staleness_s")
    assert got == {"serving_p99_ms": 75.0,
                   "heartbeat_staleness_s": float(
                       SLOS["heartbeat_staleness_s"][2])}
    with pytest.raises(ValueError, match="unknown SLO"):
        parse_slo_config("no_such_slo")
    with pytest.raises(ValueError, match="not a number"):
        parse_slo_config("serving_p99_ms:fast")


def _win(t_end, p99=None, window_s=1.0):
    w = {"t_start": t_end - window_s, "t_end": float(t_end),
         "window_s": window_s, "counters": {}, "gauges": {},
         "samples": {}, "events": {}}
    if p99 is not None:
        w["samples"]["latency_ms"] = {"count": 10, "max": p99,
                                      "p50": p99, "p95": p99, "p99": p99}
    return w


# ------------------------------------------------- burn-rate sequencing
def test_burn_rate_breach_then_recover_through_real_journal(tmp_path):
    """The acceptance sequence: two violating windows page exactly once
    (a single noisy window never does), two clean windows recover — and
    both transitions land as declared records in a REAL EventJournal."""
    path = str(tmp_path / "events.jsonl")
    bumps = []
    with events.session(path):
        ev = SloEvaluator({"serving_p99_ms": 50.0},
                          emit=events.emit_event,
                          count=lambda n, v=1: bumps.append(n))
        assert ev.watch_slo("serving_p99_ms") is True
        # a name the config did not enable registers as a no-op
        assert ev.watch_slo("heartbeat_staleness_s") is False
        assert ev.watched() == ["serving_p99_ms"]

        assert ev.evaluate([_win(1, 80.0)]) == []     # 1 violation: quiet
        t = ev.evaluate([_win(1, 80.0), _win(2, 90.0)])
        assert [x["state"] for x in t] == ["breach"]  # cursor skipped w1
        assert t[0]["slo"] == "serving_p99_ms" and t[0]["value"] == 90.0
        assert ev.breached() == ["serving_p99_ms"]
        assert ev.state()["serving_p99_ms"]["ok"] is False

        assert ev.evaluate([_win(3, 120.0)]) == []    # still burning
        assert ev.evaluate([_win(4, 10.0)]) == []     # clean streak 1
        t = ev.evaluate([_win(5, 12.0)])              # clean streak 2
        assert [x["state"] for x in t] == ["recovered"]
        assert ev.breached() == []
        # re-feeding already-consumed windows is a no-op (t_end cursor)
        assert ev.evaluate([_win(2, 90.0), _win(5, 12.0)]) == []
    names = [r["event"] for r in events.read_journal(path)]
    assert names == ["slo_breach", "slo_recovered"]
    recs = events.read_journal(path)
    assert recs[0]["severity"] == "error"
    assert recs[0]["payload"]["slo"] == "serving_p99_ms"
    assert recs[0]["payload"]["budget"] == 50.0
    assert bumps == ["slo_breaches", "slo_recoveries"]


def test_no_data_windows_are_neutral_for_breach():
    ev = SloEvaluator({"serving_p99_ms": 50.0})
    ev.watch_slo("serving_p99_ms")
    assert ev.evaluate([_win(i) for i in range(1, 10)]) == []
    assert ev.breached() == []
    st = ev.state()["serving_p99_ms"]
    assert st["violations"] == 0 and st["last_value"] is None


def test_watch_slo_rejects_undeclared_name():
    ev = SloEvaluator("on")
    with pytest.raises(ValueError, match="not declared"):
        ev.watch_slo("made_up_slo")


# --------------------------------------------------- run_report CI gate
def test_run_report_quick_gate_on_unrecovered_breach(tmp_path, capsys):
    run_report = _load_tool("run_report")
    bad = str(tmp_path / "bad.jsonl")
    with events.session(bad):
        ev = SloEvaluator({"nan_guard_trip_rate": 0.0},
                          emit=events.emit_event)
        ev.watch_slo("nan_guard_trip_rate")

        def w(t_end, trips):
            base = _win(t_end)
            base["counters"] = {"iterations": {"delta": 4, "rate": 4},
                                "nan_guard_trips": {"delta": trips,
                                                    "rate": trips}}
            return base

        ev.evaluate([w(1, 2), w(2, 2)])       # breach, never recovers
    assert run_report.main(["--events", bad, "--quick"]) == 1
    out = capsys.readouterr().out
    assert "unrecovered slo_breach: nan_guard_trip_rate" in out

    ok = str(tmp_path / "ok.jsonl")
    with events.session(ok):
        ev = SloEvaluator({"nan_guard_trip_rate": 0.0},
                          emit=events.emit_event)
        ev.watch_slo("nan_guard_trip_rate")
        ev.evaluate([w(1, 2), w(2, 2), w(3, 0), w(4, 0)])
    assert run_report.main(["--events", ok, "--quick"]) == 0
    out = capsys.readouterr().out
    assert "healthy" in out


# ------------------------------------------------------------- anomalies
def test_robust_z_basics():
    assert robust_z(1.0, [1.0] * 10) == 0.0
    assert robust_z(10.0, [1.0] * 10) > 100.0


def test_anomaly_round_time_spike_fires_once_per_cooldown():
    counts = []
    det = AnomalyDetector(count=lambda n, v=1: counts.append(n))
    found = []
    for i in range(12):
        found += det.observe_round(i, round_s=0.1)
    assert found == []                         # steady baseline: quiet
    spike = det.observe_round(12, round_s=5.0)
    assert [f["kind"] for f in spike] == ["round_time_spike"]
    assert spike[0]["round_idx"] == 12
    assert counts.count("anomalies_detected") == 1
    # cooldown: an immediate second spike does not re-page
    assert det.observe_round(13, round_s=5.0) == []
    assert det.findings_total == 1


def test_anomaly_eval_divergence_and_plateau():
    det = AnomalyDetector(divergence_rounds=3, plateau_rounds=5,
                          plateau_tol=1e-4)
    found = []
    # binary_logloss (higher_better=False) worsening every round
    for i, v in enumerate([0.5, 0.6, 0.7, 0.8, 0.9]):
        found += det.observe_round(i, evals={"v0.loss": (v, False)})
    kinds = [f["kind"] for f in found]
    assert "eval_divergence" in kinds

    det2 = AnomalyDetector(plateau_rounds=4, plateau_tol=1e-4)
    found2 = []
    for i in range(8):
        found2 += det2.observe_round(i, evals={"v0.loss": (0.5, False)})
    assert [f["kind"] for f in found2] == ["eval_plateau"]  # one-shot


# ------------------------------------------------- in-process drill
def test_training_drill_round_time_spike(tmp_path, synthetic_binary):
    """The scripted training-side drill: a sleep injected into one
    boosting round must surface as ``anomaly_detected`` in the journal,
    a nonzero ``anomalies_detected`` counter, and a rollup JSONL next to
    ``telemetry_output`` — with zero effect on the trained model."""
    X, y = synthetic_binary
    tele = str(tmp_path / "tele.jsonl")
    evp = str(tmp_path / "events.jsonl")
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "anomaly_detection": "on",
         "rollup_window_s": 0.2, "telemetry_output": tele,
         "event_output": evp}

    def _spike(env):
        if env.iteration == 16:
            time.sleep(0.5)
    _spike.order = 50         # lands before the watchtower callback (55)

    bst = lgb.train(p, lgb.Dataset(X[:256], label=y[:256], params=p),
                    num_boost_round=24, callbacks=[_spike])
    counters = bst.telemetry()["counters"]
    assert counters["anomalies_detected"] >= 1
    assert counters["rollup_windows_closed"] >= 1
    recs = events.read_journal(evp)
    spikes = [r for r in recs if r["event"] == "anomaly_detected"
              and r["payload"].get("kind") == "round_time_spike"]
    assert spikes, [r["event"] for r in recs]
    roll = default_rollup_path(tele)
    assert os.path.exists(roll)
    rows = [json.loads(line) for line in open(roll)]
    assert rows
    assert any("round_s" in r.get("samples", {}) for r in rows)
    # the exporter renders without a serving tier
    text = bst.prometheus_text()
    assert "# TYPE lgbtpu_iterations counter" in text


def test_all_off_default_builds_nothing(tmp_path, synthetic_binary):
    X, y = synthetic_binary
    tele = str(tmp_path / "tele.jsonl")
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "telemetry_output": tele}
    bst = lgb.train(p, lgb.Dataset(X[:256], label=y[:256], params=p),
                    num_boost_round=2)
    assert bst._gbdt.watchtower is None
    assert not os.path.exists(default_rollup_path(tele))
    counters = bst.telemetry()["counters"]
    assert counters.get("rollup_windows_closed", 0) == 0
    assert counters.get("anomalies_detected", 0) == 0


def test_config_rejects_bad_watchtower_keys(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X[:64], label=y[:64])
    base = {"objective": "binary", "num_leaves": 7,
            "min_data_in_leaf": 5, "verbose": -1}
    with pytest.raises(lgb.LightGBMError, match="slo_config"):
        lgb.train(dict(base, slo_config="no_such_slo"), ds,
                  num_boost_round=1)
    with pytest.raises(lgb.LightGBMError, match="anomaly_detection"):
        lgb.train(dict(base, anomaly_detection="maybe"), ds,
                  num_boost_round=1)


# ------------------------------------------------------------ prometheus
def test_prometheus_training_text_golden():
    from lightgbm_tpu.obs import prom
    text = prom.training_text(
        {"iterations": 5}, {"overlap_efficiency": 0.5},
        {"round_s": 0.25},
        {"serving_p99_ms": {"ok": True, "budget": 50.0,
                            "direction": "max", "last_value": 12.0,
                            "violations": 0, "history_windows": 3,
                            "transitions": 0}})
    for line in ("# TYPE lgbtpu_iterations counter",
                 "lgbtpu_iterations 5.0",
                 "# TYPE lgbtpu_overlap_efficiency gauge",
                 "lgbtpu_overlap_efficiency 0.5",
                 "lgbtpu_rollup_round_s 0.25",
                 'lgbtpu_slo_ok{name="serving_p99_ms"} 1.0',
                 'lgbtpu_slo_value{name="serving_p99_ms"} 12.0',
                 'lgbtpu_slo_budget{name="serving_p99_ms"} 50.0'):
        assert line in text, line
    assert text.endswith("\n")
    # None renders as a Prometheus NaN, never a crash
    assert prom.format_value(None) == "NaN"


def test_serving_slo_state_in_snapshot_and_prometheus(tmp_path,
                                                      synthetic_binary):
    from lightgbm_tpu.serving.server import PredictionServer
    X, y = synthetic_binary
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1}
    bst = lgb.train(p, lgb.Dataset(X[:256], label=y[:256], params=p),
                    num_boost_round=2)
    srv = PredictionServer({"serving_buckets": [8, 64],
                            "slo_config": "serving_p99_ms:10000"})
    try:
        srv.publish("m", booster=bst, warmup=False)
        for _ in range(3):
            srv.predict("m", X[:10])
        snap = srv.metrics_snapshot()
        assert "serving_p99_ms" in snap["slo"]
        assert snap["slo"]["serving_p99_ms"]["ok"] is True
        text = srv.prometheus_text()
        assert 'lgbtpu_slo_ok{name="serving_p99_ms"}' in text
    finally:
        srv.close()


# ---------------------------------------------------- obs_top dashboard
def _obs_top_subprocess(args):
    """Run tools/obs_top.py main() with jax+numpy POISONED: importing
    either would crash, proving the dashboard is stdlib-only."""
    script = (
        "import sys\n"
        "sys.modules['jax'] = None\n"
        "sys.modules['numpy'] = None\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'tools')!r})\n"
        "import obs_top\n"
        f"rc = obs_top.main({args!r})\n"
        "sys.exit(rc)\n")
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=60,
                          env={**os.environ, "PYTHONPATH": ""})


def _dashboard_fixture(tmp_path, latency_s):
    t0 = time.time() - 30.0
    tele = str(tmp_path / "tele.jsonl")
    with open(tele, "w") as fh:
        for i in range(4):
            fh.write(json.dumps({
                "run": "drill", "iteration": i, "unix_time": t0 + i * 0.4,
                "iter_time_s": 0.05,
                "counters": {"iterations": i + 1},
                "gauges": {"overlap_efficiency": 0.9},
                "evals": {"v0.binary_logloss": 0.5 - 0.01 * i}}) + "\n")
    srv = str(tmp_path / "serve.jsonl")
    with open(srv, "w") as fh:
        for i in range(6):
            fh.write(json.dumps({
                "ts": t0 + i * 0.5, "model": "m", "version": 1,
                "rows": 8, "buckets": 8, "pad_rows": 0,
                "latency_s": latency_s, "inflight": 1,
                "queue_depth": 0}) + "\n")
    evp = str(tmp_path / "events.jsonl")
    with open(evp, "w") as fh:
        fh.write(json.dumps({"event": "checkpoint_written",
                             "severity": "info", "rank": 0, "round": 1,
                             "unix_time": t0 + 1.0, "payload": {}}) + "\n")
    return tele, srv, evp


def test_obs_top_once_clean_view(tmp_path):
    tele, srv, evp = _dashboard_fixture(tmp_path, latency_s=0.001)
    p = _obs_top_subprocess(["--telemetry", tele, "--serving", srv,
                             "--events", evp, "--window", "1", "--once"])
    assert p.returncode == 0, p.stdout + p.stderr
    for pane in ("TRAINING", "SERVING", "SLO", "EVENTS"):
        assert pane in p.stdout, p.stdout
    assert "checkpoint_written" in p.stdout
    assert "BREACHED" not in p.stdout


def test_obs_top_once_breach_exit_and_html(tmp_path):
    # 200 ms p99 against the 50 ms default budget across >= 2 windows
    tele, srv, evp = _dashboard_fixture(tmp_path, latency_s=0.2)
    html = str(tmp_path / "top.html")
    p = _obs_top_subprocess(["--serving", srv, "--window", "1",
                             "--once", "--html", html])
    assert p.returncode == 1, p.stdout + p.stderr
    assert "BREACHED" in p.stdout
    assert "serving_p99_ms" in p.stdout
    doc = open(html, encoding="utf-8").read()
    assert "watchtower" in doc and "serving_p99_ms" in doc


def test_obs_top_exit_codes_on_missing_inputs(tmp_path):
    p = _obs_top_subprocess(["--once"])
    assert p.returncode == 2
    p = _obs_top_subprocess(["--telemetry",
                             str(tmp_path / "nope.jsonl"), "--once"])
    assert p.returncode == 2


def test_obs_top_follows_rank_sibling_files(tmp_path):
    tele, _, _ = _dashboard_fixture(tmp_path, latency_s=0.001)
    t0 = time.time() - 30.0
    sibling = str(tmp_path / "tele.e0.r1.jsonl")
    with open(sibling, "w") as fh:
        fh.write(json.dumps({"run": "drill", "iteration": 9,
                             "unix_time": t0 + 2.0, "iter_time_s": 0.05,
                             "counters": {}}) + "\n")
    p = _obs_top_subprocess(["--telemetry", tele, "--window", "1",
                             "--once"])
    assert p.returncode == 0, p.stdout + p.stderr
    assert "round=9" in p.stdout, p.stdout


# -------------------------------------------------- bench_compare trend
def _bench_capture(path, vs_baseline, quality="ok"):
    payload = {"metric": "l2", "platform": "cpu", "quality": quality,
               "vs_baseline": vs_baseline}
    if quality == "noisy":
        payload["rejected_value"] = vs_baseline
    with open(path, "w") as fh:
        json.dump({"parsed": payload}, fh)


def test_bench_compare_trend_exit_codes(tmp_path, capsys):
    bench_compare = _load_tool("bench_compare")
    d = tmp_path / "bench"
    d.mkdir()
    _bench_capture(str(d / "BENCH_r1.json"), 1.0)
    _bench_capture(str(d / "BENCH_r2.json"), 1.1)
    _bench_capture(str(d / "BENCH_r3.json"), 0.9)     # -18%: regression
    _bench_capture(str(d / "BENCH_r4.json"), 1.2, quality="noisy")
    assert bench_compare.main(["--trend", str(d)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "unusable" in out
    # same set, tolerant threshold: trajectory renders, exit clean
    assert bench_compare.main(["--trend", str(d),
                               "--threshold", "0.5"]) == 0
    capsys.readouterr()
    # nothing usable -> error exit
    only_noisy = tmp_path / "noisy"
    only_noisy.mkdir()
    _bench_capture(str(only_noisy / "BENCH_r1.json"), 1.0,
                   quality="noisy")
    assert bench_compare.main(["--trend", str(only_noisy)]) == 2
    # the original two-file compare contract is untouched
    assert bench_compare.main([str(d / "BENCH_r1.json"),
                               str(d / "BENCH_r2.json")]) == 0
    capsys.readouterr()
    assert bench_compare.main([str(d / "BENCH_r1.json")]) == 2
