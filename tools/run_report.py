#!/usr/bin/env python
"""One pane of glass over a training run's observability artifacts.

    python tools/run_report.py [--trace T.json] [--events E.jsonl]
                               [--telemetry TEL.jsonl]
                               [--quick] [--format text|json]

Joins the three artifact families one run can emit — the Chrome trace
(``trace_output``), the structured event journal (``event_output``,
obs/events.py) and the telemetry JSONL (``telemetry_output``) — into a
single report: top phases, the event timeline, the final counter
snapshot with the compile-cache columns pulled out.  Any subset of the
artifacts may be given; at least one must be.

A journal carrying continuous-learning records (pipeline/trainer.py)
additionally gets a pipeline section joining the trainer's cycle events
with the serving tier's hot-swap events: cycles completed, per-cycle
publish latency, resumes — and a cycle that started but never published
is a finding (``--quick`` exits 1: the workdir holds an unfinished,
resumable cycle).  Journals with sharded-ingest stripe records
(io/sharded.py) get a stripe-ledger section — claims joined against
commits — where a claimed-but-never-committed stripe is likewise a
``--quick`` finding: the merged dataset under that ledger is
incomplete.

``--quick`` is the CI gate mode: it only validates that every provided
artifact parses and carries its expected schema (trace has span
events, journal has records, telemetry has rows) and reports findings
without the full join.

Exit codes (tools/_report.py convention): 0 — every provided artifact
is present and non-degenerate, 1 — findings (an artifact parsed but is
empty/spanless), 2 — an artifact is unreadable or not its format (or
no artifact was given at all).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _report import (EXIT_ERROR, EXIT_FINDINGS, EXIT_OK,  # noqa: E402
                     add_format_arg, emit)
import trace_report  # noqa: E402

#: final-snapshot counters surfaced as the "compile" join column
_COMPILE_COUNTERS = (
    "round_compile_hits", "round_compile_misses",
    "fused_runner_cache_hits", "fused_runner_cache_misses",
    "xla_compile_events", "xla_program_lowerings",
    "serve_compile_hits", "serve_compile_misses",
    "rank_compile_hits", "rank_compile_misses",
)

#: rows of the compile table the "compile" column prints
_TABLE_ROWS = 8

#: final-snapshot gauges surfaced as the "rank" join column (query
#: bucketing geometry: padded-row overhead and ladder width)
_RANK_GAUGES = ("rank_pad_rows", "rank_bucket_count")

#: final-snapshot counters surfaced as the "watchtower" join column
_WATCHTOWER_COUNTERS = (
    "rollup_windows_closed", "slo_breaches", "slo_recoveries",
    "anomalies_detected",
)


def slo_stats(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Replay the journal's watchtower records into an SLO ledger.

    Breach/recover events carry the SLO name in ``payload.slo``; a name
    whose LAST transition is a breach is "unrecovered" — the signal the
    ``--quick`` CI gate turns into a nonzero exit."""
    last_state: Dict[str, str] = {}
    breaches = recoveries = anomalies = 0
    anomaly_kinds: Dict[str, int] = {}
    for rec in events:
        name = rec.get("event")
        payload = rec.get("payload") or {}
        slo = payload.get("slo") if isinstance(payload, dict) else None
        if name == "slo_breach":
            breaches += 1
            if isinstance(slo, str):
                last_state[slo] = "breached"
        elif name == "slo_recovered":
            recoveries += 1
            if isinstance(slo, str):
                last_state[slo] = "ok"
        elif name == "anomaly_detected":
            anomalies += 1
            kind = payload.get("kind") if isinstance(payload, dict) \
                else None
            if isinstance(kind, str):
                anomaly_kinds[kind] = anomaly_kinds.get(kind, 0) + 1
    unrecovered = sorted(n for n, s in last_state.items()
                         if s == "breached")
    return {
        "breaches": breaches,
        "recoveries": recoveries,
        "anomalies": anomalies,
        "anomaly_kinds": anomaly_kinds,
        "last_state": last_state,
        "unrecovered": unrecovered,
    }


def ingest_stats(events: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Replay streaming-ingest records (io/streaming.py) into a ledger.

    ``None`` when the journal holds no ingest events.  An ingest that
    started (or resumed) but never logged ``ingest_completed`` is the
    CI-gate signal — the dataset on disk is partial."""
    started = completed = resumed = 0
    shards: Dict[str, int] = {}
    rows = features = None
    for rec in events:
        name = rec.get("event")
        payload = rec.get("payload") or {}
        if not isinstance(payload, dict):
            payload = {}
        if name == "ingest_started":
            started += 1
        elif name == "ingest_resumed":
            resumed += 1
        elif name == "ingest_shard_done":
            stage = str(payload.get("stage", "?"))
            shards[stage] = shards.get(stage, 0) + 1
        elif name == "ingest_completed":
            completed += 1
            rows = payload.get("rows", rows)
            features = payload.get("features", features)
    if not (started or resumed or completed or shards):
        return None
    return {
        "started": started, "resumed": resumed, "completed": completed,
        "shards": shards, "rows": rows, "features": features,
        "unfinished": (started + resumed) > 0 and completed == 0,
    }


#: claim/steal records carry the ledger pass tag; commit records carry
#: the human stage name — the join key between the two families
_STAGE_TO_TAG = {"sketch": "p1", "bin": "p2", "collect": "c"}


def sharded_stats(events: List[Dict[str, Any]]) \
        -> Optional[Dict[str, Any]]:
    """Replay sharded-ingest records (io/sharded.py) into a stripe
    ledger: claims (first-claim + steals) joined against commits.

    ``None`` when the journal holds no stripe events.  A stripe that
    was claimed (or reassigned) but never committed is ORPHANED — the
    CI-gate signal that a worker died holding work nobody finished,
    so the merged dataset under that ledger is incomplete."""
    claims: Dict[Any, int] = {}
    done = set()
    reassigned = deaths = merges = 0
    workers = None
    dead_ranks = set()
    for rec in events:
        name = rec.get("event")
        payload = rec.get("payload") or {}
        if not isinstance(payload, dict):
            payload = {}
        if name == "ingest_stripe_claimed":
            k = (str(payload.get("stage")), payload.get("stripe"))
            claims[k] = claims.get(k, 0) + 1
        elif name == "ingest_stripe_reassigned":
            reassigned += 1
            k = (str(payload.get("stage")), payload.get("stripe"))
            claims[k] = claims.get(k, 0) + 1
        elif name == "ingest_worker_dead":
            deaths += 1
            if payload.get("dead_rank") is not None:
                dead_ranks.add(int(payload["dead_rank"]))
        elif name == "ingest_merge_completed":
            merges += 1
            workers = payload.get("workers", workers)
        elif name == "ingest_shard_done":
            tag = _STAGE_TO_TAG.get(str(payload.get("stage")))
            if tag is not None:
                done.add((tag, payload.get("shard")))
    if not (claims or reassigned or deaths or merges):
        return None
    orphaned = sorted(f"{tag}:{stripe}" for tag, stripe in claims
                      if (tag, stripe) not in done)
    return {
        "stripes_claimed": len(claims),
        "stripes_committed": len(done),
        "stripes_reassigned": reassigned,
        "worker_deaths": deaths,
        "dead_ranks": sorted(dead_ranks),
        "merges_completed": merges,
        "workers": workers,
        "orphaned_stripes": orphaned,
    }


def pipeline_stats(events: List[Dict[str, Any]]) \
        -> Optional[Dict[str, Any]]:
    """Replay continuous-learning records (pipeline/trainer.py) into a
    cycle ledger, joining the trainer's side of the journal
    (``cycle_started`` .. ``cycle_published``) with the serving side
    (``serve_hot_swap``) the same publishes produced.

    ``None`` when the journal holds no pipeline events.  A cycle that
    started but never published (nor was refused as stale) is the
    CI-gate signal — the pipeline workdir holds an unfinished cycle.
    Latencies are wall-clock (``unix_time``), not ``t_mono``, because a
    resumed cycle's records span trainer processes."""
    started: Dict[int, Any] = {}
    published: Dict[int, Dict[str, Any]] = {}
    resumes = stale = swaps = 0
    for rec in events:
        name = rec.get("event")
        payload = rec.get("payload") or {}
        if not isinstance(payload, dict):
            payload = {}
        c = payload.get("cycle")
        if name == "cycle_started" and c is not None:
            started.setdefault(int(c), rec.get("unix_time"))
        elif name == "cycle_resumed":
            resumes += 1
        elif name == "serve_hot_swap":
            swaps += 1
        elif name == "cycle_published" and c is not None:
            published[int(c)] = {"version": payload.get("version"),
                                 "t": rec.get("unix_time")}
        elif name == "publish_skipped_stale" and c is not None:
            stale += 1
            published.setdefault(int(c), {
                "version": payload.get("version"),
                "t": rec.get("unix_time"), "stale": True})
    if not (started or published or resumes):
        return None
    cycles = []
    for c in sorted(published):
        t0, t1 = started.get(c), published[c].get("t")
        lat = round(t1 - t0, 6) if t0 and t1 and t1 >= t0 else None
        cycles.append({"cycle": c, "version": published[c].get("version"),
                       "publish_latency_s": lat,
                       "stale_skipped": bool(published[c].get("stale"))})
    unfinished = sorted(set(started) - set(published))
    return {
        "cycles_completed": len(published), "resumes": resumes,
        "stale_publishes_refused": stale, "hot_swaps": swaps,
        "cycles": cycles, "unfinished_cycles": unfinished,
        "unfinished": bool(unfinished),
    }


def load_telemetry(path: str) -> List[Dict[str, Any]]:
    """Telemetry JSONL rows (one per round); torn lines are skipped."""
    rows: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict):
                rows.append(row)
    return rows


def telemetry_stats(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Final-state summary of the per-round telemetry stream."""
    counters: Dict[str, Any] = {}
    gauges: Dict[str, Any] = {}
    compile_table: List[Dict[str, Any]] = []
    iters = []
    for row in rows:
        if isinstance(row.get("counters"), dict):
            counters = row["counters"]
        if isinstance(row.get("gauges"), dict):
            gauges = row["gauges"]
        if isinstance(row.get("compile_table"), list):
            # a record carries the table only after it has grown
            compile_table = row["compile_table"]
        it = row.get("iteration")
        if isinstance(it, (int, float)):
            iters.append(int(it))
    return {
        "rows": len(rows),
        "first_round": min(iters) if iters else None,
        "last_round": max(iters) if iters else None,
        "counters": counters,
        "gauges": gauges,
        "compile": {k: counters[k] for k in _COMPILE_COUNTERS
                    if k in counters},
        "compile_table": compile_table,
        "rank": {k: gauges[k] for k in _RANK_GAUGES if k in gauges},
        "watchtower": {k: counters[k] for k in _WATCHTOWER_COUNTERS
                       if k in counters},
    }


def build_report(trace_doc: Optional[Dict[str, Any]],
                 events: Optional[List[Dict[str, Any]]],
                 telemetry: Optional[List[Dict[str, Any]]],
                 paths: Dict[str, str],
                 quick: bool = False) -> Dict[str, Any]:
    payload: Dict[str, Any] = {"tool": "run_report", "quick": quick,
                               "sources": paths}
    findings: List[str] = []
    if trace_doc is not None:
        phases = trace_report.phase_stats(trace_doc)
        if not phases:
            findings.append("trace has no complete (ph=X) span events")
        if quick:
            payload["trace"] = {"span_kinds": len(phases)}
        else:
            tr = trace_report.build_report(trace_doc,
                                           trace=paths.get("trace", ""))
            tr.pop("tool", None)
            payload["trace"] = tr
    if events is not None:
        if not events:
            findings.append("event journal holds no records")
        stats = trace_report.event_stats(events)
        if not quick:
            stats["timeline"] = [
                {"event": r.get("event"), "rank": r.get("rank"),
                 "round": r.get("round"),
                 "severity": r.get("severity")} for r in events]
        payload["events"] = stats
        slo = slo_stats(events)
        if slo["breaches"] or slo["recoveries"] or slo["anomalies"]:
            payload["slo"] = slo
        # fires in quick AND full mode: an unrecovered breach is the
        # one journal state that should fail a CI gate outright
        if slo["unrecovered"]:
            findings.append("run ends with unrecovered slo_breach: "
                            + ", ".join(slo["unrecovered"]))
        ingest = ingest_stats(events)
        if ingest is not None:
            payload["ingest"] = ingest
            if ingest["unfinished"]:
                findings.append(
                    "streaming ingest started but never completed — the "
                    "dataset in its workdir is partial (resumable)")
        shd = sharded_stats(events)
        if shd is not None:
            payload["sharded"] = shd
            if shd["orphaned_stripes"]:
                findings.append(
                    "sharded-ingest stripe(s) "
                    + ", ".join(shd["orphaned_stripes"])
                    + " claimed but never committed — a worker died "
                    "holding work no survivor finished; the merged "
                    "dataset under that ledger is incomplete")
        pipe = pipeline_stats(events)
        if pipe is not None:
            payload["pipeline"] = pipe
            if pipe["unfinished"]:
                findings.append(
                    "continuous-learning cycle(s) "
                    + ", ".join(str(c) for c in pipe["unfinished_cycles"])
                    + " started but never published — the pipeline "
                    "workdir holds an unfinished cycle (resumable)")
    if telemetry is not None:
        if not telemetry:
            findings.append("telemetry stream holds no rows")
        payload["telemetry"] = telemetry_stats(telemetry) if not quick \
            else {"rows": len(telemetry)}
    payload["findings"] = findings
    return payload


def _render_report(payload: Dict[str, Any]) -> str:
    lines = []
    for f in payload["findings"]:
        lines.append(f"FINDING: {f}")
    tr = payload.get("trace")
    if tr and "phases" in tr:
        sub = dict(tr)
        sub.setdefault("tool", "trace_report")
        lines.append(trace_report._render_report(sub))
    elif tr is not None:
        lines.append(f"trace: {tr.get('span_kinds', 0)} span kind(s)")
    ev = payload.get("events")
    if ev is not None:
        lines.append("")
        lines.append(f"event journal: {ev['count']} record(s)")
        for name in sorted(ev.get("by_name", {})):
            lines.append(f"  {name}: {ev['by_name'][name]}")
    slo = payload.get("slo")
    if slo is not None:
        lines.append("")
        lines.append(f"watchtower: {slo['breaches']} breach(es), "
                     f"{slo['recoveries']} recovery(ies), "
                     f"{slo['anomalies']} anomaly(ies)")
        for name in sorted(slo.get("last_state", {})):
            state = slo["last_state"][name]
            flag = "UNRECOVERED" if state == "breached" else "ok"
            lines.append(f"  slo {name}: {flag}")
        for kind in sorted(slo.get("anomaly_kinds", {})):
            lines.append(f"  anomaly {kind}: "
                         f"{slo['anomaly_kinds'][kind]}")
    ingest = payload.get("ingest")
    if ingest is not None:
        lines.append("")
        state = "complete" if ingest["completed"] else (
            "UNFINISHED" if ingest["unfinished"] else "idle")
        lines.append(f"streaming ingest: {state} "
                     f"({ingest['started']} started, "
                     f"{ingest['resumed']} resumed)")
        for stage in sorted(ingest.get("shards", {})):
            lines.append(f"  {stage} shards: {ingest['shards'][stage]}")
        if ingest.get("rows") is not None:
            lines.append(f"  rows: {ingest['rows']}  features: "
                         f"{ingest.get('features')}")
    shd = payload.get("sharded")
    if shd is not None:
        lines.append("")
        state = "ORPHANED STRIPES" if shd["orphaned_stripes"] else "clean"
        lines.append(f"sharded ingest: {state} "
                     f"({shd['stripes_claimed']} stripe(s) claimed, "
                     f"{shd['stripes_committed']} committed, "
                     f"{shd['stripes_reassigned']} reassigned)")
        if shd.get("workers") is not None:
            lines.append(f"  workers: {shd['workers']}  merges: "
                         f"{shd['merges_completed']}")
        if shd["worker_deaths"]:
            ranks = ", ".join(str(r) for r in shd["dead_ranks"]) or "?"
            lines.append(f"  worker deaths: {shd['worker_deaths']} "
                         f"(rank(s) {ranks})")
        for s in shd["orphaned_stripes"]:
            lines.append(f"  orphaned stripe {s}")
    pipe = payload.get("pipeline")
    if pipe is not None:
        lines.append("")
        state = "UNFINISHED" if pipe["unfinished"] else "complete"
        lines.append(f"continuous pipeline: {state} "
                     f"({pipe['cycles_completed']} cycle(s) published, "
                     f"{pipe['resumes']} resume(s), "
                     f"{pipe['hot_swaps']} hot swap(s))")
        for c in pipe.get("cycles", []):
            lat = c.get("publish_latency_s")
            lat_s = f"{lat:.3f}s" if lat is not None else "?"
            note = "  STALE-SKIPPED" if c.get("stale_skipped") else ""
            lines.append(f"  cycle {c['cycle']}: version {c['version']} "
                         f"published after {lat_s}{note}")
        if pipe.get("stale_publishes_refused"):
            lines.append(f"  stale publishes refused: "
                         f"{pipe['stale_publishes_refused']}")
    tel = payload.get("telemetry")
    if tel is not None:
        lines.append("")
        lines.append(f"telemetry: {tel['rows']} row(s)")
        if tel.get("last_round") is not None:
            lines.append(f"  rounds {tel['first_round']}"
                         f"..{tel['last_round']}")
        for section in ("compile", "rank", "watchtower"):
            vals = tel.get(section) or {}
            if vals:
                lines.append(f"  {section}:")
                for k in sorted(vals):
                    lines.append(f"    {k}: {vals[k]}")
            table = tel.get("compile_table") if section == "compile" \
                else None
            if table:
                # which programs: the process's compile table, largest
                # first (obs/compile_events.py ``table()``)
                lines.append("  compile table (seconds, count, stage, "
                             "program, [span > innermost span]):")
                for r in table[:_TABLE_ROWS]:
                    lines.append(
                        f"    {r['seconds']:9.3f}s x{r['count']:<3d} "
                        f"{r['stage']:<10s} {r['program']}  "
                        f"[{r['span']} > {r['inside']}]")
    if not payload["findings"]:
        lines.append("")
        lines.append("run artifacts healthy")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", default=None,
                    help="Chrome trace JSON (trace_output=...)")
    ap.add_argument("--events", default=None,
                    help="event-journal JSONL (event_output=...)")
    ap.add_argument("--telemetry", default=None,
                    help="telemetry JSONL (telemetry_output=...)")
    ap.add_argument("--quick", action="store_true",
                    help="schema-validation gate only (CI mode)")
    add_format_arg(ap)
    args = ap.parse_args(argv)
    if not (args.trace or args.events or args.telemetry):
        print("run_report: no artifacts given — pass at least one of "
              "--trace/--events/--telemetry", file=sys.stderr)
        return EXIT_ERROR
    paths = {}
    try:
        trace_doc = None
        if args.trace:
            trace_doc = trace_report.load_trace(args.trace)
            paths["trace"] = args.trace
        events = None
        if args.events:
            events = trace_report.load_events(args.events)
            paths["events"] = args.events
        telemetry = None
        if args.telemetry:
            telemetry = load_telemetry(args.telemetry)
            paths["telemetry"] = args.telemetry
    except (OSError, ValueError) as e:
        print(f"run_report: {e}", file=sys.stderr)
        return EXIT_ERROR
    payload = build_report(trace_doc, events, telemetry, paths,
                           quick=args.quick)
    emit(payload, args.format, _render_report)
    return EXIT_FINDINGS if payload["findings"] else EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
