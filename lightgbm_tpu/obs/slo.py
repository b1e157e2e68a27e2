"""Declarative SLOs with multi-window burn-rate alerting.

Every standing health judgement the repo makes — "serving p99 is over
budget", "a worker's heartbeat is stale", "the compile cache is
missing at steady state" — is declared ONCE in the :data:`SLOS` table
below (same discipline as obs/metrics.py ``COUNTERS`` and
obs/events.py ``EVENTS``; tpulint OBS303 parses the literal by AST and
fails the gate on a ``watch_slo`` of an undeclared name, or a declared
SLO nothing watches).

Evaluation runs over finalized rollup windows (obs/timeseries.py) with
burn-rate logic rather than point triggers:

  * **breach** — the newest window violates its budget AND at least
    ``breach_windows`` of the last ``slow_windows`` observed windows
    violated ("over budget for N of the last M windows"); a single
    noisy window never pages.
  * **recover** — a breached SLO whose last ``recover_windows``
    consecutive windows all comply (windows with no data are neutral:
    they neither extend a breach nor count as violations).

Transitions emit the declared journal events ``slo_breach`` /
``slo_recovered`` through obs/events.py — so they land in traces,
merged ranks and tools/run_report.py automatically — and bump the
``slo_breaches`` / ``slo_recoveries`` counters.

Contracts: stdlib-only, never imports jax (tools/obs_top.py loads this
file standalone by path); the journal/counter sinks are injected by the
package wiring (engine.py / serving/server.py / parallel/cluster.py)
and silently absent standalone.  Nothing here runs unless
``slo_config`` is set — the all-off default costs zero per-round work.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, Dict, List, Optional

#: Every SLO name the package can watch, declared once as
#: ``name: (domain, direction, default_budget, one-line meaning)``.
#: ``direction`` is the violation sense; every declared SLO is ``"max"``
#: (a value above budget violates).  Lint contract
#: (tpulint OBS303, same discipline as OBS301/OBS302): watching an
#: undeclared name — or declaring one nothing watches — fails
#: ``python tools/tpulint.py``.  Keys are parsed from this literal by
#: AST, so keep it a plain dict with string keys.
SLOS: Dict[str, tuple] = {
    "serving_p99_ms": (
        "serving", "max", 50.0,
        "windowed p99 request latency (ms) stays within budget "
        "(serving/server.py predict latency samples)"),
    "serving_error_rate": (
        "serving", "max", 0.01,
        "rejected requests / offered requests per window stays within "
        "budget (admission-control rejections + deadline expiries)"),
    "heartbeat_staleness_s": (
        "training", "max", 30.0,
        "max worker heartbeat age (s) observed in a window stays under "
        "budget (parallel/cluster.py elastic liveness monitor)"),
    "nan_guard_trip_rate": (
        "training", "max", 0.0,
        "nan-guard trips per boosting round in a window stays at budget "
        "(robustness/guards.py numeric guard)"),
    "compile_miss_storm": (
        "training", "max", 2.0,
        "compile-cache misses per window at steady state stay under "
        "budget (round + fused-runner caches; warmup misses burn one "
        "window and never page)"),
}

#: burn-rate defaults: breach needs the newest window violating plus
#: this many violations among the last ``slow_windows``; recovery needs
#: this many consecutive compliant windows
SLOW_WINDOWS = 6
BREACH_WINDOWS = 2
RECOVER_WINDOWS = 2


def parse_slo_config(spec: Any) -> Dict[str, float]:
    """``slo_config`` string -> {slo_name: budget}.

    ``""``/``"off"`` -> {} (all off).  ``"on"``/``"default"``/``"all"``
    -> every declared SLO at its default budget.  Otherwise a
    comma-separated list of ``name`` (default budget) or ``name:budget``
    entries.  Unknown names raise ``ValueError`` naming the offender —
    the config-key owner converts that to its fatal-parameter path."""
    text = str(spec or "").strip().lower()
    if text in ("", "off", "none", "false", "0"):
        return {}
    if text in ("on", "default", "all", "true", "1"):
        return {name: float(SLOS[name][2]) for name in SLOS}
    out: Dict[str, float] = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, budget = part.partition(":")
        name = name.strip()
        if name not in SLOS:
            raise ValueError(
                f"unknown SLO {name!r} (declared SLOs: "
                f"{', '.join(sorted(SLOS))})")
        if budget.strip():
            try:
                out[name] = float(budget)
            except ValueError:
                raise ValueError(
                    f"SLO {name!r}: budget {budget!r} is not a number")
        else:
            out[name] = float(SLOS[name][2])
    return out


# ----------------------------------------------------- window extractors
def _counter_delta(window: Dict[str, Any], name: str) -> Optional[float]:
    row = (window.get("counters") or {}).get(name)
    return None if row is None else float(row.get("delta", 0.0))


def _gauge(window: Dict[str, Any], name: str,
           field: str = "last") -> Optional[float]:
    row = (window.get("gauges") or {}).get(name)
    return None if row is None else row.get(field)


def _serving_p99(window: Dict[str, Any]) -> Optional[float]:
    row = (window.get("samples") or {}).get("latency_ms")
    return None if row is None else row.get("p99")


def _serving_error_rate(window: Dict[str, Any]) -> Optional[float]:
    rej = _counter_delta(window, "serve_rejected_requests")
    req = _counter_delta(window, "serve_requests")
    if rej is None and req is None:
        return None
    offered = (req or 0.0) + (rej or 0.0)
    if offered <= 0:
        return None
    return (rej or 0.0) / offered


def _nan_trip_rate(window: Dict[str, Any]) -> Optional[float]:
    rounds = _counter_delta(window, "iterations")
    if not rounds:
        return None
    return (_counter_delta(window, "nan_guard_trips") or 0.0) / rounds


def _compile_misses(window: Dict[str, Any]) -> Optional[float]:
    vals = [_counter_delta(window, name) for name in
            ("round_compile_misses", "fused_runner_cache_misses",
             "serve_compile_misses")]
    present = [v for v in vals if v is not None]
    return sum(present) if present else None


def _heartbeat_staleness(window: Dict[str, Any]) -> Optional[float]:
    return _gauge(window, "heartbeat_staleness_s", "max")


#: per-SLO value extractor over one finalized rollup window; a missing
#: series returns None ("no data this window" — neutral for burn-rate)
_EXTRACTORS: Dict[str, Callable] = {
    "serving_p99_ms": _serving_p99,
    "serving_error_rate": _serving_error_rate,
    "heartbeat_staleness_s": _heartbeat_staleness,
    "nan_guard_trip_rate": _nan_trip_rate,
    "compile_miss_storm": _compile_misses,
}


class _Tracker:
    """Burn-rate state for one watched SLO."""

    __slots__ = ("name", "budget", "direction", "history", "breached",
                 "clean_streak", "last_value", "transitions")

    def __init__(self, name: str, budget: float, direction: str) -> None:
        self.name = name
        self.budget = float(budget)
        self.direction = direction
        self.history: deque = deque(maxlen=SLOW_WINDOWS)
        self.breached = False
        self.clean_streak = 0
        self.last_value: Optional[float] = None
        self.transitions = 0

    def violates(self, value: Optional[float]) -> bool:
        return value is not None and value > self.budget


class SloEvaluator:
    """Evaluates enabled SLOs over finalized rollup windows.

    ``spec`` is the ``slo_config`` string (or an already-parsed
    name->budget dict).  Sites then call :meth:`watch_slo` with the
    literal names they can feed — registration is a no-op for names the
    config did not enable, so every emission site can watch its SLOs
    unconditionally.  ``emit``/``count`` are the journal/counter sinks
    (obs/events.py ``emit_event`` / obs/metrics.py ``count_event``
    inside the package; ``None`` standalone = transitions tracked but
    not journaled)."""

    def __init__(self, spec: Any = "", emit: Optional[Callable] = None,
                 count: Optional[Callable] = None,
                 breach_windows: int = BREACH_WINDOWS,
                 recover_windows: int = RECOVER_WINDOWS) -> None:
        self.enabled = dict(spec) if isinstance(spec, dict) \
            else parse_slo_config(spec)
        self.breach_windows = int(breach_windows)
        self.recover_windows = int(recover_windows)
        self._emit = emit
        self._count_hook = count
        self._trackers: Dict[str, _Tracker] = {}
        self._cursor = float("-inf")   # t_end of the last consumed window

    # ------------------------------------------------------- registration
    def watch_slo(self, name: str,
                  budget: Optional[float] = None) -> bool:
        """Register ``name`` for evaluation.  Returns True when the SLO
        is enabled by the config (and now watched); False when disabled.
        Watching a name not declared in :data:`SLOS` raises — the
        runtime backstop behind the OBS303 static gate."""
        if name not in SLOS:
            raise ValueError(f"SLO {name!r} is not declared in "
                             "obs/slo.py SLOS")
        if name not in self.enabled:
            return False
        if name not in self._trackers:
            _, direction, default_budget, _ = SLOS[name]
            b = self.enabled.get(name, default_budget) \
                if budget is None else float(budget)
            self._trackers[name] = _Tracker(name, b, direction)
        return True

    def watched(self) -> List[str]:
        return sorted(self._trackers)

    # --------------------------------------------------------- evaluation
    def evaluate(self, windows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        """Consume finalized windows (oldest..newest; windows already
        seen are skipped by ``t_end`` cursor) and return the transition
        records emitted ([{'slo', 'state', 'value', 'budget'}, ...])."""
        transitions: List[Dict[str, Any]] = []
        for window in windows:
            t_end = float(window.get("t_end", 0.0))
            if t_end <= self._cursor:
                continue
            self._cursor = t_end
            for tracker in self._trackers.values():
                transitions.extend(self._step(tracker, window))
        return transitions

    def _step(self, tracker: _Tracker,
              window: Dict[str, Any]) -> List[Dict[str, Any]]:
        value = _EXTRACTORS[tracker.name](window)
        violated = tracker.violates(value)
        tracker.history.append(violated)
        if value is not None:
            tracker.last_value = value
        out: List[Dict[str, Any]] = []
        if not tracker.breached:
            burn = sum(1 for v in tracker.history if v)
            if violated and burn >= self.breach_windows:
                tracker.breached = True
                tracker.clean_streak = 0
                tracker.transitions += 1
                out.append(self._transition(
                    tracker, "breach", value, window, burn=burn))
        else:
            if violated:
                tracker.clean_streak = 0
            else:
                tracker.clean_streak += 1
                if tracker.clean_streak >= self.recover_windows:
                    tracker.breached = False
                    tracker.transitions += 1
                    out.append(self._transition(
                        tracker, "recovered", value, window,
                        clean=tracker.clean_streak))
        return out

    def _transition(self, tracker: _Tracker, state: str,
                    value: Optional[float], window: Dict[str, Any],
                    **extra: Any) -> Dict[str, Any]:
        rec = {"slo": tracker.name, "state": state, "value": value,
               "budget": tracker.budget,
               "direction": tracker.direction,
               "t_end": window.get("t_end"), **extra}
        if state == "breach":
            self._count("slo_breaches")
            self.emit_event("slo_breach", slo=tracker.name, value=value,
                            budget=tracker.budget,
                            direction=tracker.direction, **extra)
        else:
            self._count("slo_recoveries")
            self.emit_event("slo_recovered", slo=tracker.name,
                            value=value, budget=tracker.budget,
                            direction=tracker.direction, **extra)
        return rec

    # --------------------------------------------------------------- state
    def state(self) -> Dict[str, Dict[str, Any]]:
        """Live per-SLO view: ok flag, budget, last value, violation
        count over the burn-rate history."""
        return {name: {"ok": not tr.breached, "budget": tr.budget,
                       "direction": tr.direction,
                       "last_value": tr.last_value,
                       "violations": sum(1 for v in tr.history if v),
                       "history_windows": len(tr.history),
                       "transitions": tr.transitions}
                for name, tr in self._trackers.items()}

    def breached(self) -> List[str]:
        return sorted(n for n, tr in self._trackers.items() if tr.breached)

    # ---------------------------------------------------------- sinks
    def emit_event(self, name: str, **payload: Any) -> None:
        """Forward a transition to the journal sink; silently absent
        when loaded standalone (obs_top) or unconfigured."""
        sink = self._emit
        if sink is None:
            try:
                from .events import emit_event as sink
            except ImportError:
                return
        try:
            sink(name, **payload)
        except Exception:
            self._emit = None     # a broken sink must never stop serving

    def _count(self, name: str, value: float = 1) -> None:
        hook = self._count_hook
        if hook is None:
            return
        try:
            hook(name, value)
        except Exception:
            self._count_hook = None


class Watchtower:
    """One attachable bundle of the continuous-monitoring pieces: a
    rollup ring plus optional SLO evaluator and anomaly detector.  The
    wiring sites (engine.py, serving/server.py, parallel/cluster.py)
    build one of these only when ``slo_config``/``anomaly_detection``
    is configured — the all-off default constructs nothing."""

    def __init__(self, rollup, slo: Optional[SloEvaluator] = None,
                 anomaly=None) -> None:
        self.rollup = rollup
        self.slo = slo
        self.anomaly = anomaly

    def evaluate(self) -> List[Dict[str, Any]]:
        """Run the SLO evaluator over any newly finalized windows."""
        if self.slo is None:
            return []
        return self.slo.evaluate(self.rollup.completed())

    def slo_state(self) -> Dict[str, Dict[str, Any]]:
        return {} if self.slo is None else self.slo.state()

    def breached(self) -> List[str]:
        return [] if self.slo is None else self.slo.breached()

    def close(self) -> None:
        """Flush the final partial window and evaluate it (end of a
        training run / server shutdown)."""
        self.rollup.close()
        self.evaluate()
