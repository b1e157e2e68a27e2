"""Structured trace spans exported as Chrome trace-event JSON.

The reference ships phase observability as the USE_TIMETAG aggregate table
(utils/common.h ``Common::Timer``); that answers "where did the time go in
total" but not "what did iteration 412 look like".  This module records
individual span events (begin/end wall-clock, thread, free-form args) and
exports them in the Chrome trace-event format — ``{"traceEvents": [...]}``
with complete (``ph: "X"``) events — loadable in Perfetto / chrome://tracing
for a timeline view of a training run.

Design constraints:

  * Near-zero cost when disabled: ``_ACTIVE`` is a module-level reference;
    every hot-path guard is one ``is None`` check, no dict or object churn.
  * Device work is asynchronous under jit, so a host span around a
    dispatched computation measures dispatch + any host sync inside it —
    the same caveat as any wall-clock profile of an async runtime.  For
    kernel-level attribution use the ``profile_dir`` hook
    (``jax.profiler.trace``) which records XLA's own device timeline.
  * Spans nest naturally (context-manager discipline per thread); counter
    events (``ph: "C"``) carry per-iteration scalar series (memory).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

#: the process-wide active recorder; ``None`` = tracing disabled (the
#: one-word fast-path check every instrumentation point makes first)
_ACTIVE: Optional["TraceRecorder"] = None
#: guards start()/stop() check-then-set on _ACTIVE (concurrent trains);
#: span emission reads _ACTIVE lock-free — worst case a racing span lands
#: in a recorder mid-stop, which the recorder's own lock makes safe
_ACTIVE_LOCK = threading.Lock()


class TraceRecorder:
    """Accumulates trace events; thread-safe appends; one per trace run."""

    # tpulint: guarded-by(_lock): _events, meta
    def __init__(self, export_path: Optional[str] = None) -> None:
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self.pid = os.getpid()
        self.export_path = export_path
        #: sidecar metadata the cross-rank merge (obs/merge.py) reads:
        #: rank/epoch tags plus the barrier-release clock anchor.
        #: Exported under a top-level ``lgbtpu`` key, which Perfetto
        #: ignores — the file stays a plain Chrome trace.
        self.meta: Dict[str, Any] = {"wall_t0": time.time()}

    def now_us(self) -> float:
        """Microseconds since this recorder started (trace ``ts`` unit)."""
        return (time.perf_counter() - self._t0) * 1e6

    def set_meta(self, **kw: Any) -> None:
        """Attach merge metadata (``rank=``, ``epoch=``, ...)."""
        with self._lock:
            self.meta.update(kw)

    def mark_anchor(self) -> None:
        """Record the clock-alignment anchor: call this the instant the
        distributed startup barrier releases (``jax.distributed.
        initialize`` returning), which every rank observes at the same
        wall moment.  The merge shifts each rank's monotonic timeline so
        these anchors coincide, cancelling per-rank wall-clock skew."""
        anchor_ts = self.now_us()
        with self._lock:
            self.meta["anchor_wall"] = time.time()
            self.meta["anchor_ts_us"] = round(anchor_ts, 3)
        self.add_instant("barrier_release")

    def add_complete(self, name: str, ts_us: float, dur_us: float,
                     args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"name": name, "ph": "X", "ts": round(ts_us, 3),
              "dur": round(dur_us, 3), "pid": self.pid,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def add_counter(self, name: str, values: Dict[str, Any]) -> None:
        """Counter-track event (``ph: "C"``) — Perfetto renders each arg
        as a time series (used for per-iteration memory)."""
        ev = {"name": name, "ph": "C", "ts": round(self.now_us(), 3),
              "pid": self.pid, "args": values}
        with self._lock:
            self._events.append(ev)

    def add_instant(self, name: str,
                    args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"name": name, "ph": "i", "ts": round(self.now_us(), 3),
              "pid": self.pid, "tid": threading.get_ident(), "s": "t"}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def num_events(self) -> int:
        with self._lock:
            return len(self._events)

    def to_dict(self) -> Dict[str, Any]:
        meta = [
            {"name": "process_name", "ph": "M", "pid": self.pid,
             "args": {"name": "lightgbm_tpu train"}},
        ]
        with self._lock:
            events = list(self._events)
            side = dict(self.meta)
        # the `lgbtpu` key is ours, not Chrome's — trace viewers ignore
        # unknown top-level keys, obs/merge.py reads the clock anchors
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "lgbtpu": side}

    def export(self, path: str) -> None:
        """Write the Chrome trace JSON (Perfetto-loadable) to ``path``."""
        from ..utils.paths import write_atomic
        write_atomic(path, json.dumps(self.to_dict()))


def active() -> Optional[TraceRecorder]:
    return _ACTIVE


def start(export_path: Optional[str] = None) -> Optional[TraceRecorder]:
    """Activate a fresh process-wide recorder and return it.

    Returns ``None`` when a recorder is already active (nested training —
    e.g. ``cv()`` folds inside a traced run): the outer session owns the
    recorder and the nested caller must not stop/export it.  A joiner
    asking for a DIFFERENT export path (two concurrent trains each with
    their own ``trace_output``) is warned that its spans land in the
    active session's file instead — the recorder is process-scoped."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if _ACTIVE is None:
            _ACTIVE = TraceRecorder(export_path)
            return _ACTIVE
        active_path = _ACTIVE.export_path
    if export_path and export_path != active_path:
        from ..utils import log
        log.warning(
            f"a trace session is already active (writing to "
            f"{active_path!r}); trace_output={export_path!r} "
            "will NOT be written — this run's spans join the active "
            "trace")
    return None


def stop(recorder: Optional[TraceRecorder],
         export_path: Optional[str] = None) -> None:
    """Deactivate ``recorder`` (a ``start()`` return value; ``None``
    no-ops, pairing with the nested-``start`` contract) and optionally
    export it."""
    global _ACTIVE
    if recorder is None:
        return
    with _ACTIVE_LOCK:
        if _ACTIVE is recorder:
            _ACTIVE = None
    if export_path:
        recorder.export(export_path)


def emit_complete(name: str, t0_perf: float, dur_s: float,
                  args: Optional[Dict[str, Any]] = None) -> None:
    """Record one completed span from ``time.perf_counter()`` readings
    (used by utils/timer.py so phase timing and tracing share one pair of
    clock reads)."""
    rec = _ACTIVE
    if rec is None:
        return
    rec.add_complete(name, (t0_perf - rec._t0) * 1e6, dur_s * 1e6, args)


#: prefix of the program's spans in a profiler trace (``.xplane.pb``)
ANNOTATION_PREFIX = "lgbtpu."


def annotate(name: str, counts: Dict[str, Any]):
    """An entered ``jax.profiler.TraceAnnotation("lgbtpu.<name>",
    **counts)`` when a profiler session is collecting (``profile_dir=``,
    or anyone's ``jax.profiler.start_trace``), else ``None`` after the
    one activity check: nothing is formatted or allocated.  The caller
    (utils/timer.py ``phase``, the one span entry point) exits it."""
    if not _TraceAnnotation.is_enabled():
        return None
    ann = _TraceAnnotation(ANNOTATION_PREFIX + name, **counts)
    ann.__enter__()
    return ann


def counter(name: str, values: Dict[str, Any]) -> None:
    rec = _ACTIVE
    if rec is None:
        return
    rec.add_counter(name, values)


# --------------------------------------------------------- jax.profiler hook
_PROFILER_ACTIVE = False


def start_profiler(profile_dir: str) -> bool:
    """Begin a ``jax.profiler`` device trace into ``profile_dir``
    (TensorBoard/Perfetto-compatible).  Returns False when a session of
    ours is already profiling (nested ``cv()`` folds join it silently —
    only the starter stops it) or, with a warning, when the profiler is
    unavailable."""
    global _PROFILER_ACTIVE
    if _PROFILER_ACTIVE:
        return False
    try:
        import jax
        jax.profiler.start_trace(profile_dir)
        _PROFILER_ACTIVE = True
        return True
    except Exception as e:  # profiler availability varies by backend
        from ..utils import log
        log.warning(f"profile_dir={profile_dir!r}: jax profiler trace "
                    f"could not start ({type(e).__name__}: {e})")
        return False


def stop_profiler() -> None:
    global _PROFILER_ACTIVE
    _PROFILER_ACTIVE = False
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception as e:  # pragma: no cover - symmetric guard
        from ..utils import log
        log.warning(f"jax profiler trace could not stop "
                    f"({type(e).__name__}: {e})")
