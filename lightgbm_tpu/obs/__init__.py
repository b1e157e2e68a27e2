"""Training telemetry: trace spans, metrics registry, memory observability.

Three pillars (docs/OBSERVABILITY.md):

  * :mod:`.trace` — structured span events exported as Chrome trace-event
    JSON (``trace_output=<path>``, Perfetto-loadable) plus an optional
    ``jax.profiler`` directory hook (``profile_dir=<dir>``),
  * :mod:`.metrics` — process- and booster-scoped counters/gauges
    (``Booster.telemetry()``, per-iteration JSONL via the
    ``log_telemetry`` callback / ``telemetry_output=<path>``),
  * :mod:`.memory` — host RSS and device memory sampling,
  * :mod:`.events` — structured lifecycle event journal
    (``event_output=<path>``, JSONL; declared schema, tpulint OBS302),
  * :mod:`.merge` — cross-rank trace merging with barrier-anchored
    clock alignment (cluster runs).

Everything is disabled by default and near-zero-cost when disabled: span
emission is one module-global ``is None`` check, counters bump only on
coarse host paths, and no file is ever written unless a ``*_output``
config key (or the callback) asks for one.
"""

from . import compile_events, events, memory, metrics, trace
from .metrics import MetricsRegistry, count_event, global_metrics

__all__ = ["trace", "metrics", "memory", "compile_events", "events",
           "MetricsRegistry", "global_metrics", "count_event",
           "observe_training"]

import contextlib
from typing import Iterator


@contextlib.contextmanager
def observe_training(config) -> Iterator[None]:
    """Engine-level observability session for one ``train()`` run.

    Activates (and on exit exports/stops) whatever the config asks for:
    ``trace_output`` starts the span recorder and writes the Chrome trace
    JSON, ``profile_dir`` brackets the run with ``jax.profiler.trace``.
    Nested runs (``cv()`` folds) join the outer session instead of
    fighting over the recorder.  With neither key set this is a no-op —
    no recorder, no files.

    An unwritable ``trace_output`` is rejected BEFORE round 1 (a typo
    must not cost a full training run), and a failed export at exit
    degrades to a warning — the trained booster is never lost to
    telemetry."""
    from ..utils import log
    from ..utils.paths import check_output_path
    # arm the process-wide XLA compile-event counters (idempotent, one
    # dict-add per compile) so every observed run's telemetry carries
    # xla_compile_events / xla_program_lowerings
    compile_events.install()
    trace_path = str(getattr(config, "trace_output", "") or "")
    profile_dir = str(getattr(config, "profile_dir", "") or "")
    event_path = str(getattr(config, "event_output", "") or "")
    # probe writability only when this session would own the export —
    # a joiner of an already-active session must not leave a zero-byte
    # stub at a path that will never be written
    if trace_path and trace.active() is None and \
            not check_output_path(trace_path, key="trace_output"):
        trace_path = ""
    if event_path and events.active() is None and \
            not check_output_path(event_path, key="event_output"):
        event_path = ""
    recorder = trace.start(trace_path) if trace_path else None
    journal = events.start(event_path) if event_path else None
    profiling = bool(profile_dir) and trace.start_profiler(profile_dir)
    try:
        yield
    finally:
        if profiling:
            trace.stop_profiler()
        events.stop(journal)
        try:
            trace.stop(recorder, export_path=trace_path or None)
        except OSError as e:
            trace.stop(recorder)
            log.warning(f"trace export to {trace_path!r} failed "
                        f"({type(e).__name__}: {e}); trace discarded")


def _writable(path: str) -> bool:
    """Back-compat alias for the shared probe (utils/paths.py) — the
    single implementation of the warn-before-round-1 output-path
    contract shared by trace/telemetry/checkpoint keys."""
    from ..utils.paths import writable_file
    return writable_file(path)
