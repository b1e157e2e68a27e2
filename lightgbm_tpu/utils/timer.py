"""Phase timing (reference utils/common.h:973 ``Common::Timer`` /
``FunctionTimer`` — RAII accumulation per named phase, aggregate table
printed at exit when built with USE_TIMETAG).

Here timing is always available and cheap: per-phase accumulators with a
context manager.  Two scopes exist since the telemetry round:

  * ``global_timer`` — the process-wide accumulator (reference
    ``global_timer``, gbdt.cpp:22), the CLI default: the CLI prints its
    table after training at ``verbosity >= 2``.
  * per-booster ``PhaseTimer`` instances (``GBDT.timer``) so concurrently
    alive boosters never clobber each other's tables; exposed through
    ``Booster.telemetry()``.

``phase(name, *timers, **counts)`` is the package's ONE span entry
point.  It times one region into every ENABLED timer with a single pair
of clock reads, emits the same interval to the Chrome-JSON recorder when
one is active (obs/trace.py, ``trace_output=...``), and enters a
``jax.profiler.TraceAnnotation("lgbtpu.<name>", **counts)`` whenever a
profiler session is collecting (``profile_dir=...`` or anyone's
``jax.profiler.start_trace``), so the program's host spans sit in the
same ``.xplane.pb``, on the same clock, as the device's ``XLA Ops``.
With no session, no recorder and no enabled timer a span costs the
profiler's activity check, a tuple scan, an ``is None`` check and one
append and one pop of its name on this thread's list of open spans
(``open_spans()``): nothing is formatted or timed.  That list is what
lets the compile-event listener (obs/compile_events.py) say, with
nothing switched on, under which span a program was traced, lowered
or compiled.  Spans stay at job, dispatch and tree granularity — never
per row, per leaf or inside jitted code.

``seconds=<counter>`` makes a span ALWAYS timed: on exit its seconds
are added to that process-wide telemetry counter (obs/metrics.py), for
the few set-up stages that are read where no timer table and no
session is on (``Dataset.construct``'s: two clock reads in a stage of
seconds).

Device work is asynchronous under jit, so phases that end with a host sync
(eval, metric reads) absorb queued device time — same caveat as any
wall-clock profile of an async runtime; device time is attributed by the
``jax.named_scope``s of the round program (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict

from ..obs import trace as _trace
from ..obs.metrics import count_event

_open = threading.local()


def open_spans() -> list:
    """Names of the spans open on this thread, outermost first."""
    try:
        return _open.names
    except AttributeError:
        _open.names = names = []
        return names


class phase:
    """Context manager for one named span (see the module docstring).
    ``counts`` are small scalars describing the region (``rounds=8``);
    they become the recorder event's ``args`` and the annotation's
    stats.  An annotation takes its counts when it OPENS, so a span that
    reports results is opened once they are known (``dispatch_done``).
    ``seconds`` names a process-wide counter the span's seconds are
    added to on exit, whatever is switched on."""

    __slots__ = ("name", "counts", "_timers", "_t0", "_ann", "_seconds")

    def __init__(self, name: str, *timers: "PhaseTimer",
                 seconds: str = "", **counts: Any) -> None:
        self.name = name
        self.counts = counts
        self._timers = timers
        self._t0 = None
        self._ann = None
        self._seconds = seconds

    def also(self, timer: "PhaseTimer") -> None:
        """Time the REST of this open span into ``timer`` as well (a
        job's root span opens before its booster, and so before the
        booster's own timer, exists)."""
        self._timers += (timer,)
        if self._t0 is None and any(t.enabled for t in self._timers):
            self._t0 = time.perf_counter()

    def __enter__(self) -> "phase":
        self._ann = _trace.annotate(self.name, self.counts)
        if self._seconds or _trace.active() is not None or \
                any(t.enabled for t in self._timers):
            self._t0 = time.perf_counter()
        open_spans().append(self.name)
        return self

    def __exit__(self, *exc) -> bool:
        open_spans().pop()
        t0 = self._t0
        if t0 is not None:
            dt = time.perf_counter() - t0
            if self._seconds:
                count_event(self._seconds, dt)
            for t in self._timers:
                if t.enabled:
                    # the global timer is shared across concurrently
                    # training boosters; an unlocked += drops
                    # accumulations under threads
                    with t._lock:
                        t._acc[self.name] += dt
                        t._count[self.name] += 1
            _trace.emit_complete(self.name, t0, dt, self.counts or None)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class PhaseTimer:
    def __init__(self) -> None:
        self._acc: Dict[str, float] = collections.defaultdict(float)
        self._count: Dict[str, int] = collections.defaultdict(int)
        self._lock = threading.Lock()
        self.enabled = False

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        self._acc.clear()
        self._count.clear()

    def timer(self, name: str):
        """Context manager timing ``name`` into this accumulator (and the
        active trace, if any)."""
        return phase(name, self)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        """``{phase: {"total_s", "count", "avg_ms"}}`` — the telemetry
        serialization of the aggregate table."""
        return {name: {"total_s": round(total, 6),
                       "count": self._count[name],
                       "avg_ms": round(total / self._count[name] * 1e3, 4)}
                for name, total in self._acc.items()}

    def summary(self) -> str:
        if not self._acc:
            return "no phases timed"
        width = max(len(k) for k in self._acc)
        lines = [f"{'phase'.ljust(width)}   total_s     calls   avg_ms"]
        for name, total in sorted(self._acc.items(), key=lambda kv: -kv[1]):
            c = self._count[name]
            lines.append(f"{name.ljust(width)}  {total:8.3f}  {c:8d}  "
                         f"{total / c * 1e3:7.2f}")
        return "\n".join(lines)


#: process-wide accumulator (reference ``global_timer``, gbdt.cpp:22)
global_timer = PhaseTimer()
