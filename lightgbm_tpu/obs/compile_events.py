"""XLA compile-event listener -> telemetry counters.

Recompilation regressions are invisible in test *results* — a cache-key
bug that recompiles every round body still trains correctly, it just
silently eats the BENCH headline (ISSUE 7).  ``jax.monitoring`` emits a
duration event per compile stage; this module folds them into the
telemetry registry, as counts AND as the seconds the event hands over,
so they ride ``Booster.telemetry()``, the ``log_telemetry`` JSONL and
the tier-1 compile-count regression gate (tests/test_compile_cache.py):

  * ``/jax/core/compile/jaxpr_trace_duration`` — tracing the Python
    into a jaxpr -> ``jaxpr_trace_s``.  Traces nest (a jitted function
    called inside another's trace reports its own duration, inside the
    outer one's), so only the OUTERMOST trace of a thread is counted:
    jax announces the start of each with a scalar event of the same
    name, which is how the depth is known.
  * ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — one per
    jaxpr->MLIR lowering -> ``xla_program_lowerings`` and
    ``xla_lowering_s``.  Lowering happens on every in-process
    trace-cache miss regardless of the persistent cache, so the count is
    the deterministic gate signal: N distinct programs lowered is N,
    cold disk cache or warm.
  * ``/jax/compilation_cache/cache_retrieval_time_sec`` — reading and
    deserializing an executable the persistent cache holds ->
    ``xla_cache_load_s``.
  * ``/jax/core/compile/backend_compile_duration`` — one per trip
    through the backend -> ``xla_compile_events`` and
    ``xla_backend_compile_s``.  In this jax (0.9) the event spans
    ``compile_or_get_cached``, so it also fires, holding the retrieval,
    when the persistent cache serves the executable; the retrieval
    seconds reported just before on the same thread are taken out of
    it, so the two seconds counters never hold the same second twice
    (on a cache hit ``xla_backend_compile_s`` keeps the key hashing and
    little else).

Since PR 40 every stage is also a SPAN and a row of a table.  jax's
``dispatch.log_elapsed_time`` announces a stage's start with a scalar
event and its end with a duration event, both carrying the program's
``fun_name``; the listener opens a ``phase`` (utils/timer.py, the one
span entry point) at the start and closes it at the end:

  * ``jit_trace`` (the outermost trace of a thread only), ``jit_lower``,
    ``jit_compile``, each with the count ``program=<fun_name>``;
  * ``jit_cache_load``, a marker opened once the retrieval's duration
    is known, with ``program`` and ``ms`` (the ``dispatch_done``
    pattern).

So under a profiler session they are ``lgbtpu.jit_*`` annotations on
the device's clock, nested under whatever span was open
(``fused_round_scan``, ``booster_init``, ``valid_mirror``,
``tree_growth``, ...), and under ``trace_output`` they are in the
Chrome JSON.  With nothing switched on the listener still keeps
``table()``: rows ``(outermost span or "outside_the_program", enclosing
span, program, stage) -> [seconds, count]`` for the process's whole
life, which no booster's start resets, bounded by the number of
distinct programs.  The seconds are the ones jax hands over (the
compile stage's with the retrieval taken out, as the counter's).

Listeners are process-global and jax has no targeted unregister, so
installation is once-per-process and idempotent (``install()``); the
counters are cheap enough (a few dict adds per *compile*, not per
dispatch) to leave permanently armed.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from .metrics import count_event

_INSTALLED: Optional[bool] = None   # None = never attempted
_LOCK = threading.Lock()

#: event-name fragments -> counter (substring match survives the exact
#: key names drifting across jax versions, which they historically do)
_BACKEND_COMPILE = "backend_compile"
_LOWERING = "jaxpr_to_mlir"
_JAXPR_TRACE = "jaxpr_trace"
_CACHE_LOAD = "cache_retrieval_time"

#: a stage's span, by the fragment of its event's name
_STAGES = ((_BACKEND_COMPILE, "jit_compile"), (_LOWERING, "jit_lower"),
           (_JAXPR_TRACE, "jit_trace"))
_SPAN_PREFIX = "jit_"
OUTSIDE = "outside_the_program"

#: per thread: cache-retrieval seconds seen since the last
#: backend-compile event, which spans them (``load_s``), how many
#: jaxpr traces are open (``depth``), and the stages jax has announced
#: and not yet closed (``stages``: ``(event fragment, open phase or
#: None, program, (outermost span, innermost span))``, innermost last)
_pending = threading.local()

#: (outermost span, enclosing span, program, stage) -> [seconds, count]
_table: Dict[Tuple[str, str, str, str], List[float]] = {}


#: stages the table has taken in so far (what ``stages_seen`` returns)
_seen = 0


def _row(where: Tuple[str, str], program: str, stage: str,
         seconds: float) -> None:
    global _seen
    with _LOCK:
        cell = _table.setdefault((*where, program, stage), [0.0, 0])
        cell[0] += seconds
        cell[1] += 1
        _seen += 1


def stages_seen() -> int:
    """How many stages the table holds: has it grown since I looked?"""
    return _seen


def table() -> List[dict]:
    """The process's compile stages so far, largest first: one dict a
    row with ``span`` (the outermost span open on the thread, or
    ``outside_the_program``), ``inside`` (the innermost one),
    ``program``, ``stage`` (``trace``, ``lower``, ``compile``,
    ``cache_load``), ``seconds`` and ``count``."""
    with _LOCK:
        rows = [{"span": k[0], "inside": k[1], "program": k[2],
                 "stage": k[3], "seconds": v[0], "count": int(v[1])}
                for k, v in _table.items()]
    return sorted(rows, key=lambda r: -r["seconds"])


def _timer():
    # utils/timer.py imports obs.trace and obs.metrics, and obs/__init__
    # imports this module: imported at the top, whichever of the two a
    # process reached first would find the other half initialised
    from ..utils import timer
    return timer


_span_failed = False


def _span_failure(exc: BaseException) -> None:
    """A stage's span could not be opened or closed.  The listener runs
    inside jax's tracing and compilation: it says so once and carries
    on, since the rows and the counters do not need the span."""
    global _span_failed
    if not _span_failed:
        _span_failed = True
        from ..utils import log
        log.warning(f"compile_events: a jit_* span failed ({exc!r}); "
                    "the compile table and counters are kept without it")


def _open_span(name: str, **counts):
    try:
        opened = _timer().phase(name, **counts)
        opened.__enter__()
        return opened
    except Exception as exc:    # never into jax's tracing or compile
        _span_failure(exc)
        return None


def _close_span(opened) -> None:
    if opened is not None:
        try:
            opened.__exit__(None, None, None)
        except Exception as exc:
            _span_failure(exc)


def _where() -> Tuple[str, str]:
    """The outermost and the innermost of this thread's open spans,
    the stages' own left out."""
    spans = [s for s in _timer().open_spans()
             if not s.startswith(_SPAN_PREFIX)]
    return (spans[0], spans[-1]) if spans else (OUTSIDE, OUTSIDE)


def _program(kwargs: dict) -> str:
    """The program a stage's event names: tracing names the function
    (``run``), lowering and compiling the module (``jit(run)``)."""
    name = str(kwargs.get("fun_name", "?"))
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name


def _stages() -> list:
    try:
        return _pending.stages
    except AttributeError:
        _pending.stages = stages = []
        return stages


def _on_scalar_event(event: str, value: float, **kwargs) -> None:
    # jax emits a scalar (the start time) when a timed stage OPENS
    for fragment, span in _STAGES:
        if fragment in event:
            break
    else:
        return
    program, where, opened = _program(kwargs), _where(), None
    if span == "jit_trace":
        _pending.depth = depth = getattr(_pending, "depth", 0) + 1
        if depth > 1:       # a trace inside a trace: no span of its own
            span = None
    if span is not None:
        opened = _open_span(span, program=program)
    _stages().append((fragment, opened, program, where))


def _close_stage(fragment: str, kwargs: dict):
    """Close the stage jax announced last, if it is of this kind (one
    that opened before the listener was armed has no start on record):
    its program and where it ran."""
    stages = _stages()
    if stages and stages[-1][0] == fragment:
        _, opened, program, where = stages.pop()
        _close_span(opened)
        return program, where
    return _program(kwargs), _where()


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    # keyword args: ``fun_name`` from ``log_elapsed_time``; jax >= 0.4.36
    # also passes platform/version tags, accepted and ignored
    if _BACKEND_COMPILE in event:
        load = getattr(_pending, "load_s", 0.0)
        _pending.load_s = 0.0
        program, where = _close_stage(_BACKEND_COMPILE, kwargs)
        seconds = max(duration - load, 0.0)
        count_event("xla_compile_events")
        count_event("xla_backend_compile_s", seconds)
        _row(where, program, "compile", seconds)
    elif _LOWERING in event:
        program, where = _close_stage(_LOWERING, kwargs)
        count_event("xla_program_lowerings")
        count_event("xla_lowering_s", duration)
        _row(where, program, "lower", duration)
    elif _JAXPR_TRACE in event:
        # a trace that opened before the listener was armed has no
        # start on record: it counts as an outermost one
        _pending.depth = depth = max(getattr(_pending, "depth", 0) - 1, 0)
        program, where = _close_stage(_JAXPR_TRACE, kwargs)
        if depth == 0:
            count_event("jaxpr_trace_s", duration)
            _row(where, program, "trace", duration)
    elif _CACHE_LOAD in event:
        _pending.load_s = getattr(_pending, "load_s", 0.0) + duration
        count_event("xla_cache_load_s", duration)
        # the retrieval has a duration only and no name of its own: it
        # happens inside the compile stage jax announced last
        stages = _stages()
        program, where = (stages[-1][2:] if stages
                          and stages[-1][0] == _BACKEND_COMPILE
                          else ("?", _where()))
        _row(where, program, "cache_load", duration)
        _close_span(_open_span("jit_cache_load", program=program,
                               ms=round(1e3 * duration, 3)))


def install() -> bool:
    """Arm the process-wide compile-event listener.  Returns True when
    the listener is (now or already) active, False when this jax build
    has no ``jax.monitoring`` duration-listener hook (the counters then
    simply stay at zero — callers never need to branch)."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED is not None:
            return _INSTALLED
        try:
            from jax import monitoring
            register = monitoring.register_event_duration_secs_listener
        except (ImportError, AttributeError):
            _INSTALLED = False
            return False
        register(_on_duration_event)
        scalars = getattr(monitoring, "register_scalar_listener", None)
        if scalars is not None:     # older jax: nested traces count twice
            scalars(_on_scalar_event)
        _INSTALLED = True
        return True


def installed() -> bool:
    return bool(_INSTALLED)
