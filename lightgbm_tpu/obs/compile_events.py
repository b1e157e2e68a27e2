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

Listeners are process-global and jax has no targeted unregister, so
installation is once-per-process and idempotent (``install()``); the
counters are cheap enough (a few dict adds per *compile*, not per
dispatch) to leave permanently armed.
"""

from __future__ import annotations

import threading
from typing import Optional

from .metrics import count_event

_INSTALLED: Optional[bool] = None   # None = never attempted
_LOCK = threading.Lock()

#: event-name fragments -> counter (substring match survives the exact
#: key names drifting across jax versions, which they historically do)
_BACKEND_COMPILE = "backend_compile"
_LOWERING = "jaxpr_to_mlir"
_JAXPR_TRACE = "jaxpr_trace"
_CACHE_LOAD = "cache_retrieval_time"

#: per thread: cache-retrieval seconds seen since the last
#: backend-compile event, which spans them (``load_s``), and how many
#: jaxpr traces are open (``depth``)
_pending = threading.local()


def _on_scalar_event(event: str, value: float, **kwargs) -> None:
    # jax emits a scalar (the start time) when a timed stage OPENS
    if _JAXPR_TRACE in event:
        _pending.depth = getattr(_pending, "depth", 0) + 1


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    # keyword args (jax >= 0.4.36 passes platform/version tags) are
    # accepted and ignored
    if _BACKEND_COMPILE in event:
        load = getattr(_pending, "load_s", 0.0)
        _pending.load_s = 0.0
        count_event("xla_compile_events")
        count_event("xla_backend_compile_s", max(duration - load, 0.0))
    elif _LOWERING in event:
        count_event("xla_program_lowerings")
        count_event("xla_lowering_s", duration)
    elif _JAXPR_TRACE in event:
        # a trace that opened before the listener was armed has no
        # start on record: it counts as an outermost one
        _pending.depth = depth = max(getattr(_pending, "depth", 0) - 1, 0)
        if depth == 0:
            count_event("jaxpr_trace_s", duration)
    elif _CACHE_LOAD in event:
        _pending.load_s = getattr(_pending, "load_s", 0.0) + duration
        count_event("xla_cache_load_s", duration)


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
