"""Seconds this process spent tracing Python into jaxprs and lowering
them to MLIR modules: the program's counters ``jaxpr_trace_s`` +
``xla_lowering_s``, kept by its listener of ``jax.monitoring``'s duration
events (obs/compile_events.py).  Nothing compiles inside the window
(run.py asserts it), so this is set-up's, and after the window the plain
reference's own program (PERF.md section 3)."""

from harness import scoped


def read(run):
    return scoped.program_counter("jaxpr_trace_s", "xla_lowering_s")
