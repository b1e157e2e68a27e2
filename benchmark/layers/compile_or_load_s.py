"""Seconds this process spent in the XLA backend compiler or retrieving
executables from the persistent compile cache: the program's counters
``xla_backend_compile_s`` + ``xla_cache_load_s`` (obs/compile_events.py;
the two never hold the same second twice).  Nothing compiles inside the
window (run.py asserts it), so this is set-up's, and after the window the
plain reference's own program (PERF.md section 3)."""

from harness import scoped


def read(run):
    return scoped.program_counter("xla_backend_compile_s", "xla_cache_load_s")
