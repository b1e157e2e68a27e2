"""Milliseconds a job of the window spends from the close of the job's
first ``fused_round_scan`` to the round program's first execution on the
line ``XLA Modules``: the host has handed the work over, the device has
not started, because the copies its operands came by have not arrived
(their cost is in here: a ``place`` span holds only the enqueue) or
because the profiler holds the host.  One of the four contiguous parts
that add up to ``job_start_ms`` (``harness/job_start.py`` says how the
trace is cut).

The value depends on the KIND of process and holds the profiler's own
wait: 982-2038 ms where the round program was loaded from the persistent
cache, 172-452 ms where it was compiled (PR 40's chip runs), while
untraced the loading process starts its first dispatch the sooner.  Two
runs of different kinds are not comparable on it.

``None`` without a trace or against a program without the span
``place``."""

from harness import job_start


def read(run):
    return job_start.part_ms("wait")
