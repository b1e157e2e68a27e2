"""Milliseconds from the start of an ``lgb.train`` job (the program's
span ``lgbtpu.train``) to the first execution of the round program after
it (trace line ``XLA Modules``), per job of the window: the host's part
before the device has work.  From this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    red = scoped.of_this_run()
    if red is None or not red["job_start_s"]:
        return None
    return 1000.0 * sum(red["job_start_s"]) / len(red["job_start_s"])
