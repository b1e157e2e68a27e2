"""Milliseconds a job of the window spends from the job's open to the open
of ``train_fused``, less the ``place`` spans in it: ``booster_init``
(the booster, the objective, the valid sets) without its placements.
One of the four contiguous parts that add up to ``job_start_ms``
(``harness/job_start.py`` says how the trace is cut; the placements'
own part, a few ms of enqueue, is on the ``job_start:`` line only).  ``None`` without
a trace or against a program without the span ``place``."""

from harness import job_start


def read(run):
    return job_start.part_ms("init")
