"""Milliseconds a job of the window spends from the open of ``train_fused``
to the close of its first ``fused_round_scan``: the operands, the
runner's lookup and the call into the round program until it returns.
One of the four contiguous parts that add up to ``job_start_ms``
(``harness/job_start.py`` says how the trace is cut).  ``None`` without
a trace or against a program without the span ``place``."""

from harness import job_start


def read(run):
    return job_start.part_ms("call")
