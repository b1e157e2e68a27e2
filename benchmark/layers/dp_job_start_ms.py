"""Milliseconds from the open of an ``lgb.train`` job (the program's span
``lgbtpu.train``) to the first execution of the tree program on the
busiest chip, per job of the window: the four-chip cell's job start,
8% of its ``train_round_ms`` (``harness/mesh_job_start.py``).  ``None``
without a trace or against a program without the span ``place``."""

from harness import mesh_job_start


def read(run):
    return mesh_job_start.part_ms("job_start")
