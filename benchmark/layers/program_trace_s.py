"""Seconds the program spent tracing Python into jaxprs (outermost
traces only) under its own spans (``construct``, ``train`` and what
nests in them): the rows of stage ``trace`` of its compile table
(``lightgbm_tpu/obs/compile_events.py``; ``harness/compile_table.py``
says what is read and why the plain reference's program, which runs
under no span, is not in it, where ``lower_s`` holds it).  No cache
serves tracing: every process pays it.  ``None`` against a program
without the table."""

from harness import compile_table


def read(run):
    return compile_table.stage_seconds(compile_table.rows(), "trace")
