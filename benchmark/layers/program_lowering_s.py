"""Seconds the program spent lowering jaxprs to MLIR modules under its
own spans: the rows of stage ``lower`` of its compile table
(``harness/compile_table.py``).  ``program_trace_s`` plus this is the
program's share of ``lower_s``; the rest of that older metric is the
plain reference's program.  ``None`` against a program without the
table."""

from harness import compile_table


def read(run):
    return compile_table.stage_seconds(compile_table.rows(), "lower")
