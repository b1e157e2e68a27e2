"""Seconds the program spent in the XLA backend compiler under its own
spans, the persistent cache's retrievals taken out: the rows of stage
``compile`` of its compile table (``harness/compile_table.py``).  From a
warm cache what is left is the key hashing.  ``None`` against a program
without the table."""

from harness import compile_table


def read(run):
    return compile_table.stage_seconds(compile_table.rows(), "compile")
