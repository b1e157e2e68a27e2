"""Seconds the program spent retrieving executables from the persistent
compile cache under its own spans: the rows of stage ``cache_load`` of
its compile table (the program's markers ``jit_cache_load``;
``harness/compile_table.py``).  ``None`` against a program without the
table."""

from harness import compile_table


def read(run):
    return compile_table.stage_seconds(compile_table.rows(), "cache_load")
