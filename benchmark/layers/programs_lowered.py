"""Programs the program lowered under its own spans: the count of the
rows of stage ``lower`` of its compile table (``harness/
compile_table.py``).  A lowering happens on every in-process trace-cache
miss whatever the persistent cache holds, so the count is the same from
a cold cache and a warm one.  ``None`` against a program without the
table."""

from harness import compile_table


def read(run):
    return compile_table.lowerings(compile_table.rows())
