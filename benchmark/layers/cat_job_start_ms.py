"""``job_start_ms`` in a categorical job (the cell ``allstate-cat-train``):
from a job's start to its first execution of the round program. The
reader is ``layers/job_start_ms.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "job_start_ms").read
