"""``job_start_wait_ms`` in a categorical job (the cell
``allstate-cat-train``): a job's start from the close of its first
``fused_round_scan`` to the round program's first execution. The reader
is ``layers/job_start_wait_ms.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "job_start_wait_ms").read
