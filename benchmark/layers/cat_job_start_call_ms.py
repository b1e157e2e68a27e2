"""``job_start_call_ms`` in a categorical job (the cell
``allstate-cat-train``): a job's start inside its first
``fused_round_scan`` call. The reader is
``layers/job_start_call_ms.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "job_start_call_ms").read
