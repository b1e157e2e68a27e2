"""``program_trace_s`` in a categorical job (the cell
``allstate-cat-train``): seconds the program spent tracing Python into
jaxprs under its own spans (the compile table's stage ``trace``). The
reader is ``layers/program_trace_s.py``'s, which says what is read and
from where; an accepted metric's list of cells is not a new cell's to
extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "program_trace_s").read
