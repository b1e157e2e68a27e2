"""``round_program_lower_s`` in a categorical job (the cell
``allstate-cat-train``): seconds of tracing and lowering of the one
program that runs the rounds, which in this cell carries the subset scan
and the kernel's variant with sets. The reader is
``layers/round_program_lower_s.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "round_program_lower_s").read
