"""``programs_lowered`` in a categorical job (the cell
``allstate-cat-train``): programs the program lowered under its own
spans. The reader is ``layers/programs_lowered.py``'s, which says what
is read and from where; an accepted metric's list of cells is not a new
cell's to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "programs_lowered").read
