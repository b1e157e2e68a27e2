"""``program_lowering_s`` in a categorical job (the cell
``allstate-cat-train``): seconds the program spent lowering jaxprs to
StableHLO under its own spans (the compile table's stage ``lower``). The
reader is ``layers/program_lowering_s.py``'s, which says what is read
and from where; an accepted metric's list of cells is not a new cell's
to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "program_lowering_s").read
