"""``program_cache_load_s`` in a categorical job (the cell
``allstate-cat-train``): seconds the program spent retrieving
executables from the persistent compile cache (the compile table's stage
``cache_load``). The reader is ``layers/program_cache_load_s.py``'s,
which says what is read and from where; an accepted metric's list of
cells is not a new cell's to extend, so the cell reports it under a name
of its own."""

from harness import load_module

read = load_module("layers", "program_cache_load_s").read
