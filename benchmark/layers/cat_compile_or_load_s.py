"""``compile_or_load_s`` in a categorical job (the cell ``allstate-cat-
train``): compiling or loading from the cache. The reader is
``layers/compile_or_load_s.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "compile_or_load_s").read
