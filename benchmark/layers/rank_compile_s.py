"""``compile_s`` in a ranking job (the cell ``istella-rank-train``): what
the warm-up's first dispatch took beyond a warm one: compiling the round
program or loading it from the cache. The reader is
``layers/compile_s.py``'s, which says what is read and from where; an
accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "compile_s").read
