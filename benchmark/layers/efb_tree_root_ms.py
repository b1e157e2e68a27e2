"""``tree_root_ms`` in a bundled job (the cell ``allstate-train``): what a
tree does before its round loop (the root's split search in bundle space
among it), without the root histogram's kernel. The reader is
``layers/tree_root_ms.py``'s, which says what is read and from where; an
accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "tree_root_ms").read
