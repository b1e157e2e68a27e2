"""``tree_root_ms`` in a categorical job (the cell ``allstate-cat-train``):
device time under ``tree_root``. The reader is
``layers/tree_root_ms.py``'s, which says what is read and from where; an
accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "tree_root_ms").read
