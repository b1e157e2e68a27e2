"""``partition_ms`` in a ranking job (the cell ``istella-rank-train``):
everything under the scope ``partition``. The reader is
``layers/partition_ms.py``'s, which says what is read and from where; an
accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "partition_ms").read
