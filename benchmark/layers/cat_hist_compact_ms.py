"""``hist_compact_ms`` in a categorical job (the cell ``allstate-cat-
train``): the compaction's device time. The reader is
``layers/hist_compact_ms.py``'s, which says what is read and from where;
an accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "hist_compact_ms").read
