"""``score_update_ms`` in a categorical job (the cell ``allstate-cat-
train``): device time under ``score_update``. The reader is
``layers/score_update_ms.py``'s, which says what is read and from where;
an accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "score_update_ms").read
