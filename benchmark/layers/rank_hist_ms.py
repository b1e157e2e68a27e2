"""``hist_ms`` in a ranking job (the cell ``istella-rank-train``):
everything under the scope ``round_hist``. The reader is
``layers/hist_ms.py``'s, which says what is read and from where; an
accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "hist_ms").read
