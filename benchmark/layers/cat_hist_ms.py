"""``hist_ms`` in a categorical job (the cell ``allstate-cat-train``):
device milliseconds per round under ``round_hist``, self time of
everything under the scope. The reader is ``layers/efb_hist_ms.py``'s,
which says what is read and from where; an accepted metric's list of
cells is not a new cell's to extend, so the cell reports it under a name
of its own."""

from harness import load_module

read = load_module("layers", "efb_hist_ms").read
