"""``valid_score_ms`` in a categorical job (the cell ``allstate-cat-
train``): device milliseconds per round under ``valid_score`` and
``valid_metric``: scoring the held-out rows with the new tree (a
categorical node by its set of bins) and the device AUC. The reader is
``layers/efb_valid_score_ms.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "efb_valid_score_ms").read
