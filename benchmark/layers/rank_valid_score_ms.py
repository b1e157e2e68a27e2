"""``valid_score_ms`` in a ranking job (the cell ``istella-rank-train``):
valid scoring and the valid metric (``rank_ndcg_ms`` is the metric's
part). The reader is ``layers/valid_score_ms.py``'s, which says what is
read and from where; an accepted metric's list of cells is not a new
cell's to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "valid_score_ms").read
