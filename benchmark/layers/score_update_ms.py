"""Device milliseconds per round under the scopes ``leaf_renew`` and
``score_update`` (leaf values from the true gradients, shrinkage, the
gather of a leaf value per row, the add into the scores), innermost-scope
self time from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "leaf_renew", "score_update")
