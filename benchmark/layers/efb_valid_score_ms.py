"""Device milliseconds per round under the scopes ``valid_score`` and
``valid_metric`` in a bundled job: scoring the held-out rows with the
new tree on their bundle columns, and the device AUC.  Innermost-scope
self time from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "valid_score", "valid_metric")
