"""Device milliseconds per round under the scopes ``valid_score`` and
``valid_metric`` (scoring the held-out rows with the new tree, the device
AUC, the early-stop state), innermost-scope self time from this run's
trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "valid_score", "valid_metric")
