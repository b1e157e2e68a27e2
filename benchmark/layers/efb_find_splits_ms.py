"""Device milliseconds per round under the scope ``find_splits`` in a
bundled job: the split search over the physical bundle columns (scope
``bundle_search`` inside it) and the children's bookkeeping around it.
Innermost-scope self time from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "find_splits")
