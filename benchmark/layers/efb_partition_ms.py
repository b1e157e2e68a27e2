"""Device milliseconds per round under the scope ``partition`` in a
bundled job: the fused partition + key kernel routing rows by range
predicates on the bundle columns, and its pads.  Innermost-scope self
time from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "partition")
