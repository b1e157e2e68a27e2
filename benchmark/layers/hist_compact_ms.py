"""Device milliseconds per round under the scope ``hist_compact``,
wherever it sits (a round's pass or the root pass): every movement of
data that prepares a histogram kernel's operands: selection keys, the
payload concatenate, its sort, the row gather, the pads.  Innermost-scope
self time from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "hist_compact")
