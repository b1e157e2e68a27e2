"""Device milliseconds per round under the scope ``hist_kernel``,
wherever it sits (a round's pass or the root pass): the histogram kernels
of every branch of the row ladder, the full masked pass included, and
what unpacks their output.  Innermost-scope self time from this run's
trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "hist_kernel")
