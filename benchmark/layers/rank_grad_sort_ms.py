"""Device milliseconds per round under the scope ``rank_sort`` of a
ranking job (objectives.py: every bucket's stable sort by score with the
gains, labels and slots as operands, and the sort that brings the
results back to the slots), innermost-scope self time from this run's
trace (harness/rank_trace.py)."""

from harness import rank_trace


def read(run):
    return rank_trace.scope_ms_per_round(run, "rank_sort")
