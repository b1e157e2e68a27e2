"""Device milliseconds per round under the scope ``rank_gather`` of a
ranking job (objectives.py: the score gathered through every bucket's
slot matrix), innermost-scope self time from this run's trace
(harness/rank_trace.py)."""

from harness import rank_trace


def read(run):
    return rank_trace.scope_ms_per_round(run, "rank_gather")
