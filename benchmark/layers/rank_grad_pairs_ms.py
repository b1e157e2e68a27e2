"""Device milliseconds per round under the scope ``rank_pairs`` of a
ranking job (objectives.py: the ``[queries, T, Q]`` pair step, |dNDCG|,
the sums onto sorted positions and the normalisation), innermost-scope
self time from this run's trace (harness/rank_trace.py)."""

from harness import rank_trace


def read(run):
    return rank_trace.scope_ms_per_round(run, "rank_pairs")
