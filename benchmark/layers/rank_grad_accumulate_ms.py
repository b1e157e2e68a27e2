"""Device milliseconds per round under the scope ``rank_accumulate`` of a
ranking job (objectives.py: the buckets' per-slot gradients back onto
the docs, one gather through ``slot_of_doc``), innermost-scope self time
from this run's trace (harness/rank_trace.py)."""

from harness import rank_trace


def read(run):
    return rank_trace.scope_ms_per_round(run, "rank_accumulate")
