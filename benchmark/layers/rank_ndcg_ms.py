"""Device milliseconds per round under the scope ``valid_metric`` of a
ranking job: the held-out docs' NDCG at every cut-off (metrics.py: the
score gathered through the valid set's slot matrices and sorted per
query under ``ndcg_sort``, the DCG sums after it).  Self time of the
scope and of what is nested in it, from this run's trace
(harness/rank_trace.py); ``None`` against a program without the ranking
scopes."""

from harness import rank_trace


def read(run):
    return rank_trace.scope_ms_per_round(run, "valid_metric", "ndcg_sort")
