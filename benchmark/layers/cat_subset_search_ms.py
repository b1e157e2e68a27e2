"""Device milliseconds per round under the scope ``cat_subset`` inside
``find_splits`` (ops/split.py: the sorted-subset scan of the categorical
columns that take it, two stable sorts a child that carry the sums and
the bin index with the key, the cumulative sums and the gains),
innermost-scope self time from this run's trace (harness/cat_trace.py).
``None`` against a program without the scope."""

from harness import cat_trace


def read(run):
    return cat_trace.scope_ms_per_round(run, "cat_subset")
