"""Device milliseconds per round under the scope ``cat_bitset`` inside
``find_splits`` (the winners' sets of left bins read off the scan's own
order, and their packing to 8 words of 32 bins a slot for the partition
kernel), innermost-scope self time from this run's trace
(harness/cat_trace.py).  ``None`` against a program without the scope."""

from harness import cat_trace


def read(run):
    return cat_trace.scope_ms_per_round(run, "cat_bitset")
