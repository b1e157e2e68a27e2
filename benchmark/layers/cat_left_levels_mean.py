"""Levels in the left set of a sorted-subset split, on average:
``cat_left_levels`` / ``cat_subset_splits`` of the traced window's
``dispatch_done`` spans (harness/cat_trace.py); at most
``max_cat_threshold``.  How far the sets are from one level against the
rest, which a one-hot coding can state in one split.  ``None`` against a
program without the counts."""

from harness import cat_trace


def read(run):
    return cat_trace.ratio("cat_left_levels", "cat_subset_splits")
