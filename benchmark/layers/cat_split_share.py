"""Of the splits of the window's trees, the share on a categorical
column: 100 x ``cat_splits`` / ``splits``, as the program's
``dispatch_done`` spans of the traced window counted them from the trees
each dispatch brought back (harness/cat_trace.py).  What upstream's
recommendation is worth to the trees on these rows: at 0 the categorical
columns do nothing and the cell is ``allstate-train`` without its
indicator columns.  ``None`` against a program without the counts."""

from harness import cat_trace


def read(run):
    return cat_trace.ratio("cat_splits", "splits", 100.0)
