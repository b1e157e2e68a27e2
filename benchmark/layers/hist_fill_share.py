"""Of the rows the histogram passes of the window were handed, the
share they had to read, in percent.  Needed: the program's count
``hist_rows_selected`` (per tree its rows for the root pass plus each
split's smaller child), summed over the window's ``lgbtpu.dispatch_done``
spans.  Handed: over the executed ``hist_rows_*`` branches, their static
row count (``full`` = the cell's rows), root passes on both sides.  From
this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    red = scoped.of_this_run()
    if red is None or not red["rows_selected"] or not red["rows_handed"]:
        return None
    return 100.0 * red["rows_selected"] / red["rows_handed"]
