"""Of the training set's categorical cells (rows x categorical columns),
the share in a column's other bin, a level beyond the 254 kept: 100 x
``cat_other_rows`` / (rows x ``cat_features``), the program's own
counters of the job (obs/metrics.py), which the driver reads from the
booster's registry in its path check.  Those rows go right at every
categorical node of their column.  ``None`` against a program without
the counters."""


def read(run):
    counts = run.get("cat_counts") or {}
    if not counts.get("cat_features") or not counts.get("rows"):
        return None
    return 100.0 * counts["cat_other_rows"] / (
        counts["rows"] * counts["cat_features"])
