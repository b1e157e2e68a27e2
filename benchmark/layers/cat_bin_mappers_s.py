"""Host seconds of the dense ``Dataset.construct`` spent on the
categorical columns, in the program's span ``cat_bin_mappers``
(io/dataset.py: each such column's level counts in
``_construct_mappers`` and its bins in ``_bin_matrix``, the valid set's
too), from the program's own timer table, which the driver switches on
around ``construct``.  ``None`` against a program without the span."""


def read(run):
    return (run.get("setup_spans_s") or {}).get("cat_bin_mappers")
