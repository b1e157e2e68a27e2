"""Host seconds in the program's span ``bundle_plan`` (io/dataset.py:
the greedy search for feature bundles over every row's conflicts), from
the program's own timer table, which the driver switches on around
``construct``.  ``None`` against a program without the span."""


def read(run):
    return (run.get("setup_spans_s") or {}).get("bundle_plan")
