"""Host seconds a job spends in the program's span ``rank_bucket_plan``
(objectives.py and metrics.py: the query-length bucket plan, the gains and
labels by slot, the ideal DCGs: the training queries' inside
``booster_init``, the held-out queries' when ``train_fused`` first takes
the metric's operands): the window's spans of that name over
its jobs, from this run's trace (harness/scoped.py keeps the program's
spans).  A job of the warm-up pays the same, in ``setup_s``.  ``None``
without a trace or against a program without the span."""

from harness import scoped


def read(run):
    red = scoped.of_this_run()
    if red is None:
        return None
    spans = red["program_spans"]
    plan, jobs = (spans.get(scoped.PROGRAM + name)
                  for name in ("rank_bucket_plan", "train"))
    if not plan or not jobs:
        return None
    return plan[1] / jobs[0]
