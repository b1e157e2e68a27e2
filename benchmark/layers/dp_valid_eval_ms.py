"""Host milliseconds per round in the program's spans ``valid_eval``
(scoring the valid set with the new tree: ``GBDT.train_one_iter``) and
``metric_eval`` (the metric, which waits for those scores and brings one
number to the host: ``engine.py``): what the valid set adds to a round
of the per-iteration loop.  From the program's spans in this run's trace
(harness/mesh_trace.py)."""

from harness import mesh_trace


def read(run):
    red, chip = mesh_trace.busiest(run)
    if chip is None or mesh_trace.VALID_SPANS[0] not in red["program_spans"]:
        return None
    return 1000.0 * mesh_trace.span_s(red, *mesh_trace.VALID_SPANS) \
        / run["rounds"]
