"""Device milliseconds per round, on the busiest chip, inside the
collectives the program names: self time of the operations under the
scopes ``hist_allreduce`` (the histograms' ``psum``) and
``stats_allreduce`` (the root's sums, leaf renewal's sums, the leaf
recount).  The exchange itself plus the wait for the slowest chip.  From
this run's trace (harness/mesh_trace.py)."""

from harness import mesh_trace


def read(run):
    _, chip = mesh_trace.busiest(run)
    if chip is None or not chip["collective_by_scope_s"]:
        return None
    return 1000.0 * chip["collective_s"] / run["rounds"]
