"""Share of the traced window in which no operation ran on the busiest
chip of a data-parallel job: 100 * (1 - union of that chip's operations'
intervals / window).  From this run's trace (harness/mesh_trace.py)."""

from harness import mesh_trace


def read(run):
    red, chip = mesh_trace.busiest(run)
    if chip is None or chip["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - chip["busy_s"] / red["window_s"])
