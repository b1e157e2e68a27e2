"""Device milliseconds per round under the scope ``round_hist`` on the
BUSIEST chip of a data-parallel job (the histogram kernels, compaction
and update on that chip's rows, the ``psum`` and the wait in it
included): to be read beside ``hist_ms`` of the one-chip cell at the
same rows a chip.  From this run's trace (harness/mesh_trace.py)."""

from harness import mesh_trace


def read(run):
    _, chip = mesh_trace.busiest(run)
    if chip is None or chip["hist_s"] <= 0:
        return None
    return 1000.0 * chip["hist_s"] / run["rounds"]
