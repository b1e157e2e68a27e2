"""Milliseconds per round in which the busiest chip ran no program: the
gaps between the executed programs of one round (the per-iteration loop
runs several) and between rounds, from the trace's line of executed
programs; the ``scoped:`` line names each gap by the ``lgbtpu.*`` span
open in it.  From this run's trace (harness/mesh_trace.py)."""

from harness import mesh_trace


def read(run):
    _, chip = mesh_trace.busiest(run)
    if chip is None or not chip["programs_run"]:
        return None
    return 1000.0 * chip["between_programs_s"] / run["rounds"]
