"""Milliseconds per round between the chip that computed longest and
the chip that computed least: the spread of device busy time OUTSIDE the
named collectives (a chip that reaches a ``psum`` early waits inside its
all-reduce, so busy time with the collectives in it reads alike on every
chip).  What the row ladder's per-shard buckets cost at the ``psum``.
From this run's trace (harness/mesh_trace.py)."""

from harness import mesh_trace


def read(run):
    red, chip = mesh_trace.busiest(run)
    if chip is None or len(red["chips"]) < 2:
        return None
    return 1000.0 * red["compute_spread_s"] / run["rounds"]
