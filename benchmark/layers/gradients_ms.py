"""Device milliseconds per round under the scope ``gradients`` (the
objective's gradients and the in-jit bagging/GOSS draw): self time of the
device operations whose innermost named scope it is, from this run's
trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "gradients")
