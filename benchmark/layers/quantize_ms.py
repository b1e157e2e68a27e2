"""Device milliseconds per round under the scope ``quantize`` (the int8
stochastic gradient quantisation and its scales), innermost-scope self
time from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "quantize")
