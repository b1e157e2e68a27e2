"""Device milliseconds per round under the scope ``tree_root`` (what a
tree does before its round loop: the bins' transpose, the root split), NOT
counting the root histogram's kernel, which is under ``hist_kernel``:
innermost-scope self time from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    return scoped.scope_ms_per_round(run, "tree_root")
