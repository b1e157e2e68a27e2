"""Device milliseconds per round under the scope ``round_hist`` in a
bundled job (kernels, compaction, update, over the bundle columns):
beside ``criteo-train``'s ``hist_ms``.  Self time of everything under
the scope, from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    red = scoped.of_this_run()
    if red is None or not run.get("rounds") or not red["round_hist_s"]:
        return None
    return 1000.0 * sum(red["round_hist_s"].values()) / run["rounds"]
