"""Device milliseconds per round under no named scope at all in a
bundled job: busy time minus the self time of every scope
(harness/scoped.py).  Nothing of the bundles' work may hide here."""

from harness import scoped


def read(run):
    red = scoped.of_this_run()
    if red is None or not run.get("rounds") or not red["scope_s"]:
        return None
    return 1000.0 * (red["busy_s"] - sum(red["scope_s"].values())) / run["rounds"]
