"""Device milliseconds per round under no named scope at all: busy time
minus the self time of every scope, PR 27's three and PR 28's (from this
run's trace, harness/scoped.py).  ``None`` against a program that has
none of the newer scopes: there ``unscoped_device_ms`` says it."""

from harness import scoped


def read(run):
    red = scoped.of_this_run()
    if red is None or not run.get("rounds") \
            or not set(red["scope_s"]) - set(scoped.OLD_SCOPES):
        return None
    return 1000.0 * (red["busy_s"] - sum(red["scope_s"].values())) / run["rounds"]
