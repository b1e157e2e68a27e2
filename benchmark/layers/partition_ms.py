"""Device milliseconds per round under the scope ``partition`` (partition, sort and gather):
self time of the device operations whose name path carries the scope,
from the profiler's trace of the window."""


def read(run):
    trace = run["trace"]
    if not trace or "partition" not in trace["scope_s"]:
        return None
    return 1000.0 * trace["scope_s"]["partition"] / run["rounds"]
