"""Device milliseconds per round under the scope ``find_splits`` (the split search):
self time of the device operations whose name path carries the scope,
from the profiler's trace of the window."""


def read(run):
    trace = run["trace"]
    if not trace or "find_splits" not in trace["scope_s"]:
        return None
    return 1000.0 * trace["scope_s"]["find_splits"] / run["rounds"]
