"""Device milliseconds per round outside the three named scopes:
gradients, quantisation, score update, valid scoring and the AUC (busy
time minus the scopes' self time, from the profiler's trace)."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["scope_s"]:
        return None
    return 1000.0 * (trace["busy_s"] - sum(trace["scope_s"].values())) / run["rounds"]
