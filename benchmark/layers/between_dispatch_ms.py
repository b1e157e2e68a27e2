"""Device-idle milliseconds between one execution of the round program
and the next (the trees coming to the host, the callbacks, the next
dispatch), per boundary; from the trace's line of executed programs."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["between_dispatch_s"]:
        return None
    gaps = trace["between_dispatch_s"]
    return 1000.0 * sum(gaps) / len(gaps)
