"""Seconds the warm-up's first dispatch took beyond a warm dispatch:
compiling the round program, or loading it from the persistent cache
(host clock: call into the warm-up job until its first trees are on the
host, minus the window's mean dispatch)."""


def read(run):
    if not run["dispatch_s"]:
        return None
    warm = sum(run["dispatch_s"]) / len(run["dispatch_s"])
    return run["phases"]["warmup_first_dispatch_s"] - warm
