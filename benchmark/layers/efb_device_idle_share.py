"""Share of the traced window in which no operation ran on the device,
in a bundled job: 100 * (1 - union of the device operations' intervals /
window), from this run's trace (harness/scoped.py)."""

from harness import scoped


def read(run):
    red = scoped.of_this_run()
    if red is None or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
