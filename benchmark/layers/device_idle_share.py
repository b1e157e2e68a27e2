"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of the device operations' intervals / window)."""


def read(run):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
