"""``correct`` from the numbers a comparison worked out: each against the
limit the configuration's file gives it.  The comparison itself is the
configuration's (``comparisons/<name>.py``)."""

from __future__ import annotations

import numpy as np


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, compared)``: every number beside its limit, in the
    limits' order.  A number that is missing or not finite fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        compared[name] = {"value": value, "limit": limit}
    return ok, compared
