"""Host seconds in ``lgb.Dataset(...).construct()`` for the training rows
and the valid set (host clock around the two calls)."""


def read(run):
    return run["phases"].get("construct_s")
