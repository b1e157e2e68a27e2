"""Host seconds in ``lgb.Dataset(csr).construct()`` for the training rows
and the valid set (host clock around the two calls): CSR to CSC, the bin
mappers, the bundle plan, the bundled matrix."""


def read(run):
    return run["phases"].get("construct_s")
