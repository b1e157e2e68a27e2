"""Write the ranking part of a traced window as gzipped JSON, for the
recorded-slice test of ``harness/rank_trace.py``: the device operations
traced under the scopes ``gradients`` or ``valid_metric`` that start in
``[start_s, start_s + seconds)`` counted from the start of the
``bench.window`` span, with a ``bench.window`` of exactly that slice in
place of the real one and, under ``expect``, what
``rank_trace.reduce_table`` reads in it.  The input is an ``.xplane.pb``
(the newest under ``.bench_cache/trace/<workload>`` where a workload is
named instead of a file).

    python3 benchmark/tools/rank_slice.py <trace|workload> <out.json.gz> start_s seconds
"""

import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import rank_trace, scoped  # noqa: E402


def cut(table: dict, start_s: float, seconds: float) -> dict:
    (_, w0, _), = [s for s in table["spans"] if s[0] == "bench.window"]
    a = w0 + int(round(start_s * 1e9))
    b = a + int(round(seconds * 1e9))
    ops = [o for o in table["ops"] if a <= o[1] and o[1] + o[2] <= b
           and rank_trace.scope_of(o[4]) is not None]
    out = {"what": f"the operations under gradients and valid_metric in "
                   f"{seconds} s of a traced window, from {start_s} s after "
                   "its start",
           "ops": ops, "spans": [["bench.window", a, b - a]]}
    out["expect"] = rank_trace.reduce_table(out)["scope_s"]
    return out


def main(argv) -> None:
    path = argv[1]
    if not path.endswith(".pb"):
        files = glob.glob(os.path.join(scoped.ROOT, ".bench_cache", "trace", path,
                                       "**", "*.xplane.pb"), recursive=True)
        path = max(files, key=os.path.getmtime)
    table = cut(scoped.table_of(path), float(argv[3]), float(argv[4]))
    with gzip.open(argv[2], "wt") as fh:
        json.dump(table, fh, separators=(",", ":"))
    print({k: (len(v) if isinstance(v, list) else v) for k, v in table.items()})


if __name__ == "__main__":
    main(sys.argv)
