"""Write the plain rows of a trace (``harness/scoped.py`` ``table_of``:
device operations with their scope paths, executed programs, the
harness's and the program's host spans) as gzipped JSON: the whole
trace, or the slice ``[start_s, start_s + seconds)`` counted from the
start of the ``bench.window`` span, with a ``bench.window`` of exactly
that slice in place of the real one and, under ``expect``, what
``scoped.reduce_table`` reads in it (the recorded slices under
``tests/data/`` pin those).  The input is an ``.xplane.pb`` or a table
this tool wrote before.

    python3 benchmark/tools/trace_slice.py <trace> <out.json.gz> [start_s seconds [rows_full]]
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import scoped  # noqa: E402


def load(path: str) -> dict:
    if path.endswith(".pb"):
        return scoped.table_of(path)
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def cut(table: dict, start_s: float, seconds: float, rows_full=None) -> dict:
    (_, w0, _), = [s for s in table["spans"] if s[0] == "bench.window"]
    a = w0 + int(round(start_s * 1e9))
    b = a + int(round(seconds * 1e9))

    def inside(start, dur):
        return start + dur > a and start < b
    out = {
        "what": f"{seconds} s of a traced window, from {start_s} s after its start",
        "ops": [o for o in table["ops"] if inside(o[1], o[2])],
        "modules": [m for m in table["modules"] if inside(m[1], m[2])],
        "spans": [["bench.window", a, b - a]]
        + [s for s in table["spans"] if s[0] != "bench.window"
           and inside(s[1], s[2])],
        "program": [s for s in table["program"] if inside(s[1], s[2])]}
    r = scoped.reduce_table(out, rows_full)
    roots = sum(1 for o in out["ops"] if "tree_root" in o[4].split("/")
                and o[4].rstrip(":").endswith(scoped.KERNEL_CALL)
                and a <= o[1] < b)
    out["expect"] = {
        "passes": r["passes"], "partition_passes": r["partition_passes"],
        "root_passes": roots,
        "round_hist_s": sum(r["round_hist_s"].get(k, 0.0) for k in
                            ("hist_compact", "hist_kernel", "hist_update"))}
    return out


def main(argv) -> None:
    table = load(argv[1])
    if len(argv) > 3:
        table = cut(table, float(argv[3]), float(argv[4]),
                    int(argv[5]) if len(argv) > 5 else None)
    with gzip.open(argv[2], "wt") as fh:
        json.dump(table, fh, separators=(",", ":"))
    print({k: (len(v) if isinstance(v, list) else v) for k, v in table.items()})


if __name__ == "__main__":
    main(sys.argv)
