"""Write a traced window with only the START of its device operations as
gzipped JSON, for the recorded-slice tests of ``harness/job_start.py`` and
``harness/mesh_job_start.py``: the ``bench.window`` span, every span of the
program and every executed program of the window as recorded (a few
hundred rows each; the round program is found as the module that ran
longest in the WHOLE window, so the whole window's modules stay), and the
device operations that end up to ``margin_ms`` after the round program's
first execution (on the first device; on the busiest one where there are
several).  Under ``expect``: what ``scoped.reduce_table`` reads as the
job's start, what ``job_start.reduce_table`` cuts it into and, for
several devices, what ``mesh_job_start.reduce_table`` reads on ``device``.
The input is an ``.xplane.pb`` (the newest under
``.bench_cache/trace/<workload>`` where a workload is named instead).

    python3 benchmark/tools/job_start_slice.py <trace|workload> <out.json.gz> [margin_ms]
"""

import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import job_start, mesh_job_start, mesh_trace, scoped  # noqa: E402


def cut(table: dict, margin_ms: float = 20.0) -> dict:
    (_, w0, wd), = [s for s in table["spans"] if s[0] == "bench.window"]
    devices = sorted({m[0] for m in table["modules"]})
    dev = devices[0]
    if len(devices) > 1:
        mesh = mesh_trace.reduce_table(table)
        dev = mesh["devices"][mesh["busiest"]]
    jobs = sorted(s[1] for s in table["program"]
                  if s[0] == "lgbtpu.train" and s[1] >= w0)
    runs = job_start.main_module_runs(table, dev, w0, w0 + wd)
    first = next(r for r in runs if r >= jobs[0])
    end = first + int(margin_ms * 1e6)
    out = {"what": "a traced window: its spans and executed programs whole, "
                   f"its device operations up to {margin_ms} ms after the "
                   "round program's first execution",
           "device": dev,
           "spans": [s for s in table["spans"] if s[0] == "bench.window"],
           "program": [s for s in table["program"]
                       if s[1] + s[2] > w0 and s[1] < w0 + wd],
           "modules": [m for m in table["modules"]
                       if m[1] + m[2] > w0 and m[1] < w0 + wd],
           "ops": [o for o in table["ops"] if w0 <= o[1] and o[1] + o[2] <= end]}
    expect = {"job_start_s": scoped.reduce_table(out)["job_start_s"]
              if dev == devices[0] else None}
    try:
        expect["parts"] = job_start.reduce_table(out)
    except ValueError:
        expect["parts"] = None
    expect["mesh"] = mesh_job_start.reduce_table(out, dev) \
        if len(devices) > 1 else None
    out["expect"] = expect
    return out


def main(argv) -> None:
    path = argv[1]
    if not path.endswith(".pb"):
        files = glob.glob(os.path.join(scoped.ROOT, ".bench_cache", "trace", path,
                                       "**", "*.xplane.pb"), recursive=True)
        path = max(files, key=os.path.getmtime)
    table = cut(scoped.table_of(path), *(float(a) for a in argv[3:4]))
    with gzip.open(argv[2], "wt") as fh:
        json.dump(table, fh, separators=(",", ":"))
    print({k: (len(v) if isinstance(v, list) else v) for k, v in table.items()})


if __name__ == "__main__":
    main(sys.argv)
