"""The job's start of a cell on several chips (PR 40), beside
``mesh_trace.py``: from the open of the program's span ``lgbtpu.train``
to the first execution of the tree program on the BUSIEST chip (the
module that ran longest there inside the window, line ``XLA Modules``),
per job of the window, and the ``place`` spans in it
(``GBDT._place_rows`` / ``_place_whole``: each shard of the training bins
from the host to its chip, the valid bins whole to every chip, the scores
and the labels).  The per-iteration loop has no ``train_fused``, so the
one-chip cells' four parts (``job_start.py``) do not exist here; what the
start holds beside its placements is on the ``job_start:`` line by the
spans directly under the job.

Against a program without the span ``place`` (the parent of PR 40)
every reader returns ``None``.
"""

from __future__ import annotations

import json
import sys

from . import job_start, mesh_trace, scoped

P = scoped.PROGRAM


def reduce_table(table: dict, dev: int) -> dict:
    w0, w1, program = job_start.window_of(table)
    runs = job_start.main_module_runs(table, dev, w0, w1)
    jobs, under = [], {}
    for name, s0, dur, _ in program:
        if name != P + "train":
            continue
        first = next((r for r in runs if r >= s0), None)
        if first is None or first >= s0 + dur:
            continue
        places = [s for s in program if s[0] == P + "place"
                  and s0 <= s[1] < first]
        if not places:
            continue
        jobs.append({"job_start": first - s0,
                     "place": job_start._overlap(places, s0, first),
                     "placed_bytes": sum(int(s[3].get("bytes", 0) or 0)
                                         for s in places)})
        for i, (child, a, d, _) in enumerate(program):
            if s0 <= a < first and scoped.span_depth(i, program) == 1:
                under[child[len(P):]] = under.get(child[len(P):], 0.0) \
                    + (min(a + d, first) - a) / 1e9
    return {"jobs": jobs, "device": dev,
            "under_the_job_s": {k: round(v, 6) for k, v in under.items()}}


_THIS_RUN = []


def of_this_run():
    if not _THIS_RUN:
        path, out = scoped.find_trace(), None
        mesh = mesh_trace.of_this_run()
        if path is not None and mesh is not None:
            try:
                out = reduce_table(scoped.table_of(path),
                                   mesh["devices"][mesh["busiest"]])
            except ValueError:
                out = None
        if out is not None and not out["jobs"]:
            out = None
        _THIS_RUN.append(out)
        if out is not None:
            print("job_start: " + json.dumps(
                {"busiest_chip": out["device"],
                 "jobs_ms": [{k: round(v / 1e6, 3) if k != "placed_bytes"
                              else v for k, v in j.items()}
                             for j in out["jobs"]],
                 "under_the_job_s": out["under_the_job_s"]}),
                file=sys.stderr, flush=True)
    return _THIS_RUN[0]


def part_ms(part: str):
    red = of_this_run()
    if red is None:
        return None
    return sum(j[part] for j in red["jobs"]) / len(red["jobs"]) / 1e6
