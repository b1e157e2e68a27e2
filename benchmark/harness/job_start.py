"""The job's start in four contiguous parts (PR 40): from the start of an
``lgb.train`` job (the program's span ``lgbtpu.train``) to the first
execution of its round program (line ``XLA Modules``), which is what
``job_start_ms`` reads whole (``harness/scoped.py``).  From this run's
trace, per job of the window, on the first device:

``init``   the job's open to the open of ``train_fused``, less the
           ``place`` spans in it: ``booster_init`` (the booster, the
           objective, the valid sets) without its placements
``place``  the job's ``place`` spans before the first execution
           (``GBDT._place_rows`` / ``_place_whole``: the enqueue of the
           bins', words', scores' and labels' copies to the device)
``call``   the open of ``train_fused`` to the close of its first
           ``fused_round_scan``: the operands, the runner's lookup, the
           call into the round program (tracing, lowering, compiling or
           loading it where the process has not) until it RETURNS
``wait``   that close to the round program's first execution: the host
           has handed the work over and the device has not started

The four add up to ``job_start_ms`` by construction (a part never
counts a ``place`` span twice, and a first execution that starts before
the call returns ends the call there).  The ``job_start:`` line on
stderr gives all four a job and also names what sits directly inside
``booster_init`` and ``train_fused`` before the first execution (count,
seconds) and every placement (``what``, bytes, ms).

Three of the four are per-layer metrics (``layers/job_start_init_ms``,
``_call_ms``, ``_wait_ms``).  ``place`` is on the line only: a ``place``
span holds the ENQUEUE of a copy (1-3.5 ms for 0.3-2.1 GB; PR 40's chip
runs), not its arrival, which the device waits for before the first
programs that read the arrays start, so the copies' cost lies in
``wait`` and no span can size it.

``wait`` reads differently by the kind of process, and mostly for the
profiler's sake: in a process that LOADED the round program from the
persistent cache a traced window's first execution starts 0.9-2.1 s
after the call returned (the profiler's own work: 6-15 s of system CPU
time in that window), in one that COMPILED it 0.17-0.45 s; untraced the
loading process starts the sooner.  Compare a ``wait`` only with one of
the same kind: the line's ``round_program`` says ``loaded`` or
``compiled``.

Against a program without the span ``place`` (the parent of PR 40) the
parts cannot be told apart and every reader returns ``None``.
"""

from __future__ import annotations

import json
import sys

from . import compile_table, scoped
from .tracered import BENCH

P = scoped.PROGRAM


def main_module_runs(table: dict, dev: int, w0: int, w1: int) -> list:
    """Starts of the round program's executions on ``dev`` inside the
    window: the module that ran longest there (as ``scoped.py``)."""
    mods = [m for m in table["modules"] if m[0] == dev
            and m[1] + m[2] > w0 and m[1] < w1]
    total = {}
    for _, _, dur, name in mods:
        total[name] = total.get(name, 0) + dur
    if not total:
        return []
    main = max(total, key=total.get)
    return sorted(m[1] for m in mods if m[3] == main)


def _overlap(spans, a: int, b: int) -> int:
    return sum(max(0, min(s + d, b) - max(s, a)) for _, s, d, _ in spans)


def split_job(program: list, s0: int, first: int):
    """One job's parts in ns, or ``None`` where a span is missing."""
    inside = [s for s in program if s0 <= s[1] < first]
    fused = next((s for s in inside if s[0] == P + "train_fused"), None)
    scans = [s for s in inside if s[0] == P + "fused_round_scan"]
    places = [s for s in inside if s[0] == P + "place"]
    if fused is None or not scans or not places:
        return None
    t0 = fused[1]
    c1 = min(scans[0][1] + scans[0][2], first)
    return {"init": (t0 - s0) - _overlap(places, s0, t0),
            "place": _overlap(places, s0, first),
            "call": (c1 - t0) - _overlap(places, t0, c1),
            "wait": (first - c1) - _overlap(places, c1, first)}


def window_of(table: dict):
    """``(start, end, the program's spans that touch it, by start)`` of
    the ``bench.window`` span; refuses a table without one or without an
    executed program."""
    window = [s for s in table["spans"] if s[0] == BENCH + "window"]
    if not window or not table["modules"]:
        raise ValueError("the trace holds no bench.window span or no program")
    w0, w1 = window[0][1], window[0][1] + window[0][2]
    program = sorted((s for s in table.get("program", [])
                      if s[1] + s[2] > w0 and s[1] < w1), key=lambda s: s[1])
    return w0, w1, program


def reduce_table(table: dict) -> dict:
    w0, w1, program = window_of(table)
    dev = min(m[0] for m in table["modules"])
    runs = main_module_runs(table, dev, w0, w1)
    jobs, children, places = [], {}, []
    for name, s0, dur, _ in program:
        if name != P + "train":
            continue
        first = next((r for r in runs if r >= s0), None)
        if first is None or first >= s0 + dur:
            continue
        parts = split_job(program, s0, first)
        if parts is None:
            continue
        jobs.append({"job_start": first - s0, **parts})
        for i, (child, a, d, counts) in enumerate(program):
            if not s0 <= a < first:
                continue
            if child == P + "place":
                places.append([str(counts.get("what", "?")),
                               int(counts.get("bytes", 0) or 0),
                               round(d / 1e6, 3)])
            elif scoped.span_depth(i, program) == 2:
                c = children.setdefault(child[len(P):], [0, 0.0])
                c[0] += 1
                c[1] += (min(a + d, first) - a) / 1e9
    return {"jobs": jobs, "places": places,
            "children_s": {k: [c[0], round(c[1], 6)]
                           for k, c in children.items()}}


_THIS_RUN = []


def of_this_run():
    """The reduction of this run's trace (read once a process), or
    ``None``: no trace, no window, or a program without the spans."""
    if not _THIS_RUN:
        path, out = scoped.find_trace(), None
        if path is not None:
            try:
                out = reduce_table(scoped.table_of(path))
            except ValueError:
                out = None
        if out is not None and not out["jobs"]:
            out = None
        _THIS_RUN.append(out)
        if out is not None:
            print("job_start: " + json.dumps(
                {"round_program": compile_table.round_program_kind(
                    compile_table.rows()),
                 "jobs_ms": [{k: round(v / 1e6, 3) for k, v in j.items()}
                             for j in out["jobs"]],
                 "under_booster_init_and_train_fused": out["children_s"],
                 "places_what_bytes_ms": out["places"]}),
                file=sys.stderr, flush=True)
    return _THIS_RUN[0]


def part_ms(part: str):
    """Milliseconds a job of the window spent in ``part``, or ``None``."""
    red = of_this_run()
    if red is None:
        return None
    return sum(j[part] for j in red["jobs"]) / len(red["jobs"]) / 1e6
