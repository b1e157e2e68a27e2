"""What the program compiled, by program, stage and span (PR 40).

The program's compile-event listener (``lightgbm_tpu/obs/
compile_events.py``) keeps, for the process's whole life and with nothing
switched on, a table of the stages jax announced: rows ``span`` (the
outermost ``lgbtpu.*`` span open on the thread, or
``outside_the_program``), ``inside`` (the innermost one), ``program``
(jax's ``fun_name``), ``stage`` (``trace``, ``lower``, ``compile`` with
the cache retrieval taken out, ``cache_load``), ``seconds``, ``count``.
Set-up lies outside the profiler session, so this table, not the trace,
is what the ``program_*`` readers (``layers/``) read.

Only rows UNDER A PROGRAM SPAN count (``construct``, ``train`` and what
nests in them): the plain reference's program, which the comparison
compiles after the window under no span, is out of them, where the older
``lower_s`` and ``compile_or_load_s`` (process totals read after the
comparison) hold it.

The round program is the one program that runs the rounds: of the
programs traced or lowered directly inside the span that calls it
(``fused_round_scan``; in the per-iteration loop ``collective_grow_
dispatch``, else ``tree_growth``), the one with the most seconds there.

Against a program without the table (the parent of PR 40) ``rows()``
and every reader return ``None``.
"""

from __future__ import annotations

import json
import sys

OUTSIDE = "outside_the_program"
ROUND_SPANS = ("fused_round_scan", "collective_grow_dispatch", "tree_growth")
STAGES = ("trace", "lower", "compile", "cache_load")
LOWERING = STAGES[:2]


def rows():
    """The program's table as it stands, or ``None`` where the program
    keeps none."""
    try:
        from lightgbm_tpu.obs import compile_events
        table = compile_events.table
    except (ImportError, AttributeError):
        return None
    out = table()
    say(out)
    return out


def under_spans(table: list) -> list:
    return [r for r in table if r["span"] != OUTSIDE]


def by_stage(rows: list) -> dict:
    return {s: float(sum(r["seconds"] for r in rows if r["stage"] == s))
            for s in STAGES}


def stage_seconds(table, stage: str):
    """Seconds of ``stage`` under the program's spans."""
    if table is None:
        return None
    return by_stage(under_spans(table))[stage]


def lowerings(table):
    """Programs lowered under the program's spans: one a trace-cache
    miss, from a cold persistent cache or a warm one."""
    if table is None:
        return None
    return int(sum(r["count"] for r in under_spans(table)
                   if r["stage"] == "lower"))


def round_program(table):
    """Name of the program that runs the rounds, or ``None``."""
    for span in ROUND_SPANS:
        seconds = {}
        for r in table:
            if r["inside"] == span and r["stage"] in LOWERING:
                seconds[r["program"]] = seconds.get(r["program"], 0.0) \
                    + r["seconds"]
        if seconds:
            return max(seconds, key=seconds.get)
    return None


def round_program_kind(table):
    """``loaded`` where the persistent cache served the round program
    to this process, ``compiled`` where the backend made it, or
    ``None``: the two kinds of process start a traced job differently
    (``job_start.py``)."""
    name = round_program(table) if table else None
    if name is None:
        return None
    return "loaded" if any(r["program"] == name and r["stage"] == "cache_load"
                           for r in table) else "compiled"


def round_program_lower_s(table):
    """Trace + lower seconds of the round program over ALL its lowerings
    in the process (the warm-up's dispatches, the window's job)."""
    if table is None:
        return None
    name = round_program(table)
    if name is None:
        return None
    return float(sum(r["seconds"] for r in table
                     if r["program"] == name and r["stage"] in LOWERING))


def by_program(table: list, top: int = 10) -> list:
    """The ``top`` programs of most trace + lower seconds under the
    program's spans: seconds and counts by stage, the spans they ran
    directly inside."""
    progs = {}
    for r in under_spans(table):
        p = progs.setdefault(r["program"], {"program": r["program"],
                                            "trace_lower_s": 0.0,
                                            "inside": []})
        p[r["stage"] + "_s"] = round(p.get(r["stage"] + "_s", 0.0)
                                     + r["seconds"], 6)
        p[r["stage"] + "_n"] = p.get(r["stage"] + "_n", 0) + r["count"]
        if r["stage"] in LOWERING:
            p["trace_lower_s"] += r["seconds"]
        if r["inside"] not in p["inside"]:
            p["inside"].append(r["inside"])
    out = sorted(progs.values(), key=lambda p: -p["trace_lower_s"])[:top]
    for p in out:
        p["trace_lower_s"] = round(p["trace_lower_s"], 6)
    return out


def summary(table: list) -> dict:
    def rounded(d):
        return {k: round(v, 6) for k, v in d.items()}
    inside = under_spans(table)
    total = rounded(by_stage(inside))
    outside = rounded(by_stage([r for r in table if r["span"] == OUTSIDE]))
    by_span = {}
    for r in inside:
        if r["stage"] in LOWERING:
            by_span[r["inside"]] = by_span.get(r["inside"], 0.0) + r["seconds"]
    return {"under_program_spans_s": total, "outside_the_program_s": outside,
            "programs_lowered": lowerings(table),
            "round_program": round_program(table),
            "round_program_kind": round_program_kind(table),
            "round_program_lower_s": round_program_lower_s(table),
            "trace_lower_by_innermost_span_s": rounded(dict(
                sorted(by_span.items(), key=lambda kv: -kv[1])[:10])),
            "top_programs": by_program(table)}


_SAID = []


def say(table: list) -> None:
    """Once a process, to stderr on a ``compile_table:`` line beside the
    ``scoped:`` line."""
    if _SAID:
        return
    _SAID.append(True)
    print("compile_table: " + json.dumps(summary(table)), file=sys.stderr,
          flush=True)
