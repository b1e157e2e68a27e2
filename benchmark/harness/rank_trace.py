"""The window's trace once more, for what a RANKING job names itself:
device time by the innermost of the scopes the program opens inside
``gradients`` and ``valid_metric`` (objectives.py, metrics.py), which
``scoped.py``'s ``NAMED`` does not know and so counts as ``gradients``
and ``valid_metric`` whole:

    gradients > rank_gather      the score through the slot matrices
                rank_sort        each bucket's sort by score and the sort
                                 back to the slots
                rank_pairs       the [queries, T, Q] pair step and its sums
                rank_accumulate  the slots' results back onto the docs
    valid_metric > ndcg_sort     the held-out docs' gather and sort

Time goes to the innermost of these, or to ``gradients`` /
``valid_metric`` themselves for what sits under neither.  The rows are
``scoped.table_of``'s, the window and the self times ``tracered``'s;
``of_this_run()`` reads THIS run's trace once a process and returns
``None``, and so does every reader, where there is none or where the
program (the parent of the PR that added the scopes) names none of
them.
"""

from __future__ import annotations

import json
import sys

from . import scoped, tracered

RANK_SCOPES = ("rank_gather", "rank_sort", "rank_pairs", "rank_accumulate",
               "ndcg_sort")
OUTER = ("gradients", "valid_metric")


def scope_of(path: str):
    parts = path.split("/")
    return next((p for p in reversed(parts) if p in RANK_SCOPES or p in OUTER),
                None)


def reduce_table(table: dict) -> dict:
    """Seconds of the window by ranking scope, averaged over devices."""
    window = [s for s in table["spans"] if s[0] == tracered.BENCH + "window"]
    if not window or not table["ops"]:
        raise ValueError("the trace holds no bench.window span or no device operation")
    w0, w1 = window[0][1], window[0][1] + window[0][2]
    devices = sorted({o[0] for o in table["ops"]})
    scope_ns, op_ns = {}, {}
    for dev in devices:
        for start, end, self_ns, name, path in tracered._self_times(
                [o for o in table["ops"] if o[0] == dev]):
            if end <= w0 or start >= w1:
                continue
            scope = scope_of(path)
            if scope is None:
                continue
            scope_ns[scope] = scope_ns.get(scope, 0.0) + self_ns
            label = f"{scope}:{tracered._op_kind(name)}"
            op_ns[label] = op_ns.get(label, 0.0) + self_ns
    n = len(devices)
    return {"scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
            "op_s": {k: v / n / 1e9 for k, v in op_ns.items()}}


_THIS_RUN = []


def of_this_run():
    if not _THIS_RUN:
        path, out = scoped.find_trace(), None
        if path is not None:
            try:
                out = reduce_table(scoped.table_of(path))
            except ValueError:
                out = None
        if out is not None and not any(s in out["scope_s"] for s in RANK_SCOPES):
            out = None          # a program without the scopes
        _THIS_RUN.append(out)
        if out is not None:
            top = sorted(out["op_s"].items(), key=lambda kv: -kv[1])[:16]
            print("rank_trace: " + json.dumps(
                {"scope_s": {k: round(v, 6) for k, v in out["scope_s"].items()},
                 "ops_s": [[k, round(v, 6)] for k, v in top]}),
                file=sys.stderr, flush=True)
    return _THIS_RUN[0]


def scope_ms_per_round(run, *scopes):
    """Device self time under ``scopes`` in ms per round, or ``None``."""
    red = of_this_run()
    if red is None or not run.get("rounds"):
        return None
    found = [red["scope_s"][s] for s in scopes if s in red["scope_s"]]
    if not found:
        return None
    return 1000.0 * sum(found) / run["rounds"]
