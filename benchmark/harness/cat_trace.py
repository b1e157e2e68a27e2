"""The window's trace once more, for what a job with CATEGORICAL columns
names itself: device time by the innermost of the scopes the split
search opens inside ``find_splits`` (ops/split.py, learner/batch_grower.py),
which ``scoped.py``'s ``NAMED`` does not know and so counts as
``find_splits`` whole:

    find_splits > cat_subset   the sorted-subset scan: the two carried
                               sorts, cumulative sums and gains
                  cat_bitset   the winners' sets of left bins and their
                               packing to words for the partition kernel

(the root's search opens the two inside ``tree_root``, and the packing
sits inside ``partition`` > ``find_splits``; under the children's
``vmap`` a scope's name reads ``vmap(cat_subset)``)

and what the program's ``dispatch_done`` spans of the window counted of
the trees they brought back (``splits``, ``cat_splits``,
``cat_subset_splits``, ``cat_left_levels``).  Time goes to the innermost
of the scopes, or to ``find_splits`` itself for what sits under neither.
The rows are ``scoped.table_of``'s, the window and the self times
``tracered``'s; ``of_this_run()`` reads THIS run's trace once a process
and returns ``None``, and so does every reader, where there is none or
where the program (the parent of the PR that added the scopes) names
none of them.
"""

from __future__ import annotations

import json
import sys

from . import scoped, tracered

CAT_SCOPES = ("cat_subset", "cat_bitset")
OUTER = ("find_splits",)
COUNTS = ("splits", "cat_splits", "cat_subset_splits", "cat_left_levels")


def scope_of(path: str):
    """The innermost of the scopes on an operation's path; a scope opened
    inside a ``vmap`` (the search of a pass's 2K children) stands on the
    path as ``vmap(<scope>)``."""
    parts = [p[5:-1] if p.startswith("vmap(") and p.endswith(")") else p
             for p in path.split("/")]
    return next((p for p in reversed(parts) if p in CAT_SCOPES or p in OUTER),
                None)


def reduce_table(table: dict) -> dict:
    """Seconds of the window by scope, averaged over devices, and the
    window's ``dispatch_done`` counts summed."""
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
    counts = {}
    for name, start, _, stats in table.get("program", []):
        if name == scoped.PROGRAM + "dispatch_done" and w0 <= start < w1:
            for key in COUNTS:
                if key in stats:
                    counts[key] = counts.get(key, 0) + stats[key]
    n = len(devices)
    return {"scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
            "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
            "counts": counts}


_THIS_RUN = []


def of_this_run():
    if not _THIS_RUN:
        path, out = scoped.find_trace(), None
        if path is not None:
            try:
                out = reduce_table(scoped.table_of(path))
            except ValueError:
                out = None
        if out is not None and not any(s in out["scope_s"] for s in CAT_SCOPES):
            out = None          # a program without the scopes
        _THIS_RUN.append(out)
        if out is not None:
            top = sorted(out["op_s"].items(), key=lambda kv: -kv[1])[:16]
            print("cat_trace: " + json.dumps(
                {"scope_s": {k: round(v, 6) for k, v in out["scope_s"].items()},
                 "counts": out["counts"],
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


def ratio(numerator: str, denominator: str, scale: float = 1.0):
    """``scale x numerator / denominator`` of the window's
    ``dispatch_done`` counts, or ``None``."""
    red = of_this_run()
    if red is None or not red["counts"].get(denominator):
        return None
    return scale * red["counts"].get(numerator, 0) / red["counts"][denominator]
