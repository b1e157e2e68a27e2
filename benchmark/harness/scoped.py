"""The window's trace once more, for what the program names itself
(PR 28): device time by the INNERMOST ``jax.named_scope`` of the round
program, the rows each histogram pass was handed (``hist_rows_*``), and
the program's own host spans (``lgbtpu.*``), which sit in the same
``.xplane.pb`` on the same clock as the device's operations.

``tracered.py`` fixes its scopes to three names and keeps only the
harness's ``bench.`` spans, and ``run`` carries its reduction, not the
trace; so the readers of the newer metrics (``layers/``) come here.
``of_this_run()`` finds THIS run's trace under ``.bench_cache/trace/``,
reads it once a process and reduces it inside the ``bench.window`` span;
it returns ``None``, and so does every reader, rather than read another
run's trace.  Against a program without the scopes and spans (the parent
of PR 28) every reader finds nothing to read and returns ``None``.

What the names are (docs/OBSERVABILITY.md of the program; PERF.md
section 3).  A device operation's ``tf_op`` path holds the scopes it was
traced under, outermost first; time goes to the innermost of them:

    partition round_hist find_splits                   (PR 27's three)
    gradients quantize tree_root tree_select leaf_renew score_update
    valid_score valid_metric hist_compact hist_kernel hist_update
    hist_rows_full hist_rows_<S>      (one per branch of the row ladder)

``hist_compact``/``hist_kernel`` sit inside a ``hist_rows_*``, itself
inside ``round_hist`` (a round's pass) or ``tree_root`` (the root pass).
One execution of a ``hist_rows_*`` branch is one histogram pass, and is
counted as one execution of a histogram kernel under it: the operation
whose path ends in ``pallas_call``.  (Counting the operations directly
inside a branch does not work: a kernel's wrapper may loop, and the
``while`` and ``conditional`` operations carry no path of their own.)
"""

from __future__ import annotations

import glob
import json
import os
import sys

from . import tracered
from .tracered import BENCH, DEVICE_PLANE, MODULES_LINE, OPS_LINE, SCOPE_STAT

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAM = "lgbtpu."
ROWS = "hist_rows_"
OLD_SCOPES = tracered.SCOPES
NEW_SCOPES = ("gradients", "quantize", "tree_root", "tree_select",
              "leaf_renew", "score_update", "valid_score", "valid_metric",
              "hist_compact", "hist_kernel", "hist_update")
NAMED = frozenset(OLD_SCOPES + NEW_SCOPES)
PARTITION_KERNEL = "partition_select_pallas"
KERNEL_CALL = "/pallas_call"


# ------------------------------------------------------------------ reading
def table_of(path: str) -> dict:
    """``tracered.table_of``'s rows, read in one pass, and the program's
    spans: ``[name, start_ns, dur_ns, {count: value}]``."""
    from jax.profiler import ProfileData

    from . import xplane_meta
    data = ProfileData.from_file(path)
    meta = xplane_meta.plane_metadata(path, DEVICE_PLANE)
    ops, modules, spans, program = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = int(plane.name[len(DEVICE_PLANE):].split()[0])
            scopes = meta.get(plane.name, {})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.extend([dev, int(ev.start_ns), int(ev.duration_ns),
                                    ev.name] for ev in line.events)
                elif line.name == OPS_LINE:
                    ops.extend([dev, int(ev.start_ns), int(ev.duration_ns),
                                ev.name,
                                str(scopes.get(ev.name, {}).get(SCOPE_STAT, ""))]
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(BENCH):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
                    elif ev.name.startswith(PROGRAM):
                        counts = {str(k): _number(v) for k, v in ev.stats}
                        program.append([ev.name, int(ev.start_ns),
                                        int(ev.duration_ns), counts])
    return {"ops": ops, "modules": modules, "spans": spans, "program": program}


def _number(v):
    try:
        return int(v)
    except (TypeError, ValueError):
        try:
            return float(v)
        except (TypeError, ValueError):
            return str(v)


def process_start() -> float:
    """Host clock at the start of this process: run.py's own reading
    where it is the main module, else the kernel's."""
    t = getattr(sys.modules.get("__main__"), "T_START", None)
    if t is not None:
        return float(t)
    return os.stat(f"/proc/{os.getpid()}").st_ctime


def workload_of(argv=None):
    argv = sys.argv if argv is None else argv
    for i, a in enumerate(argv):
        if a == "--workload" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--workload="):
            return a.split("=", 1)[1]
    return None


def find_trace(root: str = ROOT, workload=None, since=None):
    """This run's ``.xplane.pb``: under the run's own ``--workload``, or
    anywhere under ``.bench_cache/trace/``, the newest file written
    since this process started; ``None`` where there is none."""
    workload = workload_of() if workload is None else workload
    since = process_start() if since is None else since
    base = os.path.join(root, ".bench_cache", "trace")
    if workload:
        base = os.path.join(base, workload)
    files = [f for f in glob.glob(os.path.join(base, "**", "*.xplane.pb"),
                                  recursive=True)
             if os.path.getmtime(f) >= since]
    return max(files, key=os.path.getmtime) if files else None


def cell_rows(workload=None, root: str = ROOT):
    """Rows of the cell's training set (what a ``hist_rows_full`` pass
    reads), from the manifest and the configuration it names."""
    workload = workload_of() if workload is None else workload
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as fh:
            manifest = json.load(fh)
        cell = next(w for w in manifest["workloads"] if w["name"] == workload)
        cfg = next(c for c in manifest["configs"] if c["name"] == cell["config"])
        with open(os.path.join(root, cfg["file"])) as fh:
            return int(json.load(fh)["rows"])
    except (OSError, StopIteration, KeyError, ValueError):
        return None


_THIS_RUN = []


def of_this_run():
    """The reduction of this run's trace (read once a process), or
    ``None``: no trace of this run, or one without the window."""
    if not _THIS_RUN:
        path, out = find_trace(), None
        if path is not None:
            try:
                out = reduce_table(table_of(path), cell_rows())
            except ValueError:
                out = None
        _THIS_RUN.append(out)
        if out is not None:
            print("scoped: " + json.dumps(summary(out)), file=sys.stderr,
                  flush=True)
    return _THIS_RUN[0]


# ----------------------------------------------------------------- reducing
def scope_of(path: str):
    """The innermost named scope of an operation's path, and whether it
    sits under ``round_hist``."""
    parts = path.split("/")
    inner = next((p for p in reversed(parts)
                  if p in NAMED or p.startswith(ROWS)), None)
    return inner, "round_hist" in parts


def rows_scope(path: str):
    return next((p for p in reversed(path.split("/")) if p.startswith(ROWS)),
                None)


def _passes(rows: list) -> dict:
    """Executions of each ``hist_rows_*`` branch among ``rows`` (one
    device's operations as ``[start, end, dur, name, path]``): a pass is
    one execution of a histogram kernel, the operation whose path ends
    in ``pallas_call`` under the branch's scope."""
    out = {}
    for _, _, _, _, path in rows:
        if path.rstrip(":").endswith(KERNEL_CALL):
            branch = rows_scope(path)
            if branch is not None:
                out[branch] = out.get(branch, 0) + 1
    return out


def reduce_table(table: dict, rows_full=None) -> dict:
    """Numbers of one traced window (seconds; averaged over devices)."""
    window = [s for s in table["spans"] if s[0] == BENCH + "window"]
    if not window or not table["ops"]:
        raise ValueError("the trace holds no bench.window span or no device operation")
    w0, w1 = window[0][1], window[0][1] + window[0][2]
    program = sorted((s for s in table.get("program", [])
                      if s[1] + s[2] > w0 and s[1] < w1), key=lambda s: s[1])
    devices = sorted({o[0] for o in table["ops"]})
    n = len(devices)
    busy_ns, scope_ns, hist_ns, unnamed_ns = 0.0, {}, {}, {}
    passes, partitions, gaps, gap_list, starts = {}, 0, {}, [], []
    for dev in devices:
        rows = [r for r in tracered._self_times(
                    [o for o in table["ops"] if o[0] == dev])
                if r[1] > w0 and r[0] < w1]
        merged = tracered._union([[max(r[0], w0), min(r[1], w1)] for r in rows])
        busy_ns += sum(b - a for a, b in merged)
        for start, end, self_ns, name, path in rows:
            scope, in_hist = scope_of(path)
            kind = tracered._op_kind(name)
            if scope is None:
                unnamed_ns[kind] = unnamed_ns.get(kind, 0.0) + self_ns
                continue
            key = "hist_rows" if scope.startswith(ROWS) else scope
            scope_ns[key] = scope_ns.get(key, 0.0) + self_ns
            if in_hist:
                hist_ns[key] = hist_ns.get(key, 0.0) + self_ns
            if kind.startswith(PARTITION_KERNEL):
                partitions += 1
        for branch, count in _passes(rows).items():
            passes[branch] = passes.get(branch, 0) + count
        # the round program: the module that ran longest in the window
        mods = [m for m in table["modules"] if m[0] == dev
                and m[1] + m[2] > w0 and m[1] < w1]
        total = {}
        for _, _, dur, name in mods:
            total[name] = total.get(name, 0) + dur
        main = max(total, key=total.get) if total else None
        runs = sorted(m[1] for m in mods if m[3] == main)
        for name, s0, dur, _ in program if dev == devices[0] else ():
            if name == PROGRAM + "train":
                first = next((r for r in runs if r >= s0), None)
                if first is not None and first < s0 + dur:
                    starts.append((s0, first))
        # idle gaps, named by the innermost program span open at their middle
        edges = [[w0, w0]] + merged + [[w1, w1]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                what = span_at((a + b) // 2, program)
                gaps[what] = gaps.get(what, 0.0) + (b - a)
                gap_list.append((b - a, what))
    done = [s for s in program if s[0] == PROGRAM + "dispatch_done"
            and w0 <= s[1] < w1]
    selected = sum(s[3].get("hist_rows_selected", 0) for s in done)
    handed = None
    if passes and (rows_full or "hist_rows_full" not in passes):
        handed = sum(count * (rows_full if b == "hist_rows_full"
                              else int(b[len(ROWS):]))
                     for b, count in passes.items())
    by_span = {}
    for name, _, dur, _ in program:
        c = by_span.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += dur / 1e9
    # what the host did between a job's start and its first execution
    # of the round program, by the spans nested directly under the job
    job_start = {}
    for s0, first in starts:
        for i, (name, a, dur, _) in enumerate(program):
            if s0 <= a < first and span_depth(i, program) == 1:
                job_start[name] = job_start.get(name, 0.0) \
                    + (min(a + dur, first) - a) / 1e9
    return {
        "window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / n / 1e9,
        "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
        "round_hist_s": {k: v / n / 1e9 for k, v in hist_ns.items()},
        "unnamed_s": {k: v / n / 1e9 for k, v in unnamed_ns.items()},
        "passes": {k: v // n for k, v in passes.items()},
        "partition_passes": partitions // n,
        "rows_selected": selected if done and selected else None,
        "rows_handed": handed,
        "trees": sum(s[3].get("trees", 0) for s in done),
        "rounds": sum(s[3].get("rounds", 0) for s in done),
        "program_spans": {k: [c, round(s, 6)] for k, (c, s) in by_span.items()},
        "job_start_s": [(first - s0) / 1e9 for s0, first in starts],
        "job_start_by_span_s": job_start,
        "gap_s": {k: v / n / 1e9 for k, v in gaps.items()},
        "gaps_over_1ms": sorted(((round(d / 1e6, 3), what)
                                 for d, what in gap_list if d > 1e6),
                                reverse=True),
        "devices": n}


def span_at(t: int, program: list) -> str:
    """The innermost program span open at ``t`` (the one that started
    last), or where the program is not running."""
    inner = None
    for name, start, dur, _ in program:
        if start <= t < start + dur and (inner is None or start >= inner[1]):
            inner = (name, start)
    return inner[0] if inner else "outside_the_program"


def span_depth(i: int, program: list) -> int:
    """How many other program spans hold span ``i`` whole (of two that
    start together, the longer holds the shorter)."""
    _, a, dur, _ = program[i]
    return sum(1 for j, (_, start, d, _) in enumerate(program)
               if j != i and start <= a and a + dur <= start + d
               and (d > dur or j < i))


def summary(reduced: dict, top: int = 12) -> dict:
    """What goes to stderr and from there to PERF.md: everything but the
    long lists, with the unnamed time by operation."""
    def first(d):
        return [[k, round(v, 6)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    out = {k: v for k, v in reduced.items()
           if k not in ("scope_s", "round_hist_s", "unnamed_s", "gap_s",
                        "gaps_over_1ms")}
    out["scope_s"] = first(reduced["scope_s"])
    out["under_round_hist_s"] = first(reduced["round_hist_s"])
    out["unnamed_ops_s"] = first(reduced["unnamed_s"])
    out["idle_gaps_by_program_span_s"] = first(reduced["gap_s"])
    out["gaps_over_1ms"] = reduced["gaps_over_1ms"][:top]
    return out


# ------------------------------------------------------- what readers share
def scope_ms_per_round(run, *scopes):
    """Device self time under ``scopes`` in ms per round, or ``None``
    where this run has no trace or the trace none of the scopes."""
    red = of_this_run()
    if red is None or not run.get("rounds"):
        return None
    found = [red["scope_s"][s] for s in scopes if s in red["scope_s"]]
    if not found:
        return None
    return 1000.0 * sum(found) / run["rounds"]


def program_counter(*names):
    """Sum of the program's process-wide counters ``names``, or ``None``
    where the program does not declare them."""
    try:
        from lightgbm_tpu.obs.metrics import COUNTERS, global_metrics
    except ImportError:
        return None
    if not all(n in COUNTERS for n in names):
        return None
    return float(sum(global_metrics.counter(n) for n in names))
