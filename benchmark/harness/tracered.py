"""From the profiler's trace of a window to the numbers the per-layer
readers take: device busy time, time by ``jax.named_scope``, the idle
gaps and what the host was doing in them.

Two steps, so that the second can be checked on a small recorded table
(tests/): ``table_of`` reads an ``.xplane.pb`` into plain rows, and
``reduce_table`` turns rows into numbers.

What a real trace of this program on a TPU v5e looks like (looked at by
hand, PR 27; PERF.md section 3): the device plane is ``/device:TPU:0``;
its line ``XLA Ops`` holds one event per executed HLO operation, nested
(a ``while`` spans the operations of its body); an event's own name is
the HLO instruction, and the ``jax.named_scope`` path arrives in the
event METADATA's stat ``tf_op`` (xplane_meta.py reads it).  The line
``XLA Modules`` holds one event per executed program.  The harness's own
``TraceAnnotation`` spans (``bench.window``, ``bench.job``) are on the
host plane, on the same clock.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SCOPE_STAT = "tf_op"
SCOPES = ("round_hist", "partition", "find_splits")
BENCH = "bench."


def table_of(path: str) -> dict:
    """Plain rows of one ``.xplane.pb``: device operations as
    ``[device, start_ns, dur_ns, name, scope_path]``, executed programs
    as ``[device, start_ns, dur_ns, name]`` and the harness's host spans
    as ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    from . import xplane_meta
    data = ProfileData.from_file(path)
    meta = xplane_meta.plane_metadata(path, DEVICE_PLANE)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = int(plane.name[len(DEVICE_PLANE):].split()[0])
            scopes = meta.get(plane.name, {})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.extend([dev, int(ev.start_ns), int(ev.duration_ns),
                                    ev.name] for ev in line.events)
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([dev, int(ev.start_ns), int(ev.duration_ns),
                                ev.name,
                                str(scopes.get(ev.name, {}).get(SCOPE_STAT, ""))])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(BENCH):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {"ops": ops, "modules": modules, "spans": spans}


def _self_times(ops: list) -> list:
    """``[start, end, self_ns, name, path]`` per operation of one device:
    its duration less what the operations nested in it cover."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out, stack = [], []
    for _, start, dur, name, path in ops:
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        row = [start, end, dur, name, path]
        out.append(row)
        stack.append(row)
    return out


def _union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _op_kind(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion``."""
    return name.lstrip("%").split(" ")[0].split(".")[0]


def _scope_of(path: str):
    parts = path.split("/")
    return next((s for s in SCOPES if s in parts), None)


def reduce_table(table: dict) -> dict:
    """Numbers of one traced window (seconds; averaged over devices)."""
    spans = table["spans"]
    window = [s for s in spans if s[0] == BENCH + "window"]
    if not window or not table["ops"]:
        raise ValueError("the trace holds no bench.window span or no device operation")
    w0, w1 = window[0][1], window[0][1] + window[0][2]
    jobs = sorted((s[1], s[1] + s[2]) for s in spans if s[0] == BENCH + "job")
    devices = sorted({o[0] for o in table["ops"]})
    busy_ns, scope_ns, op_ns, gaps, between = 0.0, {}, {}, {}, []
    for dev in devices:
        rows = [r for r in _self_times([o for o in table["ops"] if o[0] == dev])
                if r[1] > w0 and r[0] < w1]
        merged = _union([[max(r[0], w0), min(r[1], w1)] for r in rows])
        busy_ns += sum(b - a for a, b in merged)
        for start, end, self_ns, name, path in rows:
            scope = _scope_of(path)
            if scope:
                scope_ns[scope] = scope_ns.get(scope, 0.0) + self_ns
            label = f"{scope or 'unscoped'}:{_op_kind(name)}"
            op_ns[label] = op_ns.get(label, 0.0) + self_ns
        # executions of the round program: the module that ran longest
        mods = [m for m in table["modules"] if m[0] == dev
                and m[1] + m[2] > w0 and m[1] < w1]
        total = {}
        for _, _, dur, name in mods:
            total[name] = total.get(name, 0) + dur
        main = max(total, key=total.get) if total else None
        runs = sorted((m[1], m[1] + m[2]) for m in mods if m[3] == main)
        between.extend((b[0] - a[1]) / 1e9 for a, b in zip(runs, runs[1:]))
        # idle gaps, named by what the host was doing in their middle
        edges = [[w0, w0]] + merged + [[w1, w1]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                what = _host_state((a + b) // 2, jobs, runs)
                gaps[what] = gaps.get(what, 0.0) + (b - a)
    n = len(devices)
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / n / 1e9,
            "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
            "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
            "gap_s": {k: v / n / 1e9 for k, v in gaps.items()},
            "between_dispatch_s": between, "jobs": len(jobs), "devices": n}


def _host_state(t: int, jobs: list, runs: list) -> str:
    """What the host was doing at time ``t``: by the harness's job spans
    and the executions of the round program."""
    if any(a <= t < b for a, b in runs):
        return "waiting:inside_the_round_program"
    job = next(((a, b) for a, b in jobs if a <= t < b), None)
    if job is None:
        return "outside_a_job"
    if not any(job[0] <= a <= t for a, _ in runs):
        return "job:booster_and_first_dispatch"
    return "job:trees_to_host_and_next_dispatch"


def reduce_dir(trace_dir: str) -> dict:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_table(table_of(files[-1]))


def breakdown(reduced: dict, top: int = 10) -> dict:
    def first(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": first(reduced["op_s"]),
            "idle_gaps": first(reduced["gap_s"])}
