"""The window's trace of a cell on several chips, reduced CHIP BY CHIP
(PR 30).  ``tracered.py`` and ``scoped.py`` average over the device
planes; what a data-parallel job costs is what its busiest chip does and
how far the chips lie apart, so the ``dp_*`` readers (``layers/``) come
here.  The rows are ``scoped.table_of``'s (every ``/device:TPU:<i>``
plane's operations with their ``jax.named_scope`` paths, the executed
programs, the harness's and the program's host spans), found and read
once a process as ``scoped.of_this_run`` does.

What is read, per chip, inside the ``bench.window`` span:

``busy``        union of the device operations' intervals
``hist``        self time of operations under the scope ``round_hist``
``collective``  self time of operations under a collective's scope
                (``hist_allreduce``: ``ops/histogram.py reduce_hist``;
                ``stats_allreduce``: the root's sums, leaf renewal's
                per-shard sums, the leaf recount).  A chip that reaches
                a ``psum`` first waits INSIDE its all-reduce, so this is
                exchange plus waiting for the slowest chip
``compute``     ``busy`` less ``collective``: what the chip did itself;
                the chips' spread of it is what the waiting comes from
``programs``    union of the executed programs' intervals (line ``XLA
                Modules``); the gaps between them are the device waiting
                for the host's next program, each named by the innermost
                ``lgbtpu.*`` span open at its middle

The busiest chip is the one with the largest ``busy``.  Collectives are
also summed by opcode (``all-reduce`` and its kin, named or not) and
printed beside the named time: a collective outside every name shows
there.  Against a program without the scopes and spans every reader
finds nothing to read and returns ``None``.
"""

from __future__ import annotations

import json
import sys

from . import scoped, tracered
from .tracered import BENCH

COLLECTIVE_SCOPES = ("hist_allreduce", "stats_allreduce")
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
HIST_SCOPE = "round_hist"
VALID_SPANS = (scoped.PROGRAM + "valid_eval", scoped.PROGRAM + "metric_eval")


def reduce_table(table: dict) -> dict:
    """Numbers of one traced window, seconds, chip by chip (``chips``:
    one dict a device, in the order of ``devices``)."""
    window = [s for s in table["spans"] if s[0] == BENCH + "window"]
    if not window or not table["ops"]:
        raise ValueError("the trace holds no bench.window span or no device operation")
    w0, w1 = window[0][1], window[0][1] + window[0][2]
    program = sorted((s for s in table.get("program", [])
                      if s[1] + s[2] > w0 and s[1] < w1), key=lambda s: s[1])
    devices = sorted({o[0] for o in table["ops"]})
    chips = []
    for dev in devices:
        rows = [r for r in tracered._self_times(
                    [o for o in table["ops"] if o[0] == dev])
                if r[1] > w0 and r[0] < w1]
        merged = tracered._union([[max(r[0], w0), min(r[1], w1)] for r in rows])
        hist = named = by_op = 0.0
        scopes, executions = {}, 0
        for start, end, self_ns, name, path in rows:
            parts = path.split("/")
            if HIST_SCOPE in parts:
                hist += self_ns
            scope = next((s for s in COLLECTIVE_SCOPES if s in parts), None)
            kind = tracered._op_kind(name)
            is_op = kind.startswith(COLLECTIVE_OPS)
            if scope is not None:
                named += self_ns
                scopes[scope] = scopes.get(scope, 0.0) + self_ns
                executions += is_op and not kind.endswith("-done")
            if is_op:
                by_op += self_ns
        mods = tracered._union([[max(m[1], w0), min(m[1] + m[2], w1)]
                                for m in table["modules"] if m[0] == dev
                                and m[1] + m[2] > w0 and m[1] < w1])
        gaps = {}
        edges = [[w0, w0]] + mods + [[w1, w1]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                what = scoped.span_at((a + b) // 2, program)
                gaps[what] = gaps.get(what, 0.0) + (b - a) / 1e9
        busy = sum(b - a for a, b in merged) / 1e9
        chips.append({
            "busy_s": busy, "hist_s": hist / 1e9,
            "collective_s": named / 1e9,
            "collective_by_scope_s": {k: v / 1e9 for k, v in scopes.items()},
            "collective_by_opcode_s": by_op / 1e9,
            "collective_executions": int(executions),
            "compute_s": busy - named / 1e9,
            "programs_s": sum(b - a for a, b in mods) / 1e9,
            "programs_run": sum(1 for m in table["modules"] if m[0] == dev
                                and w0 <= m[1] < w1),
            "between_programs_s": sum(gaps.values()),
            "between_programs_by_span_s": gaps})
    busiest = max(range(len(chips)), key=lambda i: chips[i]["busy_s"])
    by_span = {}
    for name, _, dur, _ in program:
        c = by_span.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += dur / 1e9
    return {"window_s": (w1 - w0) / 1e9, "devices": devices, "chips": chips,
            "busiest": busiest,
            "compute_spread_s": max(c["compute_s"] for c in chips)
            - min(c["compute_s"] for c in chips),
            "program_spans": {k: [c, s] for k, (c, s) in by_span.items()}}


_THIS_RUN = []


def of_this_run():
    """The reduction of this run's trace (read once a process), or
    ``None``: no trace of this run, or one without the window."""
    if not _THIS_RUN:
        path, out = scoped.find_trace(), None
        if path is not None:
            try:
                out = reduce_table(scoped.table_of(path))
            except ValueError:
                out = None
        _THIS_RUN.append(out)
    return _THIS_RUN[0]


def busiest(run):
    """``(reduction, the busiest chip's numbers)``, or ``(None, None)``
    where this run has no trace or did no round."""
    red = of_this_run()
    if red is None or not run.get("rounds"):
        return None, None
    say(red, run)
    return red, red["chips"][red["busiest"]]


def span_s(red: dict, *names):
    """Host seconds inside the program's spans ``names`` over the
    window, or ``None`` where the trace holds none of them."""
    found = [red["program_spans"][n][1] for n in names
             if n in red["program_spans"]]
    return sum(found) if found else None


_SAID = []


def say(red: dict, run: dict) -> None:
    """Once a process, to stderr on a ``scoped:`` line: every chip's
    numbers in ms a round, the gaps between programs by span, the
    collectives' logical payload over their time."""
    if _SAID:
        return
    _SAID.append(True)
    rounds = run["rounds"]

    def ms(x):
        return round(1000.0 * x / rounds, 3)
    chip = red["chips"][red["busiest"]]
    payload = run.get("collective_bytes")
    out = {"rounds": rounds, "busiest_chip": red["devices"][red["busiest"]],
           "per_chip_ms_a_round": {
               str(d): {k[:-2]: ms(v) for k, v in c.items()
                        if k.endswith("_s") and not isinstance(v, dict)}
               for d, c in zip(red["devices"], red["chips"])},
           "collective_by_scope_ms": {k: ms(v) for k, v in
                                      chip["collective_by_scope_s"].items()},
           "collective_executions_a_round":
               round(chip["collective_executions"] / rounds, 2),
           "programs_a_round": round(chip["programs_run"] / rounds, 2),
           "between_programs_by_span_ms": {
               k: ms(v) for k, v in sorted(
                   chip["between_programs_by_span_s"].items(),
                   key=lambda kv: -kv[1])},
           "host_spans_ms_a_round": {
               k: ms(s) for k, (_, s) in sorted(
                   red["program_spans"].items(), key=lambda kv: -kv[1][1])},
           "collective_payload_bytes_a_round":
               None if payload is None else round(payload / rounds),
           "collective_gb_per_s":
               None if not payload or chip["collective_s"] <= 0
               else round(payload / chip["collective_s"] / 1e9, 3)}
    print("scoped: " + json.dumps({"mesh": out}), file=sys.stderr, flush=True)
