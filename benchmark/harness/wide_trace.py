"""This run's trace for the wide dense cell (``epsilon-train``, PR 45):
the histogram work of a round whole (the round passes and the root's),
and its share of the HBM roofline for what the passes had to move
(``wide_bytes.py``).  Reads ``scoped.py``'s reduction and, for the counts
the program's ``dispatch_done`` spans carry (``hist_rows_selected``,
``hist_leaves_built``), the trace's program spans once more.  Against a
program without the count (the parent of PR 45) the share finds nothing to
read and is ``None``."""

from __future__ import annotations

import json
import os
import sys

from . import scoped, wide_bytes
from .tracered import BENCH

#: innermost scopes that are histogram work wherever they sit: the
#: kernels and what unpacks their output, the compaction (ranks and the
#: streaming kernel), the branch itself
HIST_SCOPES = ("hist_kernel", "hist_compact", "hist_rows")
ROOT_OF = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def hist_ms_per_round(run):
    """Everything under ``round_hist`` (kernels, compaction, the state's
    update) plus the root pass's histogram under ``tree_root``."""
    trace, red = run.get("trace"), scoped.of_this_run()
    if not trace or red is None or "round_hist" not in trace["scope_s"] \
            or not run.get("rounds"):
        return None
    root = sum(red["scope_s"].get(k, 0.0) - red["round_hist_s"].get(k, 0.0)
               for k in HIST_SCOPES)
    return 1000.0 * (trace["scope_s"]["round_hist"] + root) / run["rounds"]


def _window_counts():
    """Sums of the ``dispatch_done`` spans' counts inside the window."""
    path = scoped.find_trace()
    if path is None:
        return None
    table = scoped.table_of(path)
    window = [s for s in table["spans"] if s[0] == BENCH + "window"]
    if not window:
        return None
    w0, w1 = window[0][1], window[0][1] + window[0][2]
    done = [s[3] for s in table["program"]
            if s[0] == scoped.PROGRAM + "dispatch_done" and w0 <= s[1] < w1]
    if not done or not all("hist_leaves_built" in c for c in done):
        return None
    return {k: sum(int(c.get(k, 0)) for c in done)
            for k in ("hist_rows_selected", "hist_leaves_built", "trees")}


def _shape():
    """(features, device bins, split batch) of the cell's job, from the
    manifest, the configuration it names and what that expects."""
    with open(os.path.join(ROOT_OF, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == scoped.workload_of())
    cfg = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT_OF, cfg["file"])) as fh:
        cfg = json.load(fh)
    return (int(cfg["features"]), int(cfg["expects"]["device_n_bins"]),
            int(cfg["expects"]["tpu_split_batch"]))


def roofline_share(run):
    """100 x (seconds the HBM needs for the passes' necessary bytes) /
    (device seconds of the compaction and histogram kernels)."""
    red = scoped.of_this_run()
    if red is None:
        return None
    spent = sum(red["scope_s"].get(k, 0.0) for k in HIST_SCOPES)
    counts = _window_counts()
    if counts is None or spent <= 0:
        return None
    import jax
    kind = jax.devices()[0].device_kind
    features, bins, batch = _shape()
    nbytes = wide_bytes.pass_bytes(counts["hist_rows_selected"], features,
                                   bins, counts["hist_leaves_built"])
    # a tree's root pass reads every row into ONE leaf's channels; the
    # round passes are counted at the split batch's (the warm-up rounds'
    # narrower bodies too: an upper bound)
    root_rows = counts["trees"] * (scoped.cell_rows() or 0)
    ops = wide_bytes.onehot_ops(root_rows, features, bins, 1) \
        + wide_bytes.onehot_ops(counts["hist_rows_selected"] - root_rows,
                                features, bins, batch)
    floor_s = wide_bytes.roofline_s(nbytes, kind)
    print("wide_hist: " + json.dumps({
        **counts, "necessary_bytes": nbytes, "hbm_floor_s": round(floor_s, 6),
        "onehot_ops": ops, "hist_device_s": round(spent, 6),
        "achieved_bytes_per_s": round(nbytes / spent, 1),
        "onehot_ops_per_s": round(ops / spent, 1),
        "onehot_share_of_int8_peak": round(
            100.0 * ops / spent / wide_bytes.PEAKS[kind]["int8_ops_per_s"], 3),
    }), file=sys.stderr, flush=True)
    return 100.0 * floor_s / spent
