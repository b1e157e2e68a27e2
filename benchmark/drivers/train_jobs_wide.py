"""Traffic driver ``train_jobs_wide`` (a traffic file names it:
``"driver": "train_jobs_wide"``): ``train_jobs`` itself, for a dense job
of thousands of columns (the cell ``epsilon-train``, PR 45).

The protocol, the data, the job, the window and the answers are
``drivers/train_jobs.py``'s, called as they stand.  What this file adds is
the path check of what the cell is about, before and after: the program
has to say into how many column blocks it cuts a histogram pass, how large
the per-leaf state is that it carries, and under which VMEM budget
(``NEEDS``), and at this width the pass has to be cut.

A program that does not count these cannot run the cell: its histogram
kernels hold every column's accumulator in VMEM at once and unroll every
column chunk (the parent of PR 45: its round program at 2,000 columns was
still compiling after 11 minutes and 34 GB of host memory, one of its
kernels alone after 21 minutes, and its K = 42 pass wants 258 MB of the
chip's 128 MiB of VMEM).  So the run is refused at once, before any data
is made, and not left to a compile that does not end by itself.
"""

from __future__ import annotations

from harness import load_module, program

NEEDS = ("hist_col_blocks", "hist_state_bytes", "hist_vmem_budget_bytes")

_base = load_module("drivers", "train_jobs")
make_data = _base.make_data
measure = _base.measure


def _counted(bst) -> dict:
    """What the booster says of its histogram passes, held to the
    configuration's width: a pass cut into blocks, the whole per-leaf
    state resident, one budget over all of it."""
    gb = bst._gbdt
    got = {name: int(gb.metrics.counter(name)) for name in NEEDS}
    columns = int(gb.train_set.bins.shape[1])
    program.require(got["hist_col_blocks"] > 1,
                    f"a pass over {columns} columns in "
                    f"{got['hist_col_blocks']} column block")
    program.require(
        got["hist_state_bytes"] == int(gb.hp.num_leaves) * columns
        * int(gb.hp.n_bins) * 16,
        f"hist_state_bytes {got['hist_state_bytes']}: the per-leaf state is "
        "not whole")
    return got


def prepare(ctx) -> dict:
    try:
        from lightgbm_tpu.obs.metrics import COUNTERS
    except ImportError:
        COUNTERS = {}
    missing = [c for c in NEEDS if c not in COUNTERS]
    if missing:
        raise program.Refused(
            f"the program does not count {missing}: its histogram kernels "
            "are not blocked over the columns and cannot take this cell's "
            "width")
    seen = {}
    real = program.check_path

    def check_path(bst, *args, **kw):
        seen.update(_counted(bst))
        return real(bst, *args, **kw)
    program.check_path = check_path
    try:
        state = _base.prepare(ctx)
    finally:
        program.check_path = real
    state["path"] = {**state["path"], **seen}
    return state


def collect(ctx, state: dict):
    _counted(state["last"])
    return _base.collect(ctx, state)
