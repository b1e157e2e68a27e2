"""Traffic driver ``train_jobs_cat`` (a traffic file names it: ``"driver":
"train_jobs_cat"``): ``train_jobs``'s closed loop of ``lgb.train`` jobs,
on a ``Dataset`` constructed from a dense float64 matrix some of whose
columns are integer codes handed over as ``categorical_feature``.

The protocol is ``train_jobs``'s (``prepare``, ``measure``, ``collect``;
drivers/train_jobs.py says what each returns), the job is
``program.run_job``'s, the window is ``train_jobs.measure`` itself.  What
differs: the generator's categorical columns are named to ``lgb.Dataset``;
the answers carry, for every categorical node, the set of raw codes the
stated model sends left (the plain reference decides such a node by
membership of the raw code and knows nothing of bins); and the path check
adds what this cell is about — the categorical columns the configuration
names and those of them the split search scans by sorted subsets, the
row partition in the fused kernel in every round, no bundle plan, at
least one categorical split in every tree of the job.

A program that does not count what this path is checked by (``NEEDS``)
cannot be checked here: the run is refused at once, before any data is
made.
"""

from __future__ import annotations

import time

import numpy as np

from harness import load_module, program

NEEDS = ("cat_features", "cat_subset_features", "cat_levels_kept",
         "cat_other_rows", "cat_splits", "cat_subset_splits",
         "cat_left_levels", "fused_partition_declined")
SPANS = ("construct", "dense_bin_mappers", "dense_bin_matrix",
         "cat_bin_mappers")


def make_data(ctx):
    """Training and valid parts by the generator the configuration names:
    ``(xt32, xt64, y)`` each, feature-major."""
    cfg = ctx.cfg
    gen, features = load_module("datagen", cfg["data"]["generator"]), int(cfg["features"])
    return (gen.make(cfg["data"], ctx.seed, 0, int(cfg["rows"]), features),
            gen.make(cfg["data"], ctx.seed, 1, int(cfg["valid_rows"]), features))


def prepare(ctx) -> dict:
    try:
        from lightgbm_tpu.obs.metrics import COUNTERS
    except ImportError:
        COUNTERS = {}
    missing = [c for c in NEEDS if c not in COUNTERS]
    if missing:
        raise program.Refused(
            f"the program does not count {missing}: a categorical job's "
            "partition and split search cannot be checked against this "
            "cell's path")
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.timer import global_timer
    params = {**ctx.cfg["params"], **ctx.traffic.get("params", {})}
    rounds = int(ctx.traffic["num_boost_round"])
    dispatch = int(ctx.traffic["dispatch_rounds"])
    program.require(ctx.traffic["valid_set"], "this cell has a valid set")
    columns = [int(c) for c in ctx.cfg["categorical"]["columns"]]

    t = time.time()
    (xt32, xt64, y), (xv32, xv64, yv) = make_data(ctx)
    ctx.phases["data_s"] = time.time() - t

    t = time.time()
    global_timer.enable()       # the program's own spans inside construct
    ds = lgb.Dataset(xt64.T, label=y, params=params,
                     categorical_feature=columns).construct()
    dv = ds.create_valid(xv64.T, label=yv).construct()
    global_timer.disable()
    del xt64, xv64
    ctx.phases["construct_s"] = time.time() - t
    spans = {name: row["total_s"] for name, row in global_timer.as_dict().items()
             if name in SPANS}

    t = time.time()
    # two dispatches: a job's second one compiles again (PERF.md,
    # Set-up), and nothing may compile inside the window
    warm_marks = []
    bst, _, n = program.run_job(lgb, params, ds, dv, rounds, dispatch, 0.0,
                                lambda: warm_marks.append(time.time()),
                                at_least=2)
    took = check_path(bst, ctx.cfg, n, dispatch, ctx.on_tpu)
    del bst
    ctx.phases["warmup_s"] = time.time() - t
    ctx.phases["warmup_first_dispatch_s"] = warm_marks[0] - t
    return {"lgb": lgb, "params": params, "rounds": rounds, "dispatch": dispatch,
            "ds": ds, "dv": dv, "path": took, "setup_spans_s": spans,
            "inputs": {"train": (xt32, y), "valid": (xv32, yv)}}


def check_path(bst, cfg: dict, rounds: int, dispatch: int, on_tpu: bool) -> dict:
    """``program.check_path`` (every round in the fused scan, the split
    batch and histogram type the configuration expects, the state on the
    TPU), and the categorical path: the columns the configuration names,
    counted by the program, and those the subset scan takes; no bundle
    plan; on the chip every round's partition in the fused kernel
    (``fused_partition_declined`` 0; the CPU has no such kernel), at least
    one categorical split in every tree, and every tree at the leaves the
    configuration says."""
    got = program.check_path(bst, cfg, rounds, dispatch, on_tpu)
    gb, want = bst._gbdt, cfg["categorical"]
    counted = {c: int(gb.metrics.counter(c)) for c in NEEDS}
    counted["efb_bundles"] = int(gb.metrics.counter("efb_bundles"))
    for key in ("cat_features", "cat_subset_features", "efb_bundles"):
        program.require(counted[key] == int(want[key]),
                        f"{key}: expected {want[key]}, counted {counted[key]}")
    program.require(gb.bundle is None, "the program bundled columns")
    per_tree = [int((np.asarray(t.decision_type[:t.num_leaves - 1]) & 1).sum())
                for t in gb.models]
    program.require(sum(per_tree) == counted["cat_splits"],
                    f"{sum(per_tree)} categorical nodes in the trees, "
                    f"counted {counted['cat_splits']}")
    if on_tpu:
        program.require(counted["fused_partition_declined"] == 0,
                        f"{counted['fused_partition_declined']} rounds' "
                        "partition took the XLA path")
        none = [i for i, c in enumerate(per_tree) if c == 0]
        program.require(not none, f"trees without a categorical split: {none}")
        leaves = int(want["leaves"])
        short = [i for i, t in enumerate(gb.models) if t.num_leaves != leaves]
        program.require(not short, f"trees short of {leaves} leaves: {short}")
    splits = sum(t.num_leaves - 1 for t in gb.models)
    return {**got, **counted, "splits": int(splits)}


def plain_trees(models) -> list:
    """``program.plain_trees`` and, for the categorical nodes, what the
    stated model holds: ``is_cat`` per node and ``left_codes``, the raw
    codes of its left set (empty for a numeric node)."""
    out = program.plain_trees(models)
    for plain, t in zip(out, models):
        ni = t.num_leaves - 1
        is_cat = (np.asarray(t.decision_type[:ni]) & 1) > 0
        plain["is_cat"] = is_cat
        plain["left_codes"] = [
            np.asarray(sorted(t.cat_threshold[int(t.cat_split_index[i])]),
                       np.int64) if is_cat[i] else np.zeros(0, np.int64)
            for i in range(ni)]
    return out


def measure(ctx, state: dict) -> dict:
    before = {c: program.global_counter(c) for c in NEEDS}
    out = load_module("drivers", "train_jobs").measure(ctx, state)
    out["run"]["setup_spans_s"] = state["setup_spans_s"]
    # what the window's dispatches counted, and the job's own counts
    out["run"]["cat_window"] = {c: program.global_counter(c) - before[c]
                                for c in NEEDS}
    out["run"]["cat_counts"] = {**state["path"], "rows": int(ctx.cfg["rows"])}
    return out


def collect(ctx, state: dict):
    """The window's last job: its trees with their sets of raw codes, the
    AUC it recorded each round and the training scores it holds; then
    everything of the program's goes from the device."""
    last = state.pop("last")
    path = check_path(last, ctx.cfg, state["last_rounds"], state["dispatch"],
                      ctx.on_tpu)
    answers = {"trees": plain_trees(last._gbdt.models),
               "valid_auc": state["aucs"],
               "train_scores": program.train_scores(last)}
    del last
    state.pop("ds"), state.pop("dv")
    return answers, state["inputs"], {"path": state["path"], "last_job": path,
                                      "setup_spans_s": state["setup_spans_s"],
                                      "valid_auc": answers["valid_auc"],
                                      "bytes_in_use_after_free":
                                      program.free_everything()}
