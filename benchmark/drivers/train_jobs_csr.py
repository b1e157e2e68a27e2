"""Traffic driver ``train_jobs_csr`` (a traffic file names it: ``"driver":
"train_jobs_csr"``): ``train_jobs``'s closed loop of ``lgb.train`` jobs,
on a ``Dataset`` constructed from scipy CSR rows whose sparse columns the
program packs into feature bundles (EFB).

The protocol is ``train_jobs``'s (``prepare``, ``measure``, ``collect``;
drivers/train_jobs.py says what each returns), the job is
``program.run_job``'s, the window is ``train_jobs.measure`` itself and
the answers are the same.  What differs: the data is made sparse and
stays sparse (the generator returns CSR, ``lgb.Dataset`` takes it, the
reference gets it back), and the path check adds what this cell is
about — the bundles the configuration's schema gives, every round's
split search in bundle space and no expansion of a bundle histogram to
virtual-feature space anywhere in the process.

A program that does not count ``efb_bundles`` and
``bundle_space_search_rounds`` cannot be checked here: the run is refused
at once, before any data is made.
"""

from __future__ import annotations

import time

from harness import load_module, program

NEEDS = ("efb_bundles", "bundle_space_search_rounds", "bundle_expand_calls")
SPANS = ("construct", "sparse_bin_mappers", "bundle_plan", "bundle_matrix")


def make_data(ctx):
    """Training and valid parts as ``(csr, y)`` by the generator the
    configuration names."""
    cfg = ctx.cfg
    gen, features = load_module("datagen", cfg["data"]["generator"]), int(cfg["features"])
    return (gen.make(cfg["data"], ctx.seed, 0, int(cfg["rows"]), features),
            gen.make(cfg["data"], ctx.seed, 1, int(cfg["valid_rows"]), features))


def prepare(ctx) -> dict:
    from lightgbm_tpu.obs.metrics import COUNTERS
    missing = [c for c in NEEDS if c not in COUNTERS]
    if missing:
        raise program.Refused(
            f"the program does not count {missing}: a bundled job's split "
            "search cannot be checked against this cell's path")
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.timer import global_timer
    params = {**ctx.cfg["params"], **ctx.traffic.get("params", {})}
    rounds = int(ctx.traffic["num_boost_round"])
    dispatch = int(ctx.traffic["dispatch_rounds"])
    program.require(ctx.traffic["valid_set"], "this cell has a valid set")

    t = time.time()
    (xt, y), (xv, yv) = make_data(ctx)
    ctx.phases["data_s"] = time.time() - t

    t = time.time()
    global_timer.enable()       # the program's own spans inside construct
    ds = lgb.Dataset(xt, label=y, params=params).construct()
    lost_train = program.global_counter("efb_conflict_rows")
    dv = ds.create_valid(xv, label=yv).construct()
    global_timer.disable()
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
    took["efb_conflict_rows"] = {"train": lost_train, "valid":
                                 program.global_counter("efb_conflict_rows")
                                 - lost_train}
    return {"lgb": lgb, "params": params, "rounds": rounds, "dispatch": dispatch,
            "ds": ds, "dv": dv, "path": took, "setup_spans_s": spans,
            "inputs": {"train": (xt, y), "valid": (xv, yv)}}


def check_path(bst, cfg: dict, rounds: int, dispatch: int, on_tpu: bool) -> dict:
    """``program.check_path`` (every round in the fused scan, the split
    batch and histogram type the configuration expects, the state on the
    TPU), and the bundles: as many as the configuration's schema gives,
    over all its features; every round's split search in bundle space;
    no bundle histogram expanded to virtual-feature space, in any program
    this process traced; on the chip, every tree at the leaves the
    configuration's ``efb.leaves`` says (tools/variant_csr.py leaves the
    key out where it asks what other labels would grow)."""
    got = program.check_path(bst, cfg, rounds, dispatch, on_tpu)
    gb, want = bst._gbdt, cfg["efb"]
    counted = {c: int(gb.metrics.counter(c)) for c in
               ("efb_bundles", "efb_features", "bundle_space_search_rounds")}
    counted["bundle_expand_calls"] = int(
        program.global_counter("bundle_expand_calls"))
    program.require(
        counted == {"efb_bundles": int(want["bundles"]),
                    "efb_features": int(want["features"]),
                    "bundle_space_search_rounds": rounds,
                    "bundle_expand_calls": 0},
        f"{rounds} rounds over {want}, counted {counted}")
    if on_tpu and "leaves" in want:
        leaves = int(want["leaves"])
        short = [i for i, t in enumerate(gb.models) if t.num_leaves != leaves]
        program.require(not short, f"trees short of {leaves} leaves: {short}")
    return {**got, **counted}


def measure(ctx, state: dict) -> dict:
    out = load_module("drivers", "train_jobs").measure(ctx, state)
    out["run"]["setup_spans_s"] = state["setup_spans_s"]
    return out


def collect(ctx, state: dict):
    """The window's last job: its trees, the AUC it recorded each round
    and the training scores it holds; then everything of the program's
    goes from the device."""
    last = state.pop("last")
    check_path(last, ctx.cfg, state["last_rounds"], state["dispatch"], ctx.on_tpu)
    answers = {"trees": program.plain_trees(last._gbdt.models),
               "valid_auc": state["aucs"],
               "train_scores": program.train_scores(last)}
    del last
    state.pop("ds"), state.pop("dv")
    return answers, state["inputs"], {"path": state["path"],
                                      "setup_spans_s": state["setup_spans_s"],
                                      "valid_auc": answers["valid_auc"],
                                      "bytes_in_use_after_free":
                                      program.free_everything()}
