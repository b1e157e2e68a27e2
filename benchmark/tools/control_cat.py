"""Readings for the limits of ``correct`` in a cell with categorical
columns (``tools/control.py`` and ``tools/control_csr.py`` drive the other
cells' drivers and hand no ``categorical_feature`` over), in one process
on the configuration's own data set, made once and binned once for the
sound jobs; each job is ``--dispatches`` dispatches through the cell's
own driver's path check, judged by the cell's comparison and limits:

``--sound [k=v,...]``  a sound job, with parameters over the
    configuration's where given;
``--fault <name>``  a job with a fault of ``tools/faults_cat.py`` planted
    in the program (one that acts on the binning constructs its own
    ``Dataset`` under the fault);
``--control``  the CONTROL: the plain reference put in the program's
    place in bfloat16 (the precision below the float32 the program holds
    scores, gradients and leaf values in), on the first ``--rounds`` trees
    of this process's first sound job.

A fault and the control each have to come out as not correct.

    python3 benchmark/tools/control_cat.py --workload allstate-cat-train \\
        [--sound] [--fault fold_unbinned_levels] [--fault shift_left_sets] \\
        [--fault drop_cat_l2] [--fault skip_descending] \\
        [--fault widen_left_sets] [--control] [--rounds 8] \\
        [--dispatches 1] [--rehearse-cpu]

One JSON line each: the numbers, each beside its limit, and which fail.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))
sys.path.insert(2, os.path.join(HERE, "tools"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sound", action="append", nargs="?", const="", default=[])
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--dispatches", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    import faults_cat
    import run as bench
    from control_csr import floor_at, parsed, report
    from faults import Planted
    from harness import load_module, program
    _, cell, cfg, traffic = bench.find_cell(args.workload, args.rehearse_cpu)
    program.place_compile_cache(bench.ROOT)
    device = program.open_device(int(cell["chips"]), args.rehearse_cpu)
    driver = load_module("drivers", traffic["driver"])
    ctx = bench.Context(argparse.Namespace(seed=0, seconds=0.0,
                                           rehearse_cpu=args.rehearse_cpu),
                        cell, cfg, traffic, device["platform"] == "tpu")
    (xt32, xt64, y), (xv32, xv64, yv) = driver.make_data(ctx)
    inputs = {"train": (xt32, y), "valid": (xv32, yv)}
    ref = load_module("reference", cfg["reference"])
    comparison = load_module("comparisons", cfg["comparison"])

    import lightgbm_tpu as lgb
    params = {**cfg["params"], **traffic.get("params", {})}
    rounds, dispatch = (int(traffic["num_boost_round"]),
                        int(traffic["dispatch_rounds"]))
    columns = [int(c) for c in cfg["categorical"]["columns"]]

    def construct():
        ds = lgb.Dataset(xt64.T, label=y, params=params,
                         categorical_feature=columns).construct()
        return ds, ds.create_valid(xv64.T, label=yv).construct()
    # binned once: most faults are planted in the learner, a parameter
    # given here is the booster's, and neither moves a bin
    sets = construct()

    def job(kind: str, plant_it=None, **over) -> dict:
        with Planted() as plant:
            if plant_it is not None:
                plant_it(plant)
            ds, dv = construct() if getattr(plant_it, "rebins", False) else sets
            bst, aucs, n = program.run_job(lgb, {**params, **over}, ds, dv,
                                           rounds, dispatch, 0.0,
                                           at_least=args.dispatches)
            path = driver.check_path(bst, cfg, n, dispatch, ctx.on_tpu)
            answers = {"trees": driver.plain_trees(bst._gbdt.models),
                       "valid_auc": aucs,
                       "train_scores": program.train_scores(bst)}
            del bst, ds, dv
            program.free_everything()
        how = floor_at(cfg, n)
        report(kind, how, comparison.gaps(ref, how, answers, inputs, 0),
               rounds=n, cat_splits=path["cat_splits"], splits=path["splits"],
               **({"params": over} if over else {}))
        return answers

    first = None
    for pairs in args.sound:
        answers = job("sound", **parsed(pairs))
        first = first or answers
    for name in args.fault:
        job(name, getattr(faults_cat, name))
    if args.control:
        import jax.numpy as jnp
        program.require(first is not None, "--control follows a --sound job")
        trees = first["trees"][:args.rounds]
        how = floor_at(cfg, len(trees))
        answers = comparison.control_answers(ref, how, {"trees": trees}, inputs,
                                             jnp.bfloat16)
        report("control_bfloat16", how,
               comparison.gaps(ref, how, answers, inputs, 0), rounds=len(trees))
    return 0


if __name__ == "__main__":
    sys.exit(main())
