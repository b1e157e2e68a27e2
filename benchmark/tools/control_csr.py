"""Readings for the limits of ``correct`` in a cell of sparse rows
(``tools/control.py`` drives the dense cells' driver and cannot make this
one's data), in one process on the configuration's own data set, made and
binned once; each job is ``--dispatches`` dispatches through the cell's
own driver's path check, judged by the cell's comparison and limits:

``--sound [k=v,...]``  a sound job, with parameters over the
    configuration's where given (``num_grad_quant_bins=64``: what the
    split search gives away to the gradients' quantisation);
``--fault <name>``  a job with a fault of ``tools/faults_efb.py`` planted
    in the program;
``--control``  the CONTROL: the plain reference put in the program's
    place in bfloat16 (the precision below the float32 the program holds
    scores, gradients and leaf values in), on the first ``--rounds`` trees
    of this process's first sound job (the control follows tree t once
    for every tree up to t).

A fault and the control each have to come out as not correct.

    python3 benchmark/tools/control_csr.py --workload <cell> \\
        [--sound] [--sound num_grad_quant_bins=64] \\
        [--fault shift_member_segments] [--fault skip_odd_features] \\
        [--control] [--rounds 8] [--dispatches 1] [--rehearse-cpu]

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


def report(kind: str, cfg: dict, numbers: dict, **more) -> None:
    from harness import compare
    correct, compared = compare.judge(numbers, cfg["limits"])
    print(json.dumps({"kind": kind, **more, "correct": correct,
                      "fails": [k for k, c in compared.items()
                                if c["value"] is None or c["value"] > c["limit"]],
                      "compared": compared,
                      "split_regret_max": numbers["split_regret_max"]}),
          flush=True)


def floor_at(cfg: dict, rounds: int) -> dict:
    """The configuration with the floor under the AUC taken no later
    than the job's last round."""
    how = cfg["compare"]
    return dict(cfg, compare=dict(how, auc_floor=dict(
        how["auc_floor"], round=min(rounds, int(how["auc_floor"]["round"])))))


def parsed(pairs: str) -> dict:
    """``k=v,k=v`` as parameters, numbers where they read as numbers."""
    out = {}
    for pair in filter(None, pairs.split(",")):
        k, v = pair.split("=", 1)
        out[k] = json.loads(v) if v.lstrip("-")[:1].isdigit() else v
    return out


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
    import faults_efb
    import run as bench
    from faults import Planted
    from harness import load_module, program
    _, cell, cfg, traffic = bench.find_cell(args.workload, args.rehearse_cpu)
    program.place_compile_cache(bench.ROOT)
    device = program.open_device(int(cell["chips"]), args.rehearse_cpu)
    driver = load_module("drivers", traffic["driver"])
    ctx = bench.Context(argparse.Namespace(seed=0, seconds=0.0,
                                           rehearse_cpu=args.rehearse_cpu),
                        cell, cfg, traffic, device["platform"] == "tpu")
    (xt, y), (xv, yv) = driver.make_data(ctx)
    inputs = {"train": (xt, y), "valid": (xv, yv)}
    ref = load_module("reference", cfg["reference"])
    comparison = load_module("comparisons", cfg["comparison"])

    import lightgbm_tpu as lgb
    params = {**cfg["params"], **traffic.get("params", {})}
    rounds, dispatch = (int(traffic["num_boost_round"]),
                        int(traffic["dispatch_rounds"]))
    # binned once: a fault is planted in the program, a parameter given
    # here is the booster's, and neither moves a bin
    ds = lgb.Dataset(xt, label=y, params=params).construct()
    dv = ds.create_valid(xv, label=yv).construct()

    def job(kind: str, plant_it=None, **over) -> dict:
        with Planted() as plant:
            if plant_it is not None:
                plant_it(plant)
            bst, aucs, n = program.run_job(lgb, {**params, **over}, ds, dv,
                                           rounds, dispatch, 0.0,
                                           at_least=args.dispatches)
            driver.check_path(bst, cfg, n, dispatch, ctx.on_tpu)
            answers = {"trees": program.plain_trees(bst._gbdt.models),
                       "valid_auc": aucs,
                       "train_scores": program.train_scores(bst)}
            del bst
            program.free_everything()
        how = floor_at(cfg, n)
        report(kind, how, comparison.gaps(ref, how, answers, inputs, 0),
               rounds=n, **({"params": over} if over else {}))
        return answers

    first = None
    for pairs in args.sound:
        answers = job("sound", **parsed(pairs))
        first = first or answers
    for name in args.fault:
        job(name, getattr(faults_efb, name))
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
