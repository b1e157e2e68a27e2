"""Readings for the limits of ``correct`` in a ranking cell
(``tools/control.py`` drives the dense cells' driver and knows no query),
in one process on the configuration's own data set, made and binned once;
each job is ``--dispatches`` dispatches through the cell's own driver's
path check, judged by the cell's comparison and limits:

``--sound [k=v,...]``  a sound job, with parameters over the
    configuration's where given (``--all-trees``: every tree's splits
    searched, each tree's own regret printed);
``--fault <name>``  a job with a fault of ``tools/faults_rank.py`` (or a
    histogram fault of ``tools/faults.py``, on the data's first
    informative feature) planted in the program;
``--control``  the CONTROL: the plain reference put in the program's
    place in bfloat16 (the precision below the float32 the program holds
    scores, gradients and leaf values in), on the first ``--rounds`` trees
    of this process's first sound job.

A fault and the control each have to come out as not correct.

    python3 benchmark/tools/control_rank.py --workload <cell> [--sound] \\
        [--fault no_normalisation] ... [--control] [--rounds 8] \\
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


def report(kind: str, cfg: dict, numbers: dict, **more) -> dict:
    from harness import compare
    correct, compared = compare.judge(numbers, cfg["limits"])
    line = {"kind": kind, **more, "correct": correct,
            "fails": [k for k, c in compared.items()
                      if c["value"] is None or c["value"] > c["limit"]],
            "compared": compared,
            "not_compared": {k: numbers.get(k) for k in
                             ("leaf_value_gap", "split_regret_max",
                              "split_regret_by_tree",
                              "stated_splits_short",
                              "ref_valid_ndcg_last", "leaves_median",
                              "leaves_min")}}
    print(json.dumps(line), flush=True)
    return line


def floor_at(cfg: dict, rounds: int) -> dict:
    """The configuration with the floor under the NDCG taken no later
    than the job's last round."""
    how = cfg["compare"]
    return dict(cfg, compare=dict(how, ndcg_floor=dict(
        how["ndcg_floor"], round=min(rounds, int(how["ndcg_floor"]["round"])))))


def session(workload: str, rehearse_cpu: bool):
    """The cell's data, made and binned once, and ``job(kind, plant_it,
    dispatches, **over)``: one job judged by the cell's comparison."""
    import run as bench
    from faults import Planted
    from harness import load_module, program
    _, cell, cfg, traffic = bench.find_cell(workload, rehearse_cpu)
    program.place_compile_cache(bench.ROOT)
    device = program.open_device(int(cell["chips"]), rehearse_cpu)
    driver = load_module("drivers", traffic["driver"])
    ctx = bench.Context(argparse.Namespace(seed=0, seconds=0.0,
                                           rehearse_cpu=rehearse_cpu),
                        cell, cfg, traffic, device["platform"] == "tpu")
    train, valid = driver.make_data(ctx)
    inputs = {"train": train, "valid": valid}
    ref = load_module("reference", cfg["reference"])
    comparison = load_module("comparisons", cfg["comparison"])

    import lightgbm_tpu as lgb
    params = {**cfg["params"], **traffic.get("params", {})}
    rounds, dispatch = (int(traffic["num_boost_round"]),
                        int(traffic["dispatch_rounds"]))
    # binned once: a fault is planted in the program, a parameter given
    # here is the booster's, and neither moves a bin
    ds, dv = driver.construct(lgb, params, train, valid)

    def job(kind: str, plant_it=None, dispatches: int = 1,
            split_trees="configured", **over):
        with Planted() as plant:
            if plant_it is not None:
                program.free_everything()
                plant_it(plant)
            bst, ndcg, n = driver.run_job(lgb, {**params, **over}, ds, dv,
                                          rounds, dispatch, 0.0,
                                          at_least=dispatches)
            driver.check_path(bst, ndcg, cfg, n, dispatch, ctx.on_tpu)
            answers = {"trees": program.plain_trees(bst._gbdt.models),
                       "valid_ndcg": ndcg,
                       "train_scores": program.train_scores(bst)}
            del bst
            program.free_everything()
        how = floor_at(cfg, n)
        line = report(kind, how, comparison.gaps(ref, how, answers, inputs, 0,
                                                 split_trees),
                      rounds=n, **({"params": over} if over else {}))
        return answers, line

    def control(answers: dict, rounds_: int, dtype):
        trees = answers["trees"][:rounds_]
        how = floor_at(cfg, len(trees))
        out = comparison.control_answers(ref, how, {"trees": trees}, inputs,
                                         dtype)
        return report("control_" + getattr(dtype, "__name__", str(dtype)), how,
                      comparison.gaps(ref, how, out, inputs, 0),
                      rounds=len(trees))
    return cfg, job, control


def fault_named(name: str, cfg: dict):
    import faults
    import faults_rank
    from harness import load_module
    if hasattr(faults_rank, name):
        return getattr(faults_rank, name)
    gen = load_module("datagen", cfg["data"]["generator"])
    feature = int(abs(gen.weights(cfg["data"], int(cfg["features"]))).argmax())
    return lambda plant: faults.HISTOGRAM[name](plant, feature)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sound", action="append", nargs="?", const="", default=[])
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--dispatches", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--all-trees", action="store_true",
                    help="search every tree's splits in the sound jobs, "
                         "not the configured few: what any --seed can draw")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    from control_csr import parsed      # ``k=v,k=v`` as parameters
    cfg, job, control = session(args.workload, args.rehearse_cpu)
    first = None
    for pairs in args.sound:
        answers, _ = job("sound", dispatches=args.dispatches,
                         split_trees=None if args.all_trees else "configured",
                         **parsed(pairs))
        first = first or answers
    for name in args.fault:
        job(name, fault_named(name, cfg))
    if args.control:
        import jax.numpy as jnp
        from harness import program
        program.require(first is not None, "--control follows a --sound job")
        control(first, args.rounds, jnp.bfloat16)
    return 0


if __name__ == "__main__":
    sys.exit(main())
