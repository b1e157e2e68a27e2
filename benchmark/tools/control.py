"""Readings for the limits of ``correct``: in one process, over several
data sets, the program's gaps against the plain reference (sound), and
what the same comparison reads where something is at fault: a fault
planted in the program's histograms (tools/faults.py), and the control,
the reference put in the program's place in bfloat16 (the precision
below the float32 the program holds scores, gradients and leaf values
in).  A fault and the control have to come out as not correct.

    python3 benchmark/tools/control.py --workload <cell> --data cfg 301:0.034 \\
        --dispatches 3 1 --faults hist_zero_feature [--bf16] [--rehearse-cpu]

``--data``: one entry a data set, ``cfg`` for the configuration's own
``base_seed`` or another number, with ``:<pos_rate>`` to override the
label rate.  Each data set: data, ``Dataset.construct``, one job of the
cell's traffic of ``--dispatches`` dispatches at the cell's own size (no
measured window), the comparison with every tree's splits searched; then
for each fault the same job of one dispatch with the fault planted.  One
JSON line per data set, then a summary: per number the largest of the
sound runs and the smallest each fault and the control give.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))
sys.path.insert(2, os.path.join(HERE, "tools"))


def readings(workload: str, data=("cfg",), dispatches=(1,), faults=(),
             bf16: bool = False, rehearse_cpu: bool = False):
    import jax.numpy as jnp
    import faults as planted
    import run as bench
    from drivers import train_jobs
    from harness import compare, load_module, program

    manifest, cell, cfg, traffic = bench.find_cell(workload, rehearse_cpu)
    program.place_compile_cache(bench.ROOT)
    device = program.open_device(int(cell["chips"]), rehearse_cpu)
    import lightgbm_tpu as lgb
    params = {**cfg["params"], **traffic.get("params", {})}
    rounds, dispatch = int(traffic["num_boost_round"]), int(traffic["dispatch_rounds"])
    ref = load_module("reference", cfg["reference"])
    comparison = load_module("comparisons", cfg["comparison"])
    gen = load_module("datagen", cfg["data"]["generator"])
    args = argparse.Namespace(seed=0, seconds=0.0, rehearse_cpu=rehearse_cpu)
    rows = []
    for entry, n_dispatch in zip(data, list(dispatches) + [1] * len(data)):
        base, _, rate = str(entry).partition(":")
        spec = dict(cfg["data"])
        if base != "cfg":
            spec["base_seed"] = int(base)
        if rate:
            spec["pos_rate"] = float(rate)
        ctx = bench.Context(args, cell, {**cfg, "data": spec}, traffic,
                            device["platform"] == "tpu")
        (xt32, xt64, y), (xv32, xv64, yv) = train_jobs.make_data(ctx)
        ds, dv = program.construct(lgb, params, (xt64, y), (xv64, yv))
        del xt64, xv64
        inputs = {"train": (xt32, y), "valid": (xv32, yv)}

        def job(dispatches_: int) -> tuple:
            marks = []
            t = time.time()
            bst, aucs, n = program.run_job(lgb, params, ds, dv, rounds, dispatch,
                                           0.0, lambda: marks.append(time.time()),
                                           at_least=dispatches_)
            program.check_path(bst, cfg, n, dispatch, device["platform"] == "tpu")
            answers = {"trees": program.plain_trees(bst._gbdt.models),
                       "valid_auc": aucs, "train_scores": program.train_scores(bst)}
            del bst
            program.free_everything()
            return answers, n, [round(b - a, 3) for a, b in zip([t] + marks, marks)]

        answers, n, took = job(int(n_dispatch))
        t = time.time()
        sound = comparison.gaps(ref, cfg, answers, inputs, 0, split_trees=None)
        row = {"data": str(entry), "pos_rate": spec["pos_rate"], "rounds": n,
               "dispatch_s": took, "compare_s": round(time.time() - t, 3),
               "sound": sound,
               "sound_correct": compare.judge(sound, cfg["limits"])[0]}
        if bf16:
            ctrl = comparison.gaps(ref, cfg, comparison.control_answers(
                ref, cfg, answers, inputs, jnp.bfloat16), inputs, 0,
                split_trees=None)
            row["control"] = ctrl
            row["control_correct"] = compare.judge(ctrl, cfg["limits"])[0]
        target = gen.strongest_feature(spec, int(cfg["features"]))
        for fault in faults:
            with planted.Planted() as plant:
                planted.HISTOGRAM[fault](plant, feature=target)
                broken, n, took = job(1)
            got = comparison.gaps(ref, cfg, broken, inputs, 0, split_trees=None)
            row[fault] = {**got, "feature": target, "dispatch_s": took}
            row[fault + "_correct"] = compare.judge(got, cfg["limits"])[0]
        del ds, dv
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows, cfg["limits"], device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--data", nargs="+", default=["cfg"])
    ap.add_argument("--dispatches", type=int, nargs="+", default=[1])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    rows, limits, device = readings(args.workload, args.data, args.dispatches,
                                    args.faults, args.bf16, args.rehearse_cpu)
    kinds = (["control"] if args.bf16 else []) + list(args.faults)
    numeric = [k for k, v in rows[0]["sound"].items() if isinstance(v, (int, float))]
    summary = {name: {"sound_max": max(r["sound"][name] for r in rows),
                      **{f"{kind}_min": min(r[kind][name] for r in rows)
                         for kind in kinds},
                      "limit": limits.get(name)} for name in numeric}
    print(json.dumps({"workload": args.workload, "device": device,
                      "data_sets": len(rows), "summary": summary,
                      "sound_all_correct": all(r["sound_correct"] for r in rows),
                      **{f"{kind}_ever_correct": any(r[kind + "_correct"] for r in rows)
                         for kind in kinds}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
