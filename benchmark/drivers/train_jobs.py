"""Traffic driver ``train_jobs`` (a traffic file names it: ``"driver":
"train_jobs"``): ``lgb.train`` jobs on one constructed ``Dataset``, each
dispatched ``dispatch_rounds`` rounds at a time and stopped by a callback
of the harness at the first dispatch boundary after ``--seconds``.

A driver is the part of a run that knows one kind of traffic.  run.py
calls, in this order:

``prepare(ctx)``        data, set-up of the system, warm-up of every shape
                        the window uses; fills ``ctx.phases``; returns its
                        state
``measure(ctx, state)`` the measured window; returns ``window_s``,
                        ``attempted``, ``failed``, the ``end_to_end``
                        values other than ``setup_s``, what the per-layer
                        readers need (``run``) and a ``log`` to print
``collect(ctx, state)`` the answers of the timed path as plain arrays and
                        the raw inputs the reference needs; frees what the
                        program holds on the device

A later kind of traffic (batch predict, serving) brings a file beside
this one and edits nothing here.
"""

from __future__ import annotations

import time

from harness import load_module, program


def make_data(ctx):
    """Training and valid parts by the generator the configuration names:
    ``(xt32, xt64, y)`` each, ``None``s without a valid set."""
    cfg = ctx.cfg
    gen, features = load_module("datagen", cfg["data"]["generator"]), int(cfg["features"])
    train = gen.make(cfg["data"], ctx.seed, 0, int(cfg["rows"]), features)
    if not ctx.traffic["valid_set"]:
        return train, (None, None, None)
    return train, gen.make(cfg["data"], ctx.seed, 1, int(cfg["valid_rows"]), features)


def prepare(ctx) -> dict:
    import lightgbm_tpu as lgb
    params = {**ctx.cfg["params"], **ctx.traffic.get("params", {})}
    rounds = int(ctx.traffic["num_boost_round"])
    dispatch = int(ctx.traffic["dispatch_rounds"])

    t = time.time()
    (xt32, xt64, y), (xv32, xv64, yv) = make_data(ctx)
    ctx.phases["data_s"] = time.time() - t

    t = time.time()
    ds, dv = program.construct(lgb, params, (xt64, y), (xv64, yv))
    del xt64, xv64
    ctx.phases["construct_s"] = time.time() - t

    t = time.time()
    # two dispatches: a job's second one compiles again (its scores come
    # back from the first committed to the device: PERF.md, Set-up), and
    # nothing may compile inside the window
    warm_marks = []
    bst, _, n = program.run_job(lgb, params, ds, dv, rounds, dispatch, 0.0,
                                lambda: warm_marks.append(time.time()),
                                at_least=2)
    took = program.check_path(bst, ctx.cfg, n, dispatch, ctx.on_tpu)
    del bst
    ctx.phases["warmup_s"] = time.time() - t
    ctx.phases["warmup_first_dispatch_s"] = warm_marks[0] - t
    return {"lgb": lgb, "params": params, "rounds": rounds, "dispatch": dispatch,
            "ds": ds, "dv": dv, "path": took,
            "inputs": {"train": (xt32, y), "valid": (xv32, yv)}}


def measure(ctx, state: dict) -> dict:
    import jax
    lgb, params = state["lgb"], state["params"]
    rounds, dispatch = state["rounds"], state["dispatch"]
    job_s, done, last, aucs, marks = [], 0, None, None, []
    mark = lambda: marks.append(time.time())
    t0 = time.time()
    while True:
        last = None                 # the booster before goes, as a user's would
        tj = time.time()
        with jax.profiler.TraceAnnotation("bench.job"):
            last, aucs, n = program.run_job(
                lgb, params, state["ds"], state["dv"], rounds, dispatch,
                t0 + ctx.seconds, mark)
        now = time.time()
        job_s.append(now - tj)
        done += n
        if now - t0 >= ctx.seconds:
            break
    window_s = now - t0
    state["last"], state["last_rounds"], state["aucs"] = last, n, aucs
    dispatch_s = [b - a for a, b in zip([t0] + marks, marks)]
    return {"window_s": window_s, "attempted": done, "failed": 0,
            "end_to_end": {"train_round_ms": 1000.0 * window_s / done},
            "run": {"rounds": done, "dispatch_s": dispatch_s},
            "log": {"rounds": done, "job_s": [round(x, 3) for x in job_s],
                    "dispatch_s": [round(x, 3) for x in dispatch_s]}}


def collect(ctx, state: dict):
    """The window's last job: its trees, the AUC it recorded each round
    and the training scores it holds; then everything of the program's
    goes from the device."""
    last = state.pop("last")
    program.check_path(last, ctx.cfg, state["last_rounds"], state["dispatch"],
                       ctx.on_tpu)
    answers = {"trees": program.plain_trees(last._gbdt.models),
               "valid_auc": state["aucs"],
               "train_scores": program.train_scores(last)}
    del last
    state.pop("ds"), state.pop("dv")
    return answers, state["inputs"], {"path": state["path"],
                                      "valid_auc": answers["valid_auc"],
                                      "bytes_in_use_after_free":
                                      program.free_everything()}
