"""Traffic driver ``train_jobs_rank`` (a traffic file names it: ``"driver":
"train_jobs_rank"``): ``train_jobs``'s closed loop of ``lgb.train`` jobs
on a query-grouped ``Dataset`` (``group=`` the query lengths) under a
ranking objective, the held-out NDCG at every cut-off recorded each
round.

The protocol is ``train_jobs``'s (``prepare``, ``measure``, ``collect``;
drivers/train_jobs.py says what each returns).  The job loop is this
file's own: ``program.run_job`` reads ONE recorded series under the name
``params["metric"]`` gives, and an NDCG job records ``ndcg@1`` ...
``ndcg@10``.  The path check is ``program.check_path`` (every round in
the fused scan, the split batch and histogram type the configuration
expects, the state on the TPU) plus what this cell is about: the queries
and docs the configuration states, counted by the program, and as many
NDCG values a round as ``eval_at`` has cut-offs.  Trees are NOT required
to reach ``num_leaves``: ``min_sum_hessian_in_leaf`` may stop one short,
as the published job's do; the leaves of every tree are recorded.

A program that does not count ``rank_queries``, ``rank_docs``,
``rank_slot_rows`` and ``rank_pair_slots`` cannot be checked here: the run
is refused at once, before any data is made.
"""

from __future__ import annotations

import time

import numpy as np

from harness import load_module, program

NEEDS = ("rank_queries", "rank_docs", "rank_slot_rows", "rank_pair_slots")


def make_data(ctx):
    """Training and valid parts as ``(xt64 [F, n], y, sizes)`` by the
    generator the configuration names."""
    cfg = ctx.cfg
    gen, features = load_module("datagen", cfg["data"]["generator"]), int(cfg["features"])
    return (gen.make(cfg["data"], ctx.seed, 0, int(cfg["rows"]), features),
            gen.make(cfg["data"], ctx.seed, 1, int(cfg["valid_rows"]), features))


def construct(lgb, params: dict, train, valid):
    """``lgb.Dataset(X, label=y, group=sizes)`` on the float64
    feature-major matrix (a no-copy view for the program), then the valid
    set on its mappers."""
    (xt, y, sizes), (xv, yv, sizes_v) = train, valid
    ds = lgb.Dataset(xt.T, label=y, group=sizes, params=params).construct()
    return ds, ds.create_valid(xv.T, label=yv, group=sizes_v).construct()


def run_job(lgb, params: dict, ds, dv, rounds: int, dispatch: int,
            deadline: float, on_trees=None, at_least: int = 1):
    """``program.run_job`` for a job that records one series a cut-off:
    returns the booster, ``{k: NDCG@k per round}``, the rounds done."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.callback import EarlyStopException
    chunks = GBDT.fused_chunks(rounds)
    program.require(set(chunks) == {min(dispatch, rounds)},
                    f"num_boost_round={rounds} gives dispatches "
                    f"{sorted(set(chunks))}, the traffic file says {dispatch}")
    evals: dict = {}
    done = [0]

    def boundary(env):
        done[0] = env.iteration + 1
        if env.iteration % dispatch == 0 and on_trees is not None:
            on_trees()
        if done[0] % dispatch == 0 and done[0] < rounds \
                and done[0] >= at_least * dispatch and time.time() >= deadline:
            raise EarlyStopException(env.iteration, env.evaluation_result_list)
    boundary.order = 90
    boundary.fused_safe = True       # reads the clock, changes nothing

    bst = lgb.train(params, ds, num_boost_round=rounds, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(evals), boundary])
    program.require(len(bst._gbdt.models) == done[0],
                    f"{len(bst._gbdt.models)} trees after {done[0]} rounds")
    ndcg = {int(k): list(evals["valid_0"][f"ndcg@{int(k)}"])
            for k in params["eval_at"]}
    return bst, ndcg, done[0]


def check_path(bst, ndcg: dict, cfg: dict, rounds: int, dispatch: int,
               on_tpu: bool) -> dict:
    """``program.check_path``, and the ranking job: the program counted
    the configuration's queries and docs, once, and recorded one NDCG
    value a cut-off a round."""
    got = program.check_path(bst, cfg, rounds, dispatch, on_tpu)
    gb = bst._gbdt
    counted = {c: int(gb.metrics.counter(c)) for c in NEEDS}
    program.require(
        (counted["rank_queries"], counted["rank_docs"])
        == (int(cfg["queries"]), int(cfg["rows"])),
        f"the configuration has {cfg['queries']} queries of {cfg['rows']} "
        f"docs, the program counted {counted}")
    program.require(
        counted["rank_slot_rows"] >= counted["rank_docs"]
        and counted["rank_pair_slots"] >= counted["rank_slot_rows"],
        f"slots under docs or pair slots under slots: {counted}")
    program.require(all(len(v) == rounds for v in ndcg.values())
                    and len(ndcg) == len(cfg["params"]["eval_at"]),
                    f"{rounds} rounds, NDCG recorded: "
                    f"{ {k: len(v) for k, v in ndcg.items()} }")
    return {**got, **counted,
            "rank_bucket_count": gb.metrics.gauge("rank_bucket_count"),
            "rank_pad_rows": gb.metrics.gauge("rank_pad_rows")}


def leaves_of(bst) -> list:
    return [int(t.num_leaves) for t in bst._gbdt.models]


def prepare(ctx) -> dict:
    try:
        from lightgbm_tpu.obs.metrics import COUNTERS
    except ImportError as e:
        raise program.Refused(f"the program has no counters: {e}")
    missing = [c for c in NEEDS if c not in COUNTERS]
    if missing:
        raise program.Refused(
            f"the program does not count {missing}: a ranking job's "
            "queries and pair slots cannot be checked against this cell's path")
    import lightgbm_tpu as lgb
    params = {**ctx.cfg["params"], **ctx.traffic.get("params", {})}
    rounds = int(ctx.traffic["num_boost_round"])
    dispatch = int(ctx.traffic["dispatch_rounds"])
    program.require(ctx.traffic["valid_set"], "this cell has a valid set")

    t = time.time()
    train, valid = make_data(ctx)
    ctx.phases["data_s"] = time.time() - t

    t = time.time()
    ds, dv = construct(lgb, params, train, valid)
    ctx.phases["construct_s"] = time.time() - t

    t = time.time()
    # two dispatches: a job's second one compiles again (PERF.md,
    # Set-up), and nothing may compile inside the window
    warm_marks = []
    bst, ndcg, n = run_job(lgb, params, ds, dv, rounds, dispatch, 0.0,
                           lambda: warm_marks.append(time.time()), at_least=2)
    took = check_path(bst, ndcg, ctx.cfg, n, dispatch, ctx.on_tpu)
    del bst
    ctx.phases["warmup_s"] = time.time() - t
    ctx.phases["warmup_first_dispatch_s"] = warm_marks[0] - t
    return {"lgb": lgb, "params": params, "rounds": rounds, "dispatch": dispatch,
            "ds": ds, "dv": dv, "path": took,
            "inputs": {"train": train, "valid": valid}}


def measure(ctx, state: dict) -> dict:
    import jax
    lgb, params = state["lgb"], state["params"]
    rounds, dispatch = state["rounds"], state["dispatch"]
    job_s, done, last, ndcg, marks, grown = [], 0, None, None, [], []
    mark = lambda: marks.append(time.time())
    t0 = time.time()
    while True:
        last = None                 # the booster before goes, as a user's would
        tj = time.time()
        with jax.profiler.TraceAnnotation("bench.job"):
            last, ndcg, n = run_job(lgb, params, state["ds"], state["dv"],
                                    rounds, dispatch, t0 + ctx.seconds, mark)
        now = time.time()
        job_s.append(now - tj)
        done += n
        grown += leaves_of(last)
        if now - t0 >= ctx.seconds:
            break
    window_s = now - t0
    state["last"], state["last_rounds"], state["ndcg"] = last, n, ndcg
    dispatch_s = [b - a for a, b in zip([t0] + marks, marks)]
    leaves = {"median": float(np.median(grown)), "min": min(grown),
              "max": max(grown)}
    return {"window_s": window_s, "attempted": done, "failed": 0,
            "end_to_end": {"train_round_ms": 1000.0 * window_s / done},
            "run": {"rounds": done, "dispatch_s": dispatch_s,
                    "rank_counts": {c: state["path"][c] for c in NEEDS}},
            "log": {"rounds": done, "job_s": [round(x, 3) for x in job_s],
                    "dispatch_s": [round(x, 3) for x in dispatch_s],
                    "leaves_per_tree": leaves}}


def collect(ctx, state: dict):
    """The window's last job: its trees, the NDCG it recorded each round
    at each cut-off and the training scores it holds; then everything of
    the program's goes from the device."""
    last = state.pop("last")
    check_path(last, state["ndcg"], ctx.cfg, state["last_rounds"],
               state["dispatch"], ctx.on_tpu)
    answers = {"trees": program.plain_trees(last._gbdt.models),
               "valid_ndcg": state["ndcg"],
               "train_scores": program.train_scores(last)}
    del last
    state.pop("ds"), state.pop("dv")
    return answers, state["inputs"], {
        "path": state["path"],
        "valid_ndcg": {str(k): v for k, v in answers["valid_ndcg"].items()},
        "leaves": [t["num_leaves"] for t in answers["trees"]],
        "bytes_in_use_after_free": program.free_everything()}
